"""The comparison that decides ``correct`` fails a broken timed path, and
the float8 control, in the program's place, comes out not correct.

Each fault is planted in the program underneath a whole run on the CPU (the
look for a chip skipped, the program at its reduced size): a token altered
where the decode step produces it (on every eighth step); a decode step that leaves its state (the
cache) unchanged; half of the batch left out of the step. The cells run on
one chip, so there is no exchange between chips to leave out."""
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
from chipbench_testkit import run_small  # noqa: E402

from chipbench import core  # noqa: E402

MAN = core.manifest()
CELLS = [w["name"] for w in MAN["workloads"] if w["chips"] == 1]


def _limit(cell):
    return core.load_config(MAN, core.cell(MAN, cell)["config"])["limits"]["max_logit_gap"]


@pytest.fixture
def fresh_programs():
    from repro.serving.pool import clear_program_caches

    clear_program_caches()
    yield
    clear_program_caches()


def _alter_token(monkeypatch):
    from repro.serving.pool import Pool

    orig = Pool._decode_finish
    calls = [0]

    def finish(self, pre, next_tok, cache, lengths):
        # every eighth decode step hands out a token one above the one chosen
        calls[0] += 1
        if calls[0] % 8 == 0:
            next_tok = (next_tok + 1) % self.cfg.vocab_size
        return orig(self, pre, next_tok, cache, lengths)

    monkeypatch.setattr(Pool, "_decode_finish", finish)


def _stale_state(monkeypatch):
    from repro.models import attention

    monkeypatch.setattr(attention, "_write_at_lengths", lambda buf, new, lengths: buf)


def _half_batch(monkeypatch):
    from repro.serving.pool import Pool

    orig = Pool._decode_finish

    def finish(self, pre, next_tok, cache, lengths):
        # the first half of the slots keep their last token: never computed
        b = next_tok.shape[0]
        kept = jnp.where(jnp.arange(b) >= b // 2, next_tok, jnp.asarray(pre["args"][1]))
        return orig(self, pre, kept, cache, lengths)

    monkeypatch.setattr(Pool, "_decode_finish", finish)


@pytest.mark.parametrize("fault", [_alter_token, _stale_state, _half_batch],
                         ids=["token_altered", "state_unchanged", "half_batch"])
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_timed_path_is_not_correct(cell, fault, monkeypatch, fresh_programs):
    fault(monkeypatch)
    res = run_small(cell, limit=_limit(cell))
    assert res["correct"] is False
    assert res["check"]["max_logit_gap"]["value"] > _limit(cell)


@pytest.mark.parametrize("cell", CELLS)
def test_the_float8_control_fails_the_limit(cell, fresh_programs):
    """The reference in the program's place, one precision down (float8
    e4m3 for the configuration's bfloat16): its first choices at each
    position of the served sequences go through the run's own verdict."""
    res = run_small(cell, limit=_limit(cell), control=True)
    assert res["correct"] is False
    assert res["check"]["max_logit_gap"]["value"] > _limit(cell)
    assert res["program_gap"] <= _limit(cell)
    assert list(res)[-1] == "check"
