"""granite-4.0-h-micro's cell: the work of a step against hand arithmetic,
the decode batch its cache buys, the two readers of the Mamba2 mixers'
device time on a made-up trace, and whole runs of the cell on the CPU at
the program's reduced size (one period: 9 Mamba2 layers and one attention
layer, float32) against the float32 reference, sound and with two planted
faults of the recurrent state."""
import sys
import types
from pathlib import Path

import jax.numpy as jnp
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
from chipbench_testkit import run_small  # noqa: E402

from chipbench import core, tracing  # noqa: E402

MAN = core.manifest()
CELL = "granite-4.0-h-micro.decode-heavy"
GRANITE = core.load_config(MAN, "granite-4.0-h-micro")
WORK = core.work("mamba2_hybrid")
MS = 1e6
# The program and the reference both compute in float32 at the reduced
# size and differ only by float32 rounding of sums taken in another order:
# far under 1e-5 against logits that spread about 0.1 (the sound run reads
# 0.0). A state held in bfloat16 (8 bits of mantissa) moves them by about
# 8e-5, prefill state taken through the padding by about 4e-2. The cell's
# own limit (0.006, for bfloat16 activations at full size) cannot see a
# bfloat16 state: on the chip one reads 0.0016-0.0018, inside the sound
# runs' spread, so this float32 run is where it shows.
LIMIT = 1e-5


def test_a_step_of_granite_by_hand():
    d, v, ff, n_m, n_a = 2048, 100352, 8192, 36, 4
    di, heads, p, n, conv = 4096, 64, 64, 128, 4352
    mamba_mm = d * (2 * di + 2 * n + heads) + di * d
    assert WORK.mamba_matmul_params(GRANITE) == mamba_mm == 25_821_184
    attn = d * 32 * 64 + 2 * d * 8 * 64 + 32 * 64 * d
    assert WORK.attn_matmul_params(GRANITE) == attn == 10_485_760
    mamba_bytes = 2 * (mamba_mm + conv * 5 + di) + 4 * 3 * heads
    layer_bytes = 2 * (3 * d * ff + 2 * d)
    weights = n_m * mamba_bytes + n_a * 2 * attn + 40 * layer_bytes + 2 * (v * d + d)
    assert WORK.weight_bytes(GRANITE) == weights
    # bf16 everywhere but A_log, D and dt_bias: the parameter count's bytes
    from repro.configs import get_config
    params = get_config("granite-4.0-h-micro").param_count()
    assert weights == 2 * params + 2 * n_m * 3 * heads
    state = n_m * (heads * p * n * 4 + 3 * conv * 2)
    assert WORK.state_bytes_per_seq(GRANITE) == state == 76_437_504
    row = n_a * 2 * 8 * 64 * 2
    assert WORK.kv_bytes_per_token(GRANITE) == row == 8192
    tok = 2.0 * (n_m * mamba_mm + n_a * attn + 40 * 3 * d * ff) \
        + n_m * (5.0 * heads * p * n + 2.0 * 4 * conv)
    lengths = [100, 300]
    flops, nbytes = WORK.decode(GRANITE, lengths)
    assert flops == 2 * (tok + 2.0 * v * d) + 4.0 * n_a * 32 * 64 * (101 + 301)
    assert nbytes == weights + 2 * 2 * state + row * 400 + row * 2 + 2 * 2 * 2 * d
    flops, nbytes = WORK.prefill(GRANITE, 256)
    assert flops == 256 * tok + 4.0 * n_a * 32 * 64 * 256 * 257 / 2 + 2.0 * v * d
    assert nbytes == weights + state + row * 256 + 2 * 256 * d


def test_the_state_is_spread_over_the_cells_context_and_buys_batch_32():
    """``core.decode_batch`` multiplies a token's bytes by the mix's
    ``max_seq_len``: spread over the same number of tokens, one sequence's
    state counts once, and 3 GiB of cache holds 32 sequences (34 fit)."""
    mix = core.load_mix(core.cell(MAN, CELL)["traffic"])
    assert GRANITE["state_spread_tokens"] == mix["max_seq_len"] == 2048
    per_seq = WORK.cache_bytes_per_token(GRANITE) * mix["max_seq_len"]
    assert per_seq == 76_437_504 + 8192 * 2048 == 93_214_720
    assert GRANITE["cache_bytes"] // per_seq == 34
    assert core.decode_batch(GRANITE, mix, WORK) == 32


def test_the_program_holds_the_state_in_float32():
    """The deployment holds the Mamba2 state in fp32, and at these weights
    the cell's ``max_logit_gap`` cannot tell a bfloat16 state from it (the
    chip reads both under 0.003): the program's decode cache holds it in
    float32 at the published widths, and a decode step hands it on so."""
    import jax

    from repro.configs import get_config
    from repro.models import abstract_cache, abstract_params
    from repro.models.model import decode_step

    assert "fp32 SSM state" in GRANITE["deployment"]
    cfg = get_config("granite-4.0-h-micro")
    cache = abstract_cache(cfg, 2, 16)
    ids = jnp.zeros((2,), jnp.int32)
    _, stepped, _ = jax.eval_shape(
        lambda p, c: decode_step(p, cfg, ids, c, ids), abstract_params(cfg), cache)
    for tree in (cache, stepped):
        states = [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
                  if path[-1] == jax.tree_util.DictKey("ssm")]
        assert sum(x.shape[0] for x in states) == 36     # every Mamba2 layer's
        assert all(x.dtype == jnp.float32 for x in states)


def _trace_run():
    """Two decode calls of 10 ms, each holding ops under ssm/kv_write (2 ms),
    under ssm elsewhere (1 ms), under attn (1 ms) and under no scope."""
    ops, modules = [], []
    for t in (0, 20):
        ops += [["%fusion.state", t * MS, 2 * MS], ["%fusion.inproj", (t + 2) * MS, 1 * MS],
                ["%fusion.attn", (t + 3) * MS, 1 * MS], ["%copy.1", (t + 4) * MS, 1 * MS]]
        modules.append(["jit_decode_impl(3)", t * MS, 10 * MS])
    scopes = {"jit_decode_impl": {
        "%fusion.state": "jit(decode_impl)/while/body/closed_call/ssm/kv_write/add",
        "%fusion.inproj": "jit(decode_impl)/while/body/closed_call/ssm/dot_general",
        "%fusion.attn": "jit(decode_impl)/while/body/closed_call/attn/dot_general"}}
    data = {"devices": {"0": {"ops": ops, "modules": modules, "op_scopes": scopes}},
            "spans": [["cb.window", 0.0, 40 * MS]]}
    calls = [("decode_once", 0.0, 0.01, ("decode", [5] * 32)),
             ("decode_once", 0.02, 0.03, ("decode", [9] * 16))]
    spans = types.SimpleNamespace(of=lambda name: calls if name == "decode_once" else [])
    return types.SimpleNamespace(reduced=tracing.Reduced(data), spans=spans, wk=WORK,
                                 model=GRANITE, peaks=core.peaks("TPU v5 lite"))


def test_the_ssm_readers_on_a_made_up_trace():
    run = _trace_run()
    assert core.metric_reader("decode_ssm_ms").read(run) == pytest.approx(3.0)
    # 24 slots on average, each state read and written, over 2 ms a call
    want = 100.0 * 2 * 24 * 76_437_504 / 819e9 / 2e-3
    assert core.metric_reader("ssm_state_roofline").read(run) == pytest.approx(want)


def test_the_ssm_readers_read_nothing_without_the_scope():
    run = _trace_run()
    for ops in run.reduced.data["devices"]["0"]["op_scopes"].values():
        for name in ops:
            ops[name] = ops[name].replace("/ssm/", "/gdn/")
    for name in ("decode_ssm_ms", "ssm_state_roofline"):
        assert core.metric_reader(name).read(run) is None
    run.reduced = None
    assert core.metric_reader("decode_ssm_ms").read(run) is None


def test_the_ssm_reader_sees_this_builds_scopes(monkeypatch, fresh_programs):
    """The decode program that ran may carry another build's scopes (a
    compilation cache keyed without op metadata hands them back, and jit
    keeps its executable): here it is built with the old state's read
    under no scope, and the reader still finds that read under
    ``ssm/kv_write``, once the program's own code is back."""
    import jax

    from repro.models import model

    scoped = model._state_read
    monkeypatch.setattr(model, "_state_read",
                        lambda kind, stack, unit: jax.tree.map(lambda a: a[unit], stack))

    def hlo(run):
        monkeypatch.setattr(model, "_state_read", scoped)
        return core.metric_reader("decode_ssm_ms")._scoped_hlo(run.fleet)

    res = run_small(CELL, limit=LIMIT, seconds=1.0, observe=hlo)
    assert "ssm/kv_write/dynamic_slice" in res["observed"]


@pytest.fixture
def fresh_programs():
    from repro.serving.pool import clear_program_caches

    clear_program_caches()
    yield
    clear_program_caches()


def _prefill_ignores_lengths(monkeypatch):
    """The fault this configuration exposed: prefill runs the state and the
    conv window through the padding up to the prompt's bucket."""
    from repro.models import ssm

    orig = ssm.ssm_prefill
    monkeypatch.setattr(ssm, "ssm_prefill", lambda *a, lengths=None, **k: orig(*a, **k))


def _bf16_state(monkeypatch):
    """The Mamba2 state rounded to bfloat16 wherever a step hands it on."""
    from repro.models import ssm

    step, chunked = ssm.ssd_step, ssm.ssd_chunked
    bf16 = lambda s: s.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731

    def held_step(x, dt, a, b, c, state):
        y, new = step(x, dt, a, b, c, bf16(state))
        return y, bf16(new)

    def held_chunked(*a, **k):
        y, final = chunked(*a, **k)
        return y, bf16(final)

    monkeypatch.setattr(ssm, "ssd_step", held_step)
    monkeypatch.setattr(ssm, "ssd_chunked", held_chunked)


def _prompt_lengths(run):
    return sorted({len(r.prompt) for r in run.window.finished()})


@pytest.mark.parametrize("fault", [None, _prefill_ignores_lengths, _bf16_state],
                         ids=["sound", "prefill_ignores_lengths", "bf16_state"])
def test_the_reduced_cell_against_the_reference(fault, monkeypatch, fresh_programs):
    """Served through ``Fleet`` with prompts padded to their buckets, the
    sound program is correct at ``LIMIT``; either fault of the state fails
    it."""
    if fault is not None:
        fault(monkeypatch)
    res = run_small(CELL, limit=LIMIT, observe=_prompt_lengths)
    lengths = res["observed"]
    assert any(n not in (32, 64) for n in lengths), lengths     # padded prompts
    gap = res["check"]["max_logit_gap"]["value"]
    assert res["check"]["tokens_checked"]["value"] >= 48
    if fault is None:
        assert res["correct"] is True and gap <= LIMIT
    else:
        assert res["correct"] is False and gap > LIMIT
