"""Serving engine: continuous batching, phase accounting, output equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models import decode_step, init_cache, init_params, prefill
from repro.serving import ServingEngine
from repro.training import make_prompts


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("gemma-2b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


class TestEngine:
    def test_completes_more_requests_than_slots(self, setup):
        cfg, params = setup
        eng = ServingEngine(cfg, params, max_batch=2, max_seq_len=64)
        reqs = [eng.submit(p, max_new_tokens=6) for p in make_prompts(cfg, 5, 4, 12)]
        done = eng.run_to_completion()
        assert len(done) == 5
        assert all(r.done for r in reqs)
        assert all(1 <= len(r.output) <= 6 for r in reqs)

    def test_phase_stats_accumulate(self, setup):
        cfg, params = setup
        eng = ServingEngine(cfg, params, max_batch=2, max_seq_len=64)
        prompts = make_prompts(cfg, 3, 4, 10, seed=3)
        for p in prompts:
            eng.submit(p, max_new_tokens=4)
        eng.run_to_completion()
        s = eng.stats
        assert s.prefill_tokens == sum(len(p) for p in prompts)
        assert s.prefill_calls == 3
        assert s.decode_steps >= 3
        assert s.prefill_s > 0 and s.decode_s > 0

    @pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
    def test_decode_kv_rows_count_what_attention_reads(self, setup, kernel):
        """Each dense decode step holds B x L cache rows. The XLA path reads
        them all; where the model's attention is the TPU kernel (a pool on a
        TPU, said by hand here, with heads of 128 lanes and 8 KV heads) it
        reads ``kv_rows_read`` of the lengths and active mask the step was
        dispatched with."""
        import dataclasses

        from repro.kernels.decode_attn import kv_rows_read
        from repro.serving.pool import PhaseStats

        cfg, params = setup
        if kernel:
            cfg = dataclasses.replace(cfg, n_heads=8, n_kv_heads=8, head_dim=128)
            params = init_params(cfg, jax.random.PRNGKey(0))
        eng = ServingEngine(cfg, params, max_batch=2, max_seq_len=64)
        pool = eng.pool
        if kernel:
            pool.platform = "tpu"
        step, sent = pool._jit_decode, []

        def dispatch(*args):
            sent.append((np.array(args[3]), np.array(args[4])))
            return step(*args)

        pool._jit_decode = dispatch
        for p in make_prompts(cfg, 3, 4, 10, seed=3):
            eng.submit(p, max_new_tokens=4)
        eng.run_to_completion()
        s = eng.stats
        assert s.decode_steps == len(sent) >= 3
        assert s.decode_kv_rows_held == len(sent) * 2 * 64
        read = sum(int(kv_rows_read(n, a, 64).sum()) for n, a in sent)
        assert 0 < read < s.decode_kv_rows_held
        assert s.decode_kv_rows_read == (read if kernel else s.decode_kv_rows_held)
        both = s.merged_with(s)
        assert (both.decode_kv_rows_read, both.decode_kv_rows_held) == (
            2 * s.decode_kv_rows_read, 2 * s.decode_kv_rows_held)
        assert PhaseStats().decode_kv_rows_held == 0

    def test_engine_matches_manual_greedy_decode(self, setup):
        """The engine's batched/continuous path produces the same greedy
        tokens as a manual single-request prefill+decode loop."""
        cfg, params = setup
        prompt = make_prompts(cfg, 1, 8, 8, seed=9)[0]
        n_new = 5

        # manual reference
        cache = init_cache(cfg, 1, 64)
        lg, cache, lengths = prefill(params, cfg, jnp.asarray(prompt[None]), cache)
        ref = [int(jnp.argmax(lg[0]))]
        tok = jnp.asarray([ref[-1]], jnp.int32)
        for _ in range(n_new - 1):
            lg, cache, lengths = decode_step(params, cfg, tok, cache, lengths)
            ref.append(int(jnp.argmax(lg[0])))
            tok = jnp.asarray([ref[-1]], jnp.int32)

        eng = ServingEngine(cfg, params, max_batch=2, max_seq_len=64)
        req = eng.submit(prompt, max_new_tokens=n_new)
        eng.run_to_completion()
        # engine stops early on EOS; compare the prefix it generated
        n = len(req.output)
        assert req.output == ref[:n]

    def test_oversized_request_rejected(self, setup):
        cfg, params = setup
        eng = ServingEngine(cfg, params, max_batch=1, max_seq_len=32)
        eng.submit(np.arange(1, 30, dtype=np.int32), max_new_tokens=10)
        with pytest.raises(ValueError, match="exceeds engine max_seq_len"):
            eng.step()

    def test_slot_reuse_after_completion(self, setup):
        cfg, params = setup
        eng = ServingEngine(cfg, params, max_batch=1, max_seq_len=64)
        for p in make_prompts(cfg, 3, 4, 8, seed=5):
            eng.submit(p, max_new_tokens=3)
        done = eng.run_to_completion()
        assert len(done) == 3  # one slot served all three sequentially
