"""Model assembly tests: every block kind, prefill<->decode equivalence,
cache semantics, MoE routing invariants."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import (
    ModelConfig,
    StageSpec,
    decode_step,
    decode_step_batched,
    forward,
    init_cache,
    init_params,
    logits,
    prefill,
)
from repro.models import model as M
from repro.models.layers import rmsnorm
from repro.models.moe import moe_mlp, init_moe, _capacity


def tiny(stages, **kw):
    base = dict(
        name="tiny", family="dense", d_model=64, vocab_size=128,
        stages=tuple(StageSpec(unit=u, n_units=n) for u, n in stages),
        n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
        param_dtype="float32", compute_dtype="float32",
    )
    base.update(kw)
    return ModelConfig(**base)


CASES = {
    "gqa": tiny([(("attn",), 3)]),
    "gemma2": tiny([(("attn", "attn_global"), 2)], sliding_window=4,
                   attn_softcap=50.0, final_softcap=30.0),
    "mla": tiny([(("mla",), 2)], kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16),
    "mla_qlora": tiny([(("mla",), 2)], kv_lora_rank=32, q_lora_rank=24,
                      qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16),
    "moe": tiny([(("mla",), 1), (("mla_moe",), 2)], kv_lora_rank=32,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                n_routed_experts=4, n_shared_experts=1, moe_top_k=2,
                moe_d_ff=32, moe_capacity_factor=8.0, family="moe"),
    "ssm": tiny([(("ssm",), 3)], family="ssm", ssm_state=16, ssm_heads=4, ssm_chunk=4),
    "gdn": tiny([(("gdn",), 2)], gdn_heads=2, gdn_head_dim=16),
    "hybrid": tiny([(("ssm", "ssm", "shared_attn"), 2)], family="hybrid",
                   ssm_state=16, ssm_heads=4, ssm_chunk=4, n_kv_heads=4),
    "vlm": tiny([(("attn", "cross_attn"), 2)], family="vlm", n_media_tokens=6),
    # granite-4.0-h: Mamba2 + MLP blocks beside NoPE GQA, muP multipliers
    "granite": tiny([(("ssm_mlp", "attn", "ssm_mlp"), 2)], family="hybrid",
                    ssm_state=16, ssm_heads=4, ssm_chunk=4, use_rope=False,
                    attn_scale=1 / 16, embedding_multiplier=12.0,
                    residual_multiplier=0.22, logits_scaling=8.0),
    "audio": tiny([(("attn",), 2)], family="audio", input_is_embeddings=True),
    # heads of the TPU's 128 lanes, 8 KV heads: dense decode reads each
    # layer out of the stacked cache by index (the kernel's path on the TPU)
    "gqa128": tiny([(("attn",), 2)], n_heads=16, n_kv_heads=8, head_dim=128),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_prefill_decode_matches_forward(name):
    cfg = CASES[name]
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 8
    if cfg.input_is_embeddings:
        inputs = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
        pre_in, last_in = inputs[:, : S - 1], inputs[:, S - 1 : S]
    else:
        inputs = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
        pre_in, last_in = inputs[:, : S - 1], inputs[:, S - 1]
    enc = (
        jax.random.normal(jax.random.PRNGKey(7), (B, cfg.n_media_tokens, cfg.d_model))
        if cfg.n_media_tokens else None
    )

    h = forward(params, cfg, inputs, enc_states=enc, remat=False)
    lg = logits(params, cfg, h)
    assert np.isfinite(np.asarray(lg)).all()

    cache = init_cache(cfg, B, S + 4)
    lg_pre, cache, lengths = prefill(params, cfg, pre_in, cache, enc_states=enc)
    np.testing.assert_allclose(np.asarray(lg_pre), np.asarray(lg[:, S - 2]), rtol=3e-4, atol=3e-4)
    lg_dec, cache, lengths = decode_step(params, cfg, last_in, cache, lengths, enc_states=enc)
    np.testing.assert_allclose(np.asarray(lg_dec), np.asarray(lg[:, S - 1]), rtol=3e-4, atol=3e-4)


def test_multi_step_decode_consistency():
    """Decoding token-by-token equals teacher-forced forward at every step."""
    cfg = CASES["gqa"]
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S = 1, 10
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab_size)
    full = logits(params, cfg, forward(params, cfg, toks, remat=False))

    cache = init_cache(cfg, B, S + 2)
    lg, cache, lengths = prefill(params, cfg, toks[:, :4], cache)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, 3]), rtol=3e-4, atol=3e-4)
    for t in range(4, S):
        lg, cache, lengths = decode_step(params, cfg, toks[:, t], cache, lengths)
        np.testing.assert_allclose(
            np.asarray(lg), np.asarray(full[:, t]), rtol=5e-4, atol=5e-4,
            err_msg=f"step {t}",
        )


def test_ragged_batch_decode():
    """Per-request lengths: a batch where rows have different prompt lens."""
    cfg = CASES["gqa"]
    params = init_params(cfg, jax.random.PRNGKey(0))
    S1, S2 = 7, 4
    t1 = jax.random.randint(jax.random.PRNGKey(3), (1, S1), 0, cfg.vocab_size)
    t2 = jax.random.randint(jax.random.PRNGKey(4), (1, S2), 0, cfg.vocab_size)

    # reference: each alone
    def solo(toks):
        c = init_cache(cfg, 1, 12)
        lg, c, ln = prefill(params, cfg, toks, c)
        return lg

    ref1, ref2 = solo(t1), solo(t2)

    # batched with right-padding and true lengths
    padded = jnp.zeros((2, S1), jnp.int32)
    padded = padded.at[0].set(t1[0]).at[1, :S2].set(t2[0])
    cache = init_cache(cfg, 2, 12)
    lg, cache, lengths = prefill(
        params, cfg, padded, cache, prompt_lengths=jnp.array([S1, S2], jnp.int32)
    )
    np.testing.assert_allclose(np.asarray(lg[0]), np.asarray(ref1[0]), rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(lg[1]), np.asarray(ref2[0]), rtol=3e-4, atol=3e-4)


L_DECODE = 6


def _random_cache(cfg, batch, seed):
    """A decode cache of random contents (every leaf, every kind)."""
    cache = init_cache(cfg, batch, L_DECODE)
    leaves, tree = jax.tree.flatten(cache)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        jax.random.normal(k, x.shape).astype(x.dtype) for k, x in zip(keys, leaves)])


def _decode_token(cfg, batch, seed):
    key = jax.random.PRNGKey(seed)
    if cfg.input_is_embeddings:
        return jax.random.normal(key, (batch, 1, cfg.d_model))
    return jax.random.randint(key, (batch,), 0, cfg.vocab_size)


def _with_row(buf, row, lengths):
    """buf (B, L, ...) with row (B, 1, ...) at each slot's ``lengths``."""
    at = jnp.arange(buf.shape[1])[None, :] == lengths[:, None]
    return jnp.where(at.reshape(at.shape + (1,) * (buf.ndim - 2)), row, buf)


def _reference_decode(params, cfg, token, cache, lengths):
    """The full-buffer formula, scanned layer by layer: each per-token
    cache takes its new row with a ``where`` over the whole buffer, the
    block attends over that buffer, and the scan stacks the buffers; ssm and
    gdn states are replaced; the cross-attention cache stays."""
    x = (token.astype(M._cdtype(cfg)) if cfg.input_is_embeddings
         else M._embed_inputs(params, cfg, token[:, None]))
    shared = params.get("shared_block")

    def unit(x, xs, stage):
        up, uc = xs
        new = {}
        for i, kind in enumerate(stage.unit):
            b, bc = f"b{i}", uc[f"b{i}"]
            x_out, out = M._block_apply(kind, up[b], x, cfg, "decode", bc, lengths,
                                        shared, None)
            if kind in M.PAGED_KINDS:
                new[b] = {n: _with_row(bc[n], out[n], lengths) for n in bc}
                x_out, _ = M._block_apply(kind, up[b], x, cfg, "decode", new[b], lengths,
                                          shared, None)
            else:
                new[b] = bc if kind == "cross_attn" else out
            x = x_out
        return x, new

    stages = []
    for si, stage in enumerate(cfg.stages):
        x, sc = jax.lax.scan(functools.partial(unit, stage=stage), x,
                             (params["stages"][si], cache["stages"][si]))
        stages.append(sc)
    x = rmsnorm(params["final_norm"], x, cfg.rms_eps)
    return logits(params, cfg, x)[:, 0], {"stages": stages}


def _assert_same(got, want):
    jax.tree.map(lambda g, w: np.testing.assert_array_equal(np.asarray(g), np.asarray(w)),
                 got, want)


@pytest.mark.parametrize("case", ["ragged", "full_slot", "inactive", "batched"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_decode_step_writes_rows_like_the_full_buffer_formula(name, case):
    """``decode_step`` (rows written in place after the layer scan) gives
    bit for bit the logits and caches of the reference formula: at ragged
    lengths; with a slot at ``lengths == max_len``, which writes nothing;
    through the pool's step with an inactive slot, which writes its own row
    and keeps its length; and as ``decode_step_batched`` over two replicas."""
    cfg = CASES[name]
    params = init_params(cfg, jax.random.PRNGKey(0))
    lengths = jnp.array([L_DECODE if case == "full_slot" else 2, 4], jnp.int32)
    if case == "batched":
        stack = lambda *a: jnp.stack(a)
        cache = jax.tree.map(stack, _random_cache(cfg, 2, 5), _random_cache(cfg, 2, 6))
        token = stack(_decode_token(cfg, 2, 7), _decode_token(cfg, 2, 8))
        lengths = stack(lengths, lengths[::-1])
        got = decode_step_batched(params, cfg, token, cache, lengths)
        want = jax.vmap(lambda t, c, n: _reference_decode(params, cfg, t, c, n))(
            token, cache, lengths)
        _assert_same(got, want + (lengths + 1,))
        return
    cache = _random_cache(cfg, 2, 5)
    token = _decode_token(cfg, 2, 7)
    want_lg, want_cache = _reference_decode(params, cfg, token, cache, lengths)
    if case == "inactive":
        from repro.serving.pool import decode_impl_for

        active = jnp.array([False, True])
        tok, got_cache, new_len = decode_impl_for(cfg)(
            params, token, cache, lengths, active, jax.random.PRNGKey(0), jnp.zeros(2))
        np.testing.assert_array_equal(np.asarray(tok), np.argmax(np.asarray(want_lg), -1))
        np.testing.assert_array_equal(np.asarray(new_len), [2, 5])
    else:
        got_lg, got_cache, _ = decode_step(params, cfg, token, cache, lengths)
        np.testing.assert_array_equal(np.asarray(got_lg), np.asarray(want_lg))
    _assert_same(got_cache, want_cache)
    if case == "full_slot":   # the full slot's per-token rows are as they were
        for stage, got, before in zip(cfg.stages, got_cache["stages"], cache["stages"]):
            for i, kind in enumerate(stage.unit):
                if kind in M.PAGED_KINDS:
                    _assert_same(jax.tree.map(lambda a: a[:, 0], got[f"b{i}"]),
                                 jax.tree.map(lambda a: a[:, 0], before[f"b{i}"]))


@pytest.mark.parametrize("name", ["gdn", "hybrid", "moe", "vlm", "granite"])   # every block kind
def test_unrolled_decode_matches_the_scanned_one(name):
    """The accounting lowering (units in a Python loop) writes the same
    caches as the scan; XLA may fuse the two programs apart, so values
    agree to float32 rounding."""
    from repro.models.unroll import set_unroll

    cfg = CASES[name]
    params = init_params(cfg, jax.random.PRNGKey(0))
    args = (params, cfg, _decode_token(cfg, 2, 7), _random_cache(cfg, 2, 5),
            jnp.array([2, 4], jnp.int32))
    scanned = decode_step(*args)
    set_unroll(True)
    try:
        unrolled = decode_step(*args)
    finally:
        set_unroll(False)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6), unrolled, scanned)


@pytest.mark.parametrize("width", [128, 64], ids=["lanes128", "lanes64"])
def test_row_writes_match_the_formula(width):
    """``_write_at_lengths`` writes each slot's new row, every layer at
    once, as the ``where`` formula does, bit for bit, for qwen3-4b's
    128-wide heads and granite's 64-wide ones; the slot at ``lengths == L``
    keeps its rows."""
    from repro.models import attention

    buf = jax.random.normal(jax.random.PRNGKey(1), (2, 3, L_DECODE, 2, width))
    new = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 1, 2, width))
    lengths = jnp.array([2, L_DECODE, 0], jnp.int32)
    got = attention._write_at_lengths(buf, new, lengths)
    want = jax.vmap(lambda b, r: _with_row(b, r, lengths))(buf, new)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got[:, 1]), np.asarray(buf[:, 1]))


@pytest.mark.parametrize("case", ["replicas", "nested", "shared_lengths"])
def test_vmapped_row_writes_match_per_replica_writes(case):
    """Under ``jax.vmap`` (the event engine's fused decode over replicas)
    ``_write_at_lengths`` writes each replica's rows as its own call does,
    bit for bit, also in a vmap inside a vmap and with lengths the
    replicas share."""
    from repro.models import attention

    k = 3
    buf = jax.random.normal(jax.random.PRNGKey(3), (k, 2, 3, L_DECODE, 2, 128))
    new = jax.random.normal(jax.random.PRNGKey(4), (k, 2, 3, 1, 2, 128))
    lengths = jnp.array([[2, L_DECODE, 0], [5, 1, L_DECODE], [0, 3, 4]], jnp.int32)
    write = attention._write_at_lengths
    if case == "shared_lengths":
        got = jax.vmap(write, in_axes=(0, 0, None))(buf, new, lengths[0])
        want = [write(buf[i], new[i], lengths[0]) for i in range(k)]
    elif case == "nested":
        two = lambda x: jnp.stack([x, x[::-1]])
        got = jax.vmap(jax.vmap(write))(two(buf), two(new), two(lengths))
        one = [write(buf[i], new[i], lengths[i]) for i in range(k)]
        want = [jnp.stack(one), jnp.stack(one[::-1])]
    else:
        got = jax.vmap(write)(buf, new, lengths)
        want = [write(buf[i], new[i], lengths[i]) for i in range(k)]
    np.testing.assert_array_equal(np.asarray(got), np.stack(want))


def test_vmapped_decode_kernel_path_matches_each_replicas_step(monkeypatch):
    """Replicas' dense decode steps batched by ``vmap_replicas`` on the TPU
    branch (the kernel interpreted here) give each replica's own step for
    its active slots: logits and caches."""
    from repro.kernels.decode_attn import ops
    from repro.models import attention

    cfg = CASES["gqa128"]
    params = init_params(cfg, jax.random.PRNGKey(0))
    k = 2
    caches = [_random_cache(cfg, 4, 5 + i) for i in range(k)]
    tokens = jnp.stack([_decode_token(cfg, 4, 7 + i) for i in range(k)])
    lengths = jnp.array([[0, 3, 5, 6], [2, 6, 1, 4]], jnp.int32)
    active = jnp.array([[True, True, False, True], [True, True, True, False]])
    monkeypatch.setattr(attention, "gqa_decode_attention",
                        functools.partial(ops.gqa_decode_attention, interpret=True))
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    step = lambda p, t, c, n, a: decode_step(p, cfg, t, c, n, active=a)
    got_lg, got_cache, _ = M.vmap_replicas(step, 5)(
        params, tokens, jax.tree.map(lambda *x: jnp.stack(x), *caches), lengths, active)
    for i in range(k):
        want_lg, want_cache, _ = step(params, tokens[i], caches[i], lengths[i], active[i])
        on = np.asarray(active[i])
        np.testing.assert_allclose(np.asarray(got_lg[i])[on], np.asarray(want_lg)[on],
                                   rtol=2e-5, atol=2e-5)
        jax.tree.map(lambda g, w: np.testing.assert_allclose(
            np.asarray(g[i])[:, on], np.asarray(w)[:, on], rtol=2e-5, atol=2e-5),
            got_cache, want_cache)


def test_a_stale_row_write_shows_on_the_next_step(monkeypatch):
    """The seam the benchmark's fault check relies on: with
    ``attention._write_at_lengths`` made to return the buffer unchanged,
    a step still sees its own new row, but the next step differs."""
    from repro.models import attention

    cfg = CASES["gqa"]
    params = init_params(cfg, jax.random.PRNGKey(0))
    lengths = jnp.array([1, 3], jnp.int32)

    def two_steps():
        cache = _random_cache(cfg, 2, 5)
        lg1, cache, ln = decode_step(params, cfg, _decode_token(cfg, 2, 7), cache, lengths)
        lg2, _, _ = decode_step(params, cfg, _decode_token(cfg, 2, 8), cache, ln)
        return np.asarray(lg1), np.asarray(lg2)

    good = two_steps()
    monkeypatch.setattr(attention, "_write_at_lengths", lambda buf, new, lengths: buf)
    stale = two_steps()
    np.testing.assert_array_equal(stale[0], good[0])
    assert np.abs(stale[1] - good[1]).max() > 1e-3


class TestMoE:
    def test_no_drop_equivalence_to_dense_topk(self):
        cfg = CASES["moe"]
        p = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, cfg.d_model))
        out, aux = moe_mlp(p, x, cfg)
        # dense reference: run every expert on every token, combine top-k
        xf = x.reshape(-1, cfg.d_model)
        gates = jax.nn.softmax(xf @ p["router"], axis=-1)
        topw, topi = jax.lax.top_k(gates, cfg.moe_top_k)
        ref = jnp.zeros_like(xf)
        for e in range(cfg.n_routed_experts):
            h = jax.nn.silu(xf @ p["w_gate"][e]) * (xf @ p["w_up"][e])
            y = h @ p["w_down"][e]
            w = jnp.sum(jnp.where(topi == e, topw, 0.0), axis=-1)
            ref = ref + y * w[:, None]
        from repro.models.layers import mlp as mlp_fn
        ref = ref.reshape(x.shape) + mlp_fn(p["shared"], x, "swiglu")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)

    def test_capacity_drops_tokens(self):
        cfg = dataclasses.replace(CASES["moe"], moe_capacity_factor=0.25)
        p = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
        out, aux = moe_mlp(p, x, cfg)
        assert np.isfinite(np.asarray(out)).all()

    def test_aux_loss_balanced_lower_bound(self):
        """Uniform routing gives aux ~= 1 (the theoretical minimum)."""
        cfg = CASES["moe"]
        p = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
        p["router"] = jnp.zeros_like(p["router"])  # uniform gates
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
        _, aux = moe_mlp(p, x, cfg)
        assert 0.9 <= float(aux) <= 1.6

    def test_capacity_formula(self):
        cfg = CASES["moe"]
        assert _capacity(cfg, 64) == max(8, int(np.ceil(64 * cfg.moe_top_k / cfg.n_routed_experts * cfg.moe_capacity_factor)))


def test_param_count_matches_actual_tree():
    """Analytic param_count agrees with the instantiated tree (<0.5%)."""
    for name in ("gqa", "mla", "moe", "ssm", "gdn", "hybrid", "granite"):
        cfg = CASES[name]
        params = init_params(cfg, jax.random.PRNGKey(0))
        actual = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
        predicted = cfg.param_count()
        assert abs(actual - predicted) / actual < 0.005, (
            f"{name}: actual {actual} vs predicted {predicted}"
        )


def test_param_count_matches_the_granite_tree():
    """granite-4.0-h-micro at its published widths: the analytic count is
    the tree's, to the parameter (about 3.19e9)."""
    from repro.configs import get_config
    from repro.models import abstract_params

    cfg = get_config("granite-4.0-h-micro")
    actual = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(abstract_params(cfg)))
    assert actual == cfg.param_count() == 3_191_396_096


def _prefill_state(cfg, params, tokens, bucket, lengths):
    """Prefill ``tokens`` (1, S) into a cache of ``bucket`` rows: logits,
    cache, and the logits of one decode step after it."""
    lg, cache, ln = prefill(params, cfg, tokens, init_cache(cfg, 1, bucket),
                            prompt_lengths=lengths)
    nxt = jnp.argmax(lg, -1).astype(jnp.int32)
    lg2, _, _ = decode_step(params, cfg, nxt, cache, ln)
    return lg, cache, lg2


@pytest.mark.parametrize("name", ["ssm", "granite"])
def test_prefill_at_a_padded_bucket_matches_the_exact_length(name):
    """A serving pool pads a 37-token prompt to its 64-token bucket and
    passes the true length: the recurrent state, the conv window and the
    next-token logits (and the next step's) are those of a prefill at
    exactly 37 tokens. Padding rows take dt = 0 (an exact no-op) and the
    conv window is cut at the true length, so the two agree to float32
    rounding of sums taken in another grouping (1e-5). Without the length
    the pad tokens reach the state, and the check fails."""
    cfg = CASES[name]
    params = init_params(cfg, jax.random.PRNGKey(0))
    n, bucket = 37, 64
    toks = jax.random.randint(jax.random.PRNGKey(11), (1, bucket), 1, cfg.vocab_size)
    want = _prefill_state(cfg, params, toks[:, :n], bucket, None)
    got = _prefill_state(cfg, params, toks, bucket, jnp.array([n], jnp.int32))
    blind = _prefill_state(cfg, params, toks, bucket, None)   # the pad reaches the state

    def close(a, b):
        return np.allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)

    for stage, cw, cg, cb in zip(cfg.stages, want[1]["stages"], got[1]["stages"],
                                 blind[1]["stages"]):
        for i, kind in enumerate(stage.unit):
            w, g = cw[f"b{i}"], cg[f"b{i}"]
            if kind in M.PAGED_KINDS:       # rows past the prompt are never read
                w, g = ({k: v[:, :, :n] for k, v in c.items()} for c in (w, g))
            else:
                assert not all(close(cb[f"b{i}"][k], w[k]) for k in w)
            for k in w:
                assert close(g[k], w[k]), (i, k)
    assert close(got[0], want[0]) and close(got[2], want[2])
    assert not close(blind[2], want[2])


@pytest.mark.parametrize("lengths", [[0, 3, 5, 6], [2, 1, 6, 4]], ids=["edges", "ragged"])
def test_decode_kernel_path_matches_the_xla_path(monkeypatch, lengths):
    """A dense decode step on the TPU's branch (the flash-decode kernel, run
    by its interpreter here, reading each layer out of the stacked cache)
    gives the XLA path's logits and caches for the active slots: cached
    lengths 0 to ``max_len`` across blocks of 2 rows. The inactive slot
    reads no row, so from the second layer on it writes other rows than
    the XLA path, into a slot that holds no request."""
    from repro.kernels.decode_attn import ops
    from repro.models import attention

    cfg = CASES["gqa128"]
    assert attention.decode_reads_stack(cfg)
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache = _random_cache(cfg, 4, 5)
    token = _decode_token(cfg, 4, 7)
    lengths = jnp.array(lengths, jnp.int32)
    active = jnp.array([True, True, False, True])
    want_lg, want_cache, _ = decode_step(params, cfg, token, cache, lengths)

    kernel = ops.gqa_decode_attention
    monkeypatch.setattr(attention, "gqa_decode_attention",
                        functools.partial(kernel, interpret=True))
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    got_lg, got_cache, _ = decode_step(params, cfg, token, cache, lengths, active=active)
    on = np.asarray(active)
    np.testing.assert_allclose(np.asarray(got_lg)[on], np.asarray(want_lg)[on],
                               rtol=2e-5, atol=2e-5)
    jax.tree.map(lambda g, w: np.testing.assert_allclose(
        np.asarray(g)[:, on], np.asarray(w)[:, on], rtol=2e-5, atol=2e-5),
        got_cache, want_cache)
