"""Per-kernel validation: shape/dtype sweeps, allclose vs the ref.py oracles
(interpret mode on CPU, per the kernel contract)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
    block_rows,
    clamp_block,
    decode_attention,
    decode_attention_ref,
    gdn_prefill,
    gdn_scan_ref,
    gqa_decode_attention,
    gqa_paged_decode_attention,
    kv_rows_read,
    largest_divisor_block,
    mla_fused_decode,
    mla_latent_decode,
    mla_latent_decode_ref,
    mla_paged_fused_decode,
    mla_paged_latent_decode,
    mla_paged_latent_decode_ref,
    paged_decode_attention,
    paged_decode_attention_ref,
    ssd_prefill,
    ssd_scan_ref,
)

TOL = {jnp.float32: dict(rtol=5e-5, atol=5e-5), jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _dense_case(key, b, h, kv, dk, dv, l, n_units=2, dtype=jnp.float32):
    """Random operands of the dense kernel: q, the new rows, and a stacked
    cache of ``n_units`` layers."""
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (b, h, dk)).astype(dtype)
    k_new = jax.random.normal(ks[1], (b, kv, dk)).astype(dtype)
    v_new = jax.random.normal(ks[2], (b, kv, dv)).astype(dtype)
    k = jax.random.normal(ks[3], (n_units, b, l, kv, dk)).astype(dtype)
    v = jax.random.normal(ks[4], (n_units, b, l, kv, dv)).astype(dtype)
    return q, k_new, v_new, k, v


def _dense_ref(q, k_new, v_new, k, v, unit, lengths, scale):
    """The oracle over layer ``unit`` with each new row laid at ``lengths``:
    attends to positions ``<= lengths`` (all ``L`` cached rows, and not the
    new one, for a full slot)."""
    l = k.shape[2]
    at = (jnp.arange(l)[None, :] == lengths[:, None])[:, :, None, None]
    kb = jnp.where(at, k_new[:, None], k[unit])
    vb = jnp.where(at, v_new[:, None], v[unit])
    return decode_attention_ref(q, kb, vb, jnp.minimum(lengths + 1, l), scale)


class TestDecodeAttn:
    @pytest.mark.parametrize("b,h,kv,dk,dv,l,blk", [
        (1, 4, 1, 16, 16, 96, 32),      # MQA, 3 blocks
        (2, 8, 2, 32, 16, 192, 64),     # GQA, asymmetric dv, 3 blocks
        (3, 6, 6, 16, 16, 96, 32),      # MHA, 3 blocks
        (2, 4, 2, 64, 64, 256, 256),    # single block
    ])
    def test_shapes_sweep(self, b, h, kv, dk, dv, l, blk):
        """Slot 0 holds ``blk - 1`` rows, the rest random lengths; the
        cache is read in blocks of ``blk`` rows."""
        assert block_rows(l) == blk
        key = jax.random.PRNGKey(b * 1000 + h)
        q, k_new, v_new, k, v = _dense_case(key, b, h, kv, dk, dv, l)
        vl = jax.random.randint(jax.random.fold_in(key, 3), (b,), 0, l + 1).at[0].set(blk - 1)
        out = decode_attention(q, k_new, v_new, k, v, jnp.int32(1), vl, jnp.ones(b, bool),
                               scale=0.2, interpret=True)
        ref = _dense_ref(q, k_new, v_new, k, v, 1, vl, 0.2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL[jnp.float32])

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        key = jax.random.PRNGKey(9)
        b, h, kv, d, l = 2, 4, 2, 32, 128
        args = _dense_case(key, b, h, kv, d, d, l, dtype=dtype)
        vl = jnp.array([l - 1, l // 2], jnp.int32)
        out = decode_attention(*args, jnp.int32(0), vl, jnp.ones(b, bool),
                               scale=0.18, interpret=True)
        ref = _dense_ref(*(a.astype(jnp.float32) for a in args), 0, vl, 0.18)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref), **TOL[dtype]
        )

    def test_wrapper_pads_nondivisible_length(self):
        """The wrapper takes the model's query axis; a cache length that no
        ``BLOCK_ROWS`` divides is read in blocks of a divisor (100: 4 rows),
        with no padding."""
        key = jax.random.PRNGKey(11)
        b, h, kv, d, l = 2, 4, 2, 16, 100
        q, k_new, v_new, k, v = _dense_case(key, b, h, kv, d, d, l)
        vl = jnp.array([99, 37], jnp.int32)
        assert block_rows(l) == 4
        out = gqa_decode_attention(q[:, None], k_new[:, None], v_new[:, None], k, v,
                                   jnp.int32(1), vl, jnp.ones(b, bool),
                                   scale=0.25, interpret=True)
        assert out.shape == (b, 1, h, d)
        ref = _dense_ref(q, k_new, v_new, k, v, 1, vl, 0.25)
        np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(ref), rtol=5e-5, atol=5e-5)

    def test_single_valid_token(self):
        """A slot with no cached row attends to its new row alone."""
        key = jax.random.PRNGKey(12)
        b, h, kv, d, l = 1, 2, 1, 16, 64
        q, k_new, v_new, k, v = _dense_case(key, b, h, kv, d, d, l)
        out = decode_attention(q, k_new, v_new, k, v, jnp.int32(0), jnp.array([0], jnp.int32),
                               jnp.ones(b, bool), scale=1.0, interpret=True)
        np.testing.assert_allclose(np.asarray(out)[0], np.asarray(v_new)[0, 0][None].repeat(2, 0), rtol=1e-5)


# the model's side of the dense kernel: heads of 16 lanes, group 4, two
# blocks of 512 positions, three stacked layers
DENSE_L, DENSE_UNITS = 1024, 3
DENSE_CASES = {
    "lengths": {},
    "inactive": {"active": [True, False, True, False, True, True]},
    "gqa_group4": {"lengths": [700, 3, 512, 0, 1023, 90]},
    "nope_scale": {"cfg": {"attn_scale": 1 / 64, "use_rope": False}},
    "softcap": {"cfg": {"attn_softcap": 30.0}},
    "window": {"cfg": {"sliding_window": 300}, "is_global": False},
    "unit0": {"unit": 0},
    "last_unit": {"unit": DENSE_UNITS - 1},
}


@pytest.mark.parametrize("case", sorted(DENSE_CASES))
def test_dense_kernel_matches_the_xla_decode_attention(case):
    """The dense kernel over the stacked cache gives ``_decode_attend`` over
    ``_with_row_at_lengths`` of its layer, the model's XLA path: lengths
    0, 1, block - 1, block, L - 1 and L (a full slot that writes nothing);
    inactive slots, whose stale lengths it does not read (each then attends
    to its new row alone); grouped heads (4 q heads a KV head); a NoPE
    config's scale; softcap; a sliding window; the first and last layer."""
    import types

    from repro.models import attention as A

    spec = DENSE_CASES[case]
    b, h, kv, d = 6, 8, 2, 16
    blk = block_rows(DENSE_L)
    cfg = types.SimpleNamespace(**{"head_dim": d, "attn_scale": None, "attn_softcap": 0.0,
                                   "sliding_window": 0, **spec.get("cfg", {})})
    is_global = spec.get("is_global", True)
    unit = spec.get("unit", 1)
    lengths = jnp.array(spec.get("lengths", [0, 1, blk - 1, blk, DENSE_L - 1, DENSE_L]),
                        jnp.int32)
    active = jnp.array(spec.get("active", [True] * b))
    q, k_new, v_new, k, v = _dense_case(jax.random.PRNGKey(len(case)), b, h, kv, d, d,
                                        DENSE_L, n_units=DENSE_UNITS)
    got = gqa_decode_attention(
        q[:, None], k_new[:, None], v_new[:, None], k, v, jnp.int32(unit), lengths, active,
        scale=A._attn_scale(cfg), softcap=cfg.attn_softcap,
        window=0 if is_global else cfg.sliding_window, interpret=True)[:, 0]

    def xla(lengths):
        wo = {"wo": jnp.eye(h * d).reshape(h, d, h * d)}     # ctx, flattened
        kb = A._with_row_at_lengths(k[unit], k_new[:, None], lengths)
        vb = A._with_row_at_lengths(v[unit], v_new[:, None], lengths)
        out = A._decode_attend(wo, q[:, None], kb, vb, lengths, cfg, is_global, jnp.float32)
        return np.asarray(out).reshape(b, h, d)

    want = np.where(np.asarray(active)[:, None, None], xla(lengths),
                    xla(jnp.zeros_like(lengths)))
    np.testing.assert_allclose(np.asarray(got), want, **TOL[jnp.float32])


def test_rows_read_follow_the_kernels_block_rule():
    """``kv_rows_read``, behind the pool's ``decode_kv_rows_read`` counter,
    names the rows the kernel streams: a NaN in the first row past them
    leaves a slot's output as it was, and one in the last row inside them,
    masked or not, reaches it (0 x NaN is NaN in the p @ V product)."""
    b, h, kv, d, l = 5, 4, 2, 16, 1024
    blk = block_rows(l)
    q, k_new, v_new, k, v = _dense_case(jax.random.PRNGKey(5), b, h, kv, d, d, l)
    lengths = jnp.array([0, 1, blk, blk + 1, l], jnp.int32)
    active = jnp.array([True, True, True, False, True])
    rows = np.asarray(kv_rows_read(lengths, active, l))
    np.testing.assert_array_equal(rows, [0, blk, blk, 0, l])
    np.testing.assert_array_equal(kv_rows_read(np.asarray(lengths), np.asarray(active), l), rows)
    run = lambda v: np.asarray(decode_attention(
        q, k_new, v_new, k, v, jnp.int32(0), lengths, active, scale=0.3, interpret=True))
    clean = run(v)
    for i in range(b):
        if rows[i] < l:
            past = run(v.at[0, i, rows[i]].set(jnp.nan))
            np.testing.assert_array_equal(past, clean)
        if rows[i] > 0:
            inside = run(v.at[0, i, rows[i] - 1].set(jnp.nan))
            assert np.isnan(inside[i]).all()
            np.testing.assert_array_equal(np.delete(inside, i, 0), np.delete(clean, i, 0))


@pytest.mark.parametrize("case", ["replicas", "shared_cache", "unit_per_replica", "nested"])
def test_vmapped_kernel_matches_per_replica_calls(case):
    """Under ``jax.vmap`` (replicas batched on one device) the dense kernel
    folds the vmapped axis into its slots and gives each replica's own
    call: every replica's cache and lengths (one slot inactive), a cache
    shared by the batch, a layer per replica, and a vmap inside a vmap."""
    k, b, h, kv, d, l, n_units = 3, 4, 8, 2, 16, 96, 2
    keys = jax.random.split(jax.random.PRNGKey(21), k)
    q, k_new, v_new, kc, vc = (jnp.stack(x) for x in zip(
        *(_dense_case(kk, b, h, kv, d, d, l, n_units=n_units) for kk in keys)))
    lengths = jax.random.randint(jax.random.PRNGKey(22), (k, b), 0, l + 1)
    active = jnp.ones((k, b), bool).at[1, 2].set(False)
    units = jnp.array([1, 0, 1], jnp.int32)
    call = lambda q, kn, vn, kc, vc, u, n, a: decode_attention(
        q, kn, vn, kc, vc, u, n, a, scale=0.25, interpret=True)
    ax = (0, 0, 0, 0, 0, None, 0, 0)
    if case == "shared_cache":
        ax, kc, vc = (0, 0, 0, None, None, None, 0, 0), kc[0], vc[0]
    if case == "unit_per_replica":
        ax = (0,) * 8
    pick = lambda x, i, a: x if a is None else x[i]
    u = units if case == "unit_per_replica" else jnp.int32(1)
    args = (q, k_new, v_new, kc, vc, u, lengths, active)
    want = np.stack([call(*(pick(x, i, a) for x, a in zip(args, ax))) for i in range(k)])
    if case == "nested":
        two = lambda x: jnp.stack([x, x[::-1]])
        got = jax.vmap(jax.vmap(call, in_axes=ax), in_axes=ax)(
            *(x if a is None else two(x) for x, a in zip(args, ax)))
        got, want = np.asarray(got), np.stack([want, want[::-1]])
        on = np.stack([np.asarray(active), np.asarray(active)[::-1]])
    else:
        got, on = np.asarray(jax.vmap(call, in_axes=ax)(*args)), np.asarray(active)
    np.testing.assert_array_equal(got[on], want[on])


def _random_tables(key, b, nb, n_pages, valid_blocks):
    """Block tables with DISTINCT live pages per request (shuffled, so pages
    are deliberately non-contiguous) padded with the null page 0."""
    perm = jax.random.permutation(key, jnp.arange(1, n_pages))
    tables = np.zeros((b, nb), np.int32)
    used = 0
    for i in range(b):
        n = int(valid_blocks[i])
        tables[i, :n] = np.asarray(perm[used:used + n])
        used += n
    return jnp.asarray(tables)


class TestPagedDecodeAttn:
    @pytest.mark.parametrize("b,h,kv,dk,dv,bs,nb", [
        (1, 4, 1, 16, 16, 8, 4),       # MQA
        (2, 8, 2, 32, 16, 16, 3),      # GQA, asymmetric dv
        (3, 6, 6, 16, 16, 8, 4),       # MHA
    ])
    def test_sweep_vs_ref(self, b, h, kv, dk, dv, bs, nb):
        key = jax.random.PRNGKey(b * 100 + h)
        n_pages = 1 + b * nb
        q = jax.random.normal(key, (b, h, dk), jnp.float32)
        kp = jax.random.normal(jax.random.fold_in(key, 1), (n_pages, bs, kv, dk))
        vp = jax.random.normal(jax.random.fold_in(key, 2), (n_pages, bs, kv, dv))
        valid_blocks = jax.random.randint(jax.random.fold_in(key, 3), (b,), 1, nb + 1)
        tables = _random_tables(jax.random.fold_in(key, 4), b, nb, n_pages, valid_blocks)
        # valid length lands inside the last live block
        vl = (valid_blocks - 1) * bs + jax.random.randint(
            jax.random.fold_in(key, 5), (b,), 1, bs + 1)
        out = paged_decode_attention(q, kp, vp, tables, vl, scale=0.2, interpret=True)
        ref = paged_decode_attention_ref(q, kp, vp, tables, vl, scale=0.2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL[jnp.float32])

    def test_matches_dense_kernel_on_gathered_layout(self):
        """Paged kernel == dense kernel fed the gathered contiguous cache:
        the block-table indirection must be pure layout."""
        key = jax.random.PRNGKey(3)
        b, h, kv, d, bs, nb = 2, 4, 2, 32, 16, 4
        n_pages = 1 + b * nb
        q = jax.random.normal(key, (b, h, d))
        kp = jax.random.normal(jax.random.fold_in(key, 1), (n_pages, bs, kv, d))
        vp = jax.random.normal(jax.random.fold_in(key, 2), (n_pages, bs, kv, d))
        tables = _random_tables(jax.random.fold_in(key, 3), b, nb, n_pages,
                                np.array([4, 3]))
        vl = jnp.array([60, 41], jnp.int32)
        out = paged_decode_attention(q, kp, vp, tables, vl, scale=0.18, interpret=True)
        k_dense = kp[tables].reshape(b, nb * bs, kv, d)
        v_dense = vp[tables].reshape(b, nb * bs, kv, d)
        # the dense kernel over the same rows: the last one is its new row
        last = (vl - 1)[:, None, None, None]
        new_row = lambda x: jnp.take_along_axis(x, last, axis=1)[:, 0]
        ref = decode_attention(q, new_row(k_dense), new_row(v_dense), k_dense[None],
                               v_dense[None], jnp.int32(0), vl - 1, jnp.ones(b, bool),
                               scale=0.18, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=5e-5, atol=5e-5)

    def test_wrapper_accepts_query_seq_axis(self):
        key = jax.random.PRNGKey(4)
        b, h, kv, d, bs, nb = 2, 4, 2, 16, 8, 2
        n_pages = 1 + b * nb
        q = jax.random.normal(key, (b, 1, h, d))
        kp = jax.random.normal(jax.random.fold_in(key, 1), (n_pages, bs, kv, d))
        vp = jax.random.normal(jax.random.fold_in(key, 2), (n_pages, bs, kv, d))
        tables = _random_tables(jax.random.fold_in(key, 3), b, nb, n_pages,
                                np.array([2, 1]))
        vl = jnp.array([12, 5], jnp.int32)
        out = gqa_paged_decode_attention(q, kp, vp, tables, vl, scale=0.25, interpret=True)
        assert out.shape == (b, 1, h, d)
        ref = paged_decode_attention_ref(q[:, 0], kp, vp, tables, vl, scale=0.25)
        np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(ref),
                                   rtol=5e-5, atol=5e-5)


class TestCommonHelpers:
    def test_clamp_block(self):
        assert clamp_block(512, 100) == 100    # one tile covers the axis
        assert clamp_block(32, 100) == 32      # tile + padding
        assert clamp_block(64, 64) == 64
        with pytest.raises(ValueError):
            clamp_block(0, 10)

    def test_largest_divisor_block(self):
        assert largest_divisor_block(8, 12) == 6
        assert largest_divisor_block(4, 12) == 4
        assert largest_divisor_block(5, 7) == 1


class TestMLADecode:
    @pytest.mark.parametrize("b,h,rank,rope,l,blk", [
        (1, 8, 32, 8, 64, 32),
        (2, 16, 64, 16, 128, 64),
        (2, 4, 16, 8, 96, 32),
    ])
    def test_sweep(self, b, h, rank, rope, l, blk):
        key = jax.random.PRNGKey(b + h)
        ql = jax.random.normal(key, (b, h, rank))
        qr = jax.random.normal(jax.random.fold_in(key, 1), (b, h, rope))
        ckv = jax.random.normal(jax.random.fold_in(key, 2), (b, l, rank))
        kr = jax.random.normal(jax.random.fold_in(key, 3), (b, l, rope))
        vl = jax.random.randint(jax.random.fold_in(key, 4), (b,), 1, l + 1)
        out = mla_latent_decode(ql, qr, ckv, kr, vl, scale=0.12, block_l=blk, interpret=True)
        ref = mla_latent_decode_ref(ql, qr, ckv, kr, vl, scale=0.12)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=5e-5, atol=5e-5)

    def test_fused_path_equals_model_absorbed_decode(self):
        """mla_fused_decode == the model's absorbed einsum path."""
        from repro.models.config import ModelConfig, StageSpec
        from repro.models.mla import init_mla, _attend_absorbed, _mla_scale
        cfg = ModelConfig(
            name="t", family="dense", d_model=32, vocab_size=64,
            stages=(StageSpec(unit=("mla",), n_units=1),),
            n_heads=4, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, d_ff=64, param_dtype="float32", compute_dtype="float32",
        )
        p = init_mla(jax.random.PRNGKey(0), cfg, jnp.float32)
        B, L = 2, 32
        key = jax.random.PRNGKey(1)
        q_nope = jax.random.normal(key, (B, 1, cfg.n_heads, 8))
        q_rope = jax.random.normal(jax.random.fold_in(key, 1), (B, 1, cfg.n_heads, 4))
        ckv = jax.random.normal(jax.random.fold_in(key, 2), (B, L, 16))
        kr = jax.random.normal(jax.random.fold_in(key, 3), (B, L, 4))
        vl = jnp.array([L, 17], jnp.int32)

        mask = (jnp.arange(L)[None, :] < vl[:, None])[:, None, None, :]
        ref = _attend_absorbed(p, q_nope, q_rope, ckv, kr, mask, cfg, jnp.float32)[:, 0]
        out = mla_fused_decode(
            p["w_uk"], p["w_uv"], p["w_o"], q_nope[:, 0], q_rope[:, 0],
            ckv, kr, vl, scale=_mla_scale(cfg), block_l=16, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)


class TestPagedMLADecode:
    @pytest.mark.parametrize("b,h,rank,rope,bs,nb", [
        (1, 8, 32, 8, 8, 4),
        (2, 16, 64, 16, 16, 3),
        (2, 4, 16, 8, 8, 4),
    ])
    def test_sweep_vs_ref(self, b, h, rank, rope, bs, nb):
        key = jax.random.PRNGKey(b * 10 + h)
        n_pages = 1 + b * nb
        ql = jax.random.normal(key, (b, h, rank))
        qr = jax.random.normal(jax.random.fold_in(key, 1), (b, h, rope))
        cp = jax.random.normal(jax.random.fold_in(key, 2), (n_pages, bs, rank))
        krp = jax.random.normal(jax.random.fold_in(key, 3), (n_pages, bs, rope))
        valid_blocks = jax.random.randint(jax.random.fold_in(key, 4), (b,), 1, nb + 1)
        tables = _random_tables(jax.random.fold_in(key, 5), b, nb, n_pages, valid_blocks)
        vl = (valid_blocks - 1) * bs + jax.random.randint(
            jax.random.fold_in(key, 6), (b,), 1, bs + 1)
        out = mla_paged_latent_decode(ql, qr, cp, krp, tables, vl, scale=0.12, interpret=True)
        ref = mla_paged_latent_decode_ref(ql, qr, cp, krp, tables, vl, scale=0.12)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=5e-5, atol=5e-5)

    def test_paged_fused_equals_dense_fused(self):
        """mla_paged_fused_decode == mla_fused_decode on the gathered cache
        (same absorb einsums, paged latent kernel inside)."""
        from repro.models.config import ModelConfig, StageSpec
        from repro.models.mla import init_mla, _mla_scale
        cfg = ModelConfig(
            name="t", family="dense", d_model=32, vocab_size=64,
            stages=(StageSpec(unit=("mla",), n_units=1),),
            n_heads=4, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, d_ff=64, param_dtype="float32", compute_dtype="float32",
        )
        p = init_mla(jax.random.PRNGKey(0), cfg, jnp.float32)
        B, bs, nb = 2, 8, 3
        n_pages = 1 + B * nb
        key = jax.random.PRNGKey(1)
        q_nope = jax.random.normal(key, (B, cfg.n_heads, 8))
        q_rope = jax.random.normal(jax.random.fold_in(key, 1), (B, cfg.n_heads, 4))
        cp = jax.random.normal(jax.random.fold_in(key, 2), (n_pages, bs, 16))
        krp = jax.random.normal(jax.random.fold_in(key, 3), (n_pages, bs, 4))
        tables = _random_tables(jax.random.fold_in(key, 4), B, nb, n_pages,
                                np.array([3, 2]))
        vl = jnp.array([22, 11], jnp.int32)
        out = mla_paged_fused_decode(
            p["w_uk"], p["w_uv"], p["w_o"], q_nope, q_rope,
            cp, krp, tables, vl, scale=_mla_scale(cfg), interpret=True)
        ckv = cp[tables].reshape(B, nb * bs, 16)
        kr = krp[tables].reshape(B, nb * bs, 4)
        ref = mla_fused_decode(
            p["w_uk"], p["w_uv"], p["w_o"], q_nope, q_rope,
            ckv, kr, vl, scale=_mla_scale(cfg), block_l=bs, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)


class TestSSD:
    @pytest.mark.parametrize("b,s,h,p,n,q,hb", [
        (1, 32, 4, 16, 32, 8, 2),
        (2, 64, 8, 16, 32, 16, 4),
        (2, 48, 4, 32, 16, 16, 4),   # padding path (48 % 16 == 0 but hb sweep)
    ])
    def test_sweep(self, b, s, h, p, n, q, hb):
        key = jax.random.PRNGKey(s + h)
        x = jax.random.normal(key, (b, s, h, p)) * 0.5
        dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (b, s, h)))
        a = -jnp.exp(jnp.linspace(-2, 0.5, h))
        bm = jax.random.normal(jax.random.fold_in(key, 2), (b, s, n)) * 0.3
        cm = jax.random.normal(jax.random.fold_in(key, 3), (b, s, n)) * 0.3
        y, fs = ssd_prefill(x, dt, a, bm, cm, q_chunk=q, head_block=hb, interpret=True)
        yr, fsr = ssd_scan_ref(x, dt, a, bm, cm)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(fs), np.asarray(fsr), rtol=2e-4, atol=2e-4)

    def test_nondivisible_seq_padding(self):
        key = jax.random.PRNGKey(77)
        b, s, h, p, n = 1, 37, 4, 16, 16
        x = jax.random.normal(key, (b, s, h, p)) * 0.5
        dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (b, s, h)))
        a = -jnp.exp(jnp.linspace(-1, 0.3, h))
        bm = jax.random.normal(jax.random.fold_in(key, 2), (b, s, n)) * 0.3
        cm = jax.random.normal(jax.random.fold_in(key, 3), (b, s, n)) * 0.3
        y, fs = ssd_prefill(x, dt, a, bm, cm, q_chunk=16, head_block=4, interpret=True)
        yr, fsr = ssd_scan_ref(x, dt, a, bm, cm)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(fs), np.asarray(fsr), rtol=2e-4, atol=2e-4)

    def test_matches_model_chunked_formulation(self):
        """Kernel == the model's ssd_chunked (different algorithm, same math)."""
        from repro.models.ssm import ssd_chunked
        key = jax.random.PRNGKey(5)
        b, s, h, p, n = 2, 32, 4, 8, 16
        x = jax.random.normal(key, (b, s, h, p)) * 0.5
        dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (b, s, h)))
        a = -jnp.exp(jnp.linspace(-2, 0.5, h))
        bm = jax.random.normal(jax.random.fold_in(key, 2), (b, s, n)) * 0.3
        cm = jax.random.normal(jax.random.fold_in(key, 3), (b, s, n)) * 0.3
        y1, f1 = ssd_prefill(x, dt, a, bm, cm, q_chunk=8, head_block=2, interpret=True)
        y2, f2 = ssd_chunked(x, dt, a, bm[:, :, None], cm[:, :, None], 8)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(f1), np.asarray(f2), rtol=2e-4, atol=2e-4)


class TestGDN:
    @pytest.mark.parametrize("b,s,h,k,q", [
        (1, 16, 2, 16, 8),
        (2, 64, 4, 32, 32),
        (1, 50, 3, 16, 16),   # padding path
    ])
    def test_sweep(self, b, s, h, k, q):
        key = jax.random.PRNGKey(s)
        qv = jax.random.normal(key, (b, s, h, k))
        qv = qv / jnp.linalg.norm(qv, axis=-1, keepdims=True)
        kv = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, k))
        kv = kv / jnp.linalg.norm(kv, axis=-1, keepdims=True)
        vv = jax.random.normal(jax.random.fold_in(key, 2), (b, s, h, k)) * 0.5
        beta = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(key, 3), (b, s, h)))
        alpha = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(key, 4), (b, s, h)) + 2)
        y, fs = gdn_prefill(qv, kv, vv, beta, alpha, q_chunk=q, interpret=True)
        yr, fsr = gdn_scan_ref(qv, kv, vv, beta, alpha)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(fs), np.asarray(fsr), rtol=2e-4, atol=2e-4)

    def test_state_contraction_property(self):
        """With alpha=1, beta=1 and orthonormal keys the state stores v_t
        exactly at k_t (delta-rule associative memory)."""
        b, h, kd = 1, 1, 8
        s = kd
        eye = jnp.eye(kd)[None, :, None, :]            # keys = basis vectors
        q = eye
        k = eye
        v = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, kd))
        ones = jnp.ones((b, s, h))
        y, fs = gdn_prefill(q, k, v, ones, ones, q_chunk=4, interpret=True)
        # final state: S[k_i] row = v_i
        np.testing.assert_allclose(np.asarray(fs[0, 0]), np.asarray(v[0, :, 0]), rtol=1e-5, atol=1e-5)
