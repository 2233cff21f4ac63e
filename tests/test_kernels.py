"""Per-kernel validation: shape/dtype sweeps, allclose vs the ref.py oracles
(interpret mode on CPU, per the kernel contract)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
    clamp_block,
    decode_attention,
    decode_attention_ref,
    gdn_prefill,
    gdn_scan_ref,
    gqa_decode_attention,
    gqa_paged_decode_attention,
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


class TestDecodeAttn:
    @pytest.mark.parametrize("b,h,kv,dk,dv,l,blk", [
        (1, 4, 1, 16, 16, 64, 32),      # MQA
        (2, 8, 2, 32, 16, 128, 64),     # GQA, asymmetric dv
        (3, 6, 6, 16, 16, 96, 32),      # MHA
        (2, 4, 2, 64, 64, 256, 256),    # single block
    ])
    def test_shapes_sweep(self, b, h, kv, dk, dv, l, blk):
        key = jax.random.PRNGKey(b * 1000 + h)
        q = jax.random.normal(key, (b, h, dk), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, l, kv, dk), jnp.float32)
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, l, kv, dv), jnp.float32)
        vl = jax.random.randint(jax.random.fold_in(key, 3), (b,), 1, l + 1)
        out = decode_attention(q, k, v, vl, scale=0.2, block_k=blk, interpret=True)
        ref = decode_attention_ref(q, k, v, vl, scale=0.2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL[jnp.float32])

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        key = jax.random.PRNGKey(9)
        b, h, kv, d, l = 2, 4, 2, 32, 128
        q = jax.random.normal(key, (b, h, d)).astype(dtype)
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, l, kv, d)).astype(dtype)
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, l, kv, d)).astype(dtype)
        vl = jnp.array([l, l // 2], jnp.int32)
        out = decode_attention(q, k, v, vl, scale=0.18, block_k=64, interpret=True)
        ref = decode_attention_ref(
            q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32), vl, scale=0.18
        )
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref), **TOL[dtype]
        )

    def test_wrapper_pads_nondivisible_length(self):
        key = jax.random.PRNGKey(11)
        b, h, kv, d, l = 2, 4, 2, 16, 100   # 100 not a block multiple
        q = jax.random.normal(key, (b, h, d))
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, l, kv, d))
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, l, kv, d))
        vl = jnp.array([100, 37], jnp.int32)
        out = gqa_decode_attention(q, k, v, vl, scale=0.25, block_k=32, interpret=True)
        ref = decode_attention_ref(q, k, v, vl, scale=0.25)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=5e-5, atol=5e-5)

    def test_single_valid_token(self):
        key = jax.random.PRNGKey(12)
        b, h, kv, d, l = 1, 2, 1, 16, 64
        q = jax.random.normal(key, (b, h, d))
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, l, kv, d))
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, l, kv, d))
        vl = jnp.array([1], jnp.int32)
        out = decode_attention(q, k, v, vl, scale=1.0, block_k=32, interpret=True)
        np.testing.assert_allclose(np.asarray(out)[0], np.asarray(v)[0, 0, 0][None].repeat(2, 0), rtol=1e-5)


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
        ref = decode_attention(q, k_dense, v_dense, vl, scale=0.18, block_k=bs, interpret=True)
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
