"""Multi-head Latent Attention (DeepSeek-V2) — compressed-KV paradigm.

Two decode paths are provided, mirroring the paper's §6.2:

* ``absorb=False`` — the *naive / vLLM-like* path: every step decompresses the
  whole latent cache back to full per-head K/V (``w_uk``/``w_uv`` einsums over
  all cached positions). This is the data-movement machinery the paper blames
  for 90 % of the MLA–GQA gap. It is the faithful baseline.
* ``absorb=True`` — the *fused/absorbed* path the paper calls for: ``w_uk`` is
  absorbed into the query and ``w_uv`` into the output projection, so
  attention runs directly in the compressed latent space and the cache is
  never decompressed. ``repro.kernels.mla_decode`` implements the same math
  as a single VMEM-tiled Pallas kernel.

Latent cache: ``{"ckv": (B, L, kv_lora), "kr": (B, L, rope_dim)}`` —
``kv_lora + rope_dim`` bytes/token (576 dims for DeepSeek-V2, vs 2·n_kv·hd
for GQA; the 3.6x compression of the paper's TransMLA pair).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.attention import NEG_INF, _gather_pages, _with_row_at_lengths
from repro.models.flash import attention_prefill_auto
from repro.models.layers import apply_rope, rmsnorm, init_rmsnorm


def init_mla(key, cfg, dtype) -> Dict:
    d = cfg.d_model
    h = cfg.n_heads
    rank, rope, nope, vdim = (
        cfg.kv_lora_rank,
        cfg.qk_rope_head_dim,
        cfg.qk_nope_head_dim,
        cfg.v_head_dim,
    )
    keys = jax.random.split(key, 8)
    s = 1.0 / np.sqrt(d)
    sr = 1.0 / np.sqrt(rank)
    p = {
        "w_dkv": (jax.random.normal(keys[0], (d, rank)) * s).astype(dtype),
        "w_kr": (jax.random.normal(keys[1], (d, rope)) * s).astype(dtype),
        "w_uk": (jax.random.normal(keys[2], (rank, h, nope)) * sr).astype(dtype),
        "w_uv": (jax.random.normal(keys[3], (rank, h, vdim)) * sr).astype(dtype),
        "w_o": (jax.random.normal(keys[4], (h, vdim, d)) * (1.0 / np.sqrt(h * vdim))).astype(dtype),
        "norm_kv": init_rmsnorm(rank, dtype),
    }
    if cfg.q_lora_rank:
        p["w_dq"] = (jax.random.normal(keys[5], (d, cfg.q_lora_rank)) * s).astype(dtype)
        p["norm_q"] = init_rmsnorm(cfg.q_lora_rank, dtype)
        p["w_uq"] = (
            jax.random.normal(keys[6], (cfg.q_lora_rank, h, nope + rope))
            * (1.0 / np.sqrt(cfg.q_lora_rank))
        ).astype(dtype)
    else:
        p["w_uq"] = (jax.random.normal(keys[7], (d, h, nope + rope)) * s).astype(dtype)
    return p


def _mla_scale(cfg) -> float:
    return 1.0 / np.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def _queries(params, x, positions, cfg):
    """-> q_nope (B,S,H,nope), q_rope (B,S,H,rope) with RoPE applied."""
    if cfg.q_lora_rank:
        cq = rmsnorm(params["norm_q"], x @ params["w_dq"], cfg.rms_eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, params["w_uq"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, params["w_uq"])
    q_nope = q[..., : cfg.qk_nope_head_dim]
    q_rope = apply_rope(q[..., cfg.qk_nope_head_dim :], positions, cfg.rope_theta)
    return q_nope, q_rope


def _latents(params, x, positions, cfg):
    """-> ckv (B,S,rank) normalised latent, kr (B,S,rope) rotary shared key."""
    ckv = rmsnorm(params["norm_kv"], x @ params["w_dkv"], cfg.rms_eps)
    kr = (x @ params["w_kr"])[:, :, None, :]  # (B,S,1,rope) single shared head
    kr = apply_rope(kr, positions, cfg.rope_theta)[:, :, 0, :]
    return ckv, kr


def _attend_naive(params, q_nope, q_rope, ckv, kr, mask, cfg, out_dtype):
    """Decompress latents to full K/V, then standard attention.

    The decompression einsums materialise (B, L, H, nope) and (B, L, H, v) —
    the per-step data movement the paper identifies as MLA's decode tax.
    """
    k_nope = jnp.einsum("blr,rhk->blhk", ckv, params["w_uk"])  # decompress K
    v = jnp.einsum("blr,rhk->blhk", ckv, params["w_uv"])       # decompress V
    scores = jnp.einsum("bshk,blhk->bhsl", q_nope, k_nope, preferred_element_type=jnp.float32)
    scores += jnp.einsum("bshk,blk->bhsl", q_rope, kr, preferred_element_type=jnp.float32)
    scores = scores * _mla_scale(cfg)
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhsl,blhk->bshk", probs.astype(v.dtype), v)
    return jnp.einsum("bshk,hkd->bsd", ctx, params["w_o"]).astype(out_dtype)


def _attend_absorbed(params, q_nope, q_rope, ckv, kr, mask, cfg, out_dtype):
    """Attention in latent space; cache never decompressed."""
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, params["w_uk"])  # absorb w_uk
    scores = jnp.einsum("bshr,blr->bhsl", q_lat, ckv, preferred_element_type=jnp.float32)
    scores += jnp.einsum("bshk,blk->bhsl", q_rope, kr, preferred_element_type=jnp.float32)
    scores = scores * _mla_scale(cfg)
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx_lat = jnp.einsum("bhsl,blr->bshr", probs.astype(ckv.dtype), ckv)
    ctx = jnp.einsum("bshr,rhk->bshk", ctx_lat, params["w_uv"])   # absorb w_uv
    return jnp.einsum("bshk,hkd->bsd", ctx, params["w_o"]).astype(out_dtype)


def _attend_absorbed_blocked(params, q_nope, q_rope, ckv, kr, cfg, out_dtype):
    """Absorbed attention via the generic blocked kernel.

    MLA's absorbed form *is* MQA with one shared latent KV head:
    K = [ckv; kr] (Dk = rank+rope), V = ckv (Dv = rank). This lets the same
    flash machinery (and the same Pallas kernel on TPU) serve MLA prefill,
    bounding memory at long context.
    """
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, params["w_uk"])
    q_cat = jnp.concatenate([q_lat, q_rope], axis=-1)            # (B,S,H,rank+rope)
    k_cat = jnp.concatenate([ckv, kr], axis=-1)[:, :, None, :]   # (B,L,1,rank+rope)
    v_lat = ckv[:, :, None, :]                                   # (B,L,1,rank)
    ctx_lat = attention_prefill_auto(
        q_cat, k_cat, v_lat, scale=_mla_scale(cfg), causal=True
    )
    ctx = jnp.einsum("bshr,rhk->bshk", ctx_lat.astype(ckv.dtype), params["w_uv"])
    return jnp.einsum("bshk,hkd->bsd", ctx, params["w_o"]).astype(out_dtype)


def mla_prefill(
    params: Dict,
    x: jax.Array,
    cfg,
    *,
    positions: Optional[jax.Array] = None,
    cache: Optional[Dict] = None,
    absorb: bool = False,
) -> Tuple[jax.Array, Optional[Dict]]:
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    with jax.named_scope("attn"):
        q_nope, q_rope = _queries(params, x, positions, cfg)
        ckv, kr = _latents(params, x, positions, cfg)
        if absorb:
            out = _attend_absorbed_blocked(params, q_nope, q_rope, ckv, kr, cfg, x.dtype)
        else:
            mask = (jnp.arange(s)[:, None] >= jnp.arange(s)[None, :])[None, None]
            out = _attend_naive(params, q_nope, q_rope, ckv, kr, mask, cfg, x.dtype)
    if cache is not None:
        with jax.named_scope("kv_write"):
            cache = {
                "ckv": jax.lax.dynamic_update_slice(cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, 0, 0)),
                "kr": jax.lax.dynamic_update_slice(cache["kr"], kr.astype(cache["kr"].dtype), (0, 0, 0)),
            }
    return out, cache


def mla_decode(
    params: Dict,
    x: jax.Array,                   # (B, 1, d)
    cache: Dict,
    lengths: jax.Array,             # (B,)
    cfg,
    *,
    absorb: bool,
) -> Tuple[jax.Array, Dict]:
    """One decode step over the dense latent cache, read only: returns the
    output and the new token's latent rows ``{"ckv", "kr"}: (B, 1, ...)``,
    which the caller writes at ``lengths``."""
    return _decode_over(params, x, cache["ckv"], cache["kr"], lengths, cfg, absorb)


def mla_decode_paged(
    params: Dict,
    x: jax.Array,                   # (B, 1, d)
    cache: Dict,                    # {"ckv": (P, bs, rank), "kr": (P, bs, rope)}
    block_tables: jax.Array,        # (B, nb)
    lengths: jax.Array,             # (B,)
    cfg,
    *,
    absorb: bool,
) -> Tuple[jax.Array, Dict]:
    """Absorbed MLA decode over the PAGED latent cache, read only: gather
    the table's pages, lay the new latent over them, attend; the caller
    writes the returned rows through the block table. Same math as
    ``mla_decode`` — and the compressed cache makes each page
    ``(rank + rope) * bs`` bytes, the 3.6x traffic reduction the paged
    traffic meter makes visible per block. TPU kernel counterpart:
    ``kernels.mla_decode.mla_paged_fused_decode``."""
    return _decode_over(params, x, _gather_pages(cache["ckv"], block_tables),
                        _gather_pages(cache["kr"], block_tables), lengths, cfg, absorb)


def _decode_over(params, x, ckv_cache, kr_cache, lengths, cfg, absorb):
    """Decode attention over contiguous latent buffers (B, L, ...) with the
    new token's latents laid over position ``lengths``."""
    positions = lengths[:, None]
    with jax.named_scope("attn"):
        q_nope, q_rope = _queries(params, x, positions, cfg)
        ckv_new, kr_new = _latents(params, x, positions, cfg)
        ckv_new = ckv_new.astype(ckv_cache.dtype)
        kr_new = kr_new.astype(kr_cache.dtype)
        ckv_buf = _with_row_at_lengths(ckv_cache, ckv_new, lengths)
        kr_buf = _with_row_at_lengths(kr_cache, kr_new, lengths)
        l_max = ckv_buf.shape[1]
        mask = (jnp.arange(l_max)[None, :] <= lengths[:, None])[:, None, None, :]
        attend = _attend_absorbed if absorb else _attend_naive
        out = attend(
            params, q_nope, q_rope, ckv_buf.astype(x.dtype), kr_buf.astype(x.dtype), mask, cfg, x.dtype
        )
    return out, {"ckv": ckv_new, "kr": kr_new}
