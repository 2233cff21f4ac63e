"""Jit'd fused-MLA decode wrapper: full absorbed attention step.

``mla_fused_decode(params, q_nope, q_rope, cache, valid_len)`` performs
absorb(w_uk) -> latent flash-decode kernel -> absorb(w_uv) -> w_o, i.e.
the complete decode-attention path over the compressed cache. The two
absorb einsums are dense (H-batched) GEMMs XLA schedules well; the
cache-touching inner loop — the part the paper shows dominating MLA's
decode energy — runs in the Pallas kernel with zero decompression traffic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import clamp_block, pad_to_multiple
from repro.kernels.mla_decode.mla_decode import mla_latent_decode, mla_paged_latent_decode


@functools.partial(jax.jit, static_argnames=("scale", "block_l", "interpret"))
def mla_fused_decode(
    w_uk: jax.Array,       # (rank, H, nope)
    w_uv: jax.Array,       # (rank, H, vdim)
    w_o: jax.Array,        # (H, vdim, d)
    q_nope: jax.Array,     # (B, H, nope)
    q_rope: jax.Array,     # (B, H, rope)
    ckv: jax.Array,        # (B, L, rank)
    kr: jax.Array,         # (B, L, rope)
    valid_len: jax.Array,  # (B,)
    *,
    scale: float,
    block_l: int = 512,
    interpret: bool = False,
) -> jax.Array:            # (B, d)
    blk = clamp_block(block_l, ckv.shape[1])
    ckv = pad_to_multiple(ckv, blk, axis=1)
    kr = pad_to_multiple(kr, blk, axis=1)
    q_lat = jnp.einsum("bhk,rhk->bhr", q_nope, w_uk)
    ctx_lat = mla_latent_decode(
        q_lat, q_rope, ckv, kr, valid_len,
        scale=scale, block_l=blk, interpret=interpret,
    )
    ctx = jnp.einsum("bhr,rhk->bhk", ctx_lat.astype(w_uv.dtype), w_uv)
    return jnp.einsum("bhk,hkd->bd", ctx, w_o)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def mla_paged_fused_decode(
    w_uk: jax.Array,          # (rank, H, nope)
    w_uv: jax.Array,          # (rank, H, vdim)
    w_o: jax.Array,           # (H, vdim, d)
    q_nope: jax.Array,        # (B, H, nope)
    q_rope: jax.Array,        # (B, H, rope)
    ckv_pages: jax.Array,     # (P, bs, rank)
    kr_pages: jax.Array,      # (P, bs, rope)
    block_tables: jax.Array,  # (B, nb)
    valid_len: jax.Array,     # (B,)
    *,
    scale: float,
    interpret: bool = False,
) -> jax.Array:               # (B, d)
    """Full absorbed decode step over the PAGED latent cache: absorb(w_uk)
    -> paged latent kernel -> absorb(w_uv) -> w_o. No padding — the page
    size is the tile size."""
    q_lat = jnp.einsum("bhk,rhk->bhr", q_nope, w_uk)
    ctx_lat = mla_paged_latent_decode(
        q_lat, q_rope, ckv_pages, kr_pages, block_tables, valid_len,
        scale=scale, interpret=interpret,
    )
    ctx = jnp.einsum("bhr,rhk->bhk", ctx_lat.astype(w_uv.dtype), w_uv)
    return jnp.einsum("bhk,hkd->bd", ctx, w_o)
