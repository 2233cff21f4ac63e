"""Jit'd public wrappers for the flash-decode kernels.

``gqa_decode_attention`` adapts the model's dense cache layout
((B, L, KV, hd) + per-request lengths) to the kernel and pads L to the
block size; ``gqa_paged_decode_attention`` takes the paged layout
((P, bs, KV, hd) pages + a per-request block table) as-is. Both compile
for the TPU by default; CPU callers pass ``interpret=True``.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.common import clamp_block, pad_to_multiple
from repro.kernels.decode_attn.decode_attn import decode_attention, paged_decode_attention


@functools.partial(jax.jit, static_argnames=("scale", "block_k", "interpret"))
def gqa_decode_attention(
    q: jax.Array,          # (B, 1, H, hd) or (B, H, hd)
    k_cache: jax.Array,    # (B, L, KV, hd)
    v_cache: jax.Array,    # (B, L, KV, hd)
    valid_len: jax.Array,  # (B,)
    *,
    scale: float,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    squeeze = q.ndim == 4
    if squeeze:
        q = q[:, 0]
    block_k = clamp_block(block_k, k_cache.shape[1])
    k_cache = pad_to_multiple(k_cache, block_k, axis=1)
    v_cache = pad_to_multiple(v_cache, block_k, axis=1)
    out = decode_attention(
        q, k_cache, v_cache, valid_len,
        scale=scale, block_k=block_k, interpret=interpret,
    )
    return out[:, None] if squeeze else out


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def gqa_paged_decode_attention(
    q: jax.Array,             # (B, 1, H, hd) or (B, H, hd)
    k_pages: jax.Array,       # (P, bs, KV, hd) physical KV pages
    v_pages: jax.Array,       # (P, bs, KV, hd)
    block_tables: jax.Array,  # (B, nb) logical block -> physical page id
    valid_len: jax.Array,     # (B,)
    *,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """Paged-cache flash decode: the model's block-table layout, unmodified.

    No padding is ever needed — the page size IS the block size, and the
    table width fixes the logical sequence extent.
    """
    squeeze = q.ndim == 4
    if squeeze:
        q = q[:, 0]
    out = paged_decode_attention(
        q, k_pages, v_pages, block_tables, valid_len,
        scale=scale, interpret=interpret,
    )
    return out[:, None] if squeeze else out
