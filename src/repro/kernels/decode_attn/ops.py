"""Jit'd public wrappers for the flash-decode kernels.

``gqa_decode_attention`` takes the model's decode layout (q and the new
K/V rows with their query axis, the stacked (n_units, B, L, KV, hd) cache
and the layer's index); ``gqa_paged_decode_attention`` takes the paged layout
((P, bs, KV, hd) pages + a per-request block table) as-is. Both compile
for the TPU by default; CPU callers pass ``interpret=True``.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.decode_attn.decode_attn import decode_attention, paged_decode_attention


@functools.partial(jax.jit, static_argnames=("scale", "softcap", "window", "interpret"))
def gqa_decode_attention(
    q: jax.Array,          # (B, 1, H, hd) or (B, H, hd)
    k_new: jax.Array,      # (B, 1, KV, hd) or (B, KV, hd)
    v_new: jax.Array,
    k_cache: jax.Array,    # (n_units, B, L, KV, hd) stacked over layers
    v_cache: jax.Array,
    unit: jax.Array,       # () the layer to read
    lengths: jax.Array,    # (B,)
    active: jax.Array,     # (B,) bool
    *,
    scale: float,
    softcap: float = 0.0,
    window: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """Dense flash decode in the model's decode layout: the step's q and new
    K/V rows with their query axis, and the stacked cache as it is held."""
    squeeze = q.ndim == 4
    if squeeze:
        q, k_new, v_new = q[:, 0], k_new[:, 0], v_new[:, 0]
    out = decode_attention(
        q, k_new, v_new, k_cache, v_cache, unit, lengths, active,
        scale=scale, softcap=softcap, window=window, interpret=interpret,
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
