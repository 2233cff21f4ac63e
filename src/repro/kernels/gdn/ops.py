"""Jit'd wrapper for the fused GDN kernel (padding + model layout)."""
from __future__ import annotations

import functools

import jax

from repro.kernels.common import clamp_block, pad_to_multiple
from repro.kernels.gdn.gdn import gdn_scan


@functools.partial(jax.jit, static_argnames=("q_chunk", "interpret"))
def gdn_prefill(
    q: jax.Array,       # (B, S, H, K)
    k: jax.Array,
    v: jax.Array,
    beta: jax.Array,    # (B, S, H)
    alpha: jax.Array,
    *,
    q_chunk: int = 128,
    interpret: bool = False,
):
    bsz, s, h, kd = q.shape
    q_chunk = clamp_block(q_chunk, s)
    # beta=0 rows are exact no-ops (state untouched when alpha=1)
    q = pad_to_multiple(q, q_chunk, axis=1)
    k = pad_to_multiple(k, q_chunk, axis=1)
    v = pad_to_multiple(v, q_chunk, axis=1)
    beta = pad_to_multiple(beta, q_chunk, axis=1)
    alpha = pad_to_multiple(alpha, q_chunk, axis=1, value=1.0)
    y, fs = gdn_scan(q, k, v, beta, alpha, q_chunk=q_chunk, interpret=interpret)
    return y[:, :s], fs
