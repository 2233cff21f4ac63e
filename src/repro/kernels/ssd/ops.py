"""Jit'd wrapper: drop-in fused SSD prefill for the model's ssm block.

Handles padding to chunk multiples (dt=0 rows are exact no-ops) and head
blocks, and returns (y, final_state) in the model's cache layout.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.common import clamp_block, largest_divisor_block, pad_to_multiple
from repro.kernels.ssd.ssd import ssd_scan


@functools.partial(jax.jit, static_argnames=("q_chunk", "head_block", "interpret"))
def ssd_prefill(
    x: jax.Array,      # (B, S, H, P)
    dt: jax.Array,     # (B, S, H)
    a: jax.Array,      # (H,)
    b: jax.Array,      # (B, S, N)
    c: jax.Array,      # (B, S, N)
    *,
    q_chunk: int = 128,
    head_block: int = 8,
    interpret: bool = False,
):
    bsz, s, h, p = x.shape
    q_chunk = clamp_block(q_chunk, s)
    head_block = largest_divisor_block(head_block, h)
    # dt=0 rows are exact no-ops, so zero-padding the time axis is safe
    x = pad_to_multiple(x, q_chunk, axis=1)
    dt = pad_to_multiple(dt, q_chunk, axis=1)
    b = pad_to_multiple(b, q_chunk, axis=1)
    c = pad_to_multiple(c, q_chunk, axis=1)
    y, fs = ssd_scan(
        x, dt, a, b, c,
        q_chunk=q_chunk, head_block=head_block, interpret=interpret,
    )
    return y[:, :s], fs
