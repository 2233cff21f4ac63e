"""Chunked SSD (Mamba2) Pallas kernel — the fused recurrent prefill the
paper's §7.2 predicts would close the order-of-magnitude gap.

TPU mapping of the SSD duality: within a chunk of Q tokens the recurrence
is computed as dense (Q x Q)/(Q x N) matmuls on the MXU (intra-chunk
"attention-like" term), while the cross-chunk state (P, N) of each head is
carried in VMEM scratch across the sequential chunk axis — one HBM pass
over the inputs, no per-token state round-trips (the eager baseline's
downfall).

Grid = (B, H/hb, S/Q); chunk axis innermost/sequential; the hb heads of a
block are unrolled, each as 2-D tiles. Requires a single B/C group (all
assigned SSM configs use ssm_groups=1).

Layout: the wrapper makes the operands head-major so every block's last two
dims are a whole tile: x as (B, H, S, P) and, for the state update, (B, H,
P, S); dt as a column (B, H, S, 1) and a row (B, H, 1, S). The per-head
decay rates ride in SMEM. Prefix sums over the chunk are masked lane or
sublane reductions, so nothing needs an in-kernel transpose.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, x_ref, xt_ref, dtc_ref, dtr_ref, b_ref, c_ref, y_ref, fs_ref,
            state_ref, *, q_chunk, head_block):
    hi = pl.program_id(1)
    z = pl.program_id(2)

    @pl.when(z == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    f32 = jnp.float32
    bm = b_ref[0].astype(f32)         # (Q, N)
    cm = c_ref[0].astype(f32)         # (Q, N)
    # intra-chunk scores c_i . b_j, shared by every head of the block
    cb = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=f32
    )                                  # (Q, Q)
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (q_chunk, q_chunk), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (q_chunk, q_chunk), 1)
    causal = iota_i >= iota_j

    for h in range(head_block):
        a = a_ref[hi * head_block + h]                # scalar decay rate
        x = x_ref[0, h].astype(f32)                   # (Q, P)
        xt = xt_ref[0, h].astype(f32)                 # (P, Q)
        dt_c = dtc_ref[0, h]                          # (Q, 1)
        dt_r = dtr_ref[0, h]                          # (1, Q)
        # inclusive prefix sums of the log-decays, as a column and a row
        cum_c = jnp.sum(jnp.where(causal, dt_r * a, 0.0), axis=1,
                        keepdims=True)                # (Q, 1)
        cum_r = jnp.sum(jnp.where(iota_i <= iota_j, dt_c * a, 0.0), axis=0,
                        keepdims=True)                # (1, Q)
        chunk_decay = jnp.sum(dt_r * a, axis=1, keepdims=True)    # (1, 1)

        # intra-chunk: y_i += sum_{j<=i} (c_i.b_j) exp(cum_i-cum_j) dt_j x_j
        # (mask inside the exp: masked exponents are large-positive)
        w = cb * jnp.exp(jnp.where(causal, cum_c - cum_r, -jnp.inf)) * dt_r
        y = jnp.dot(w, x, preferred_element_type=f32)             # (Q, P)

        # inter-chunk: y_i += exp(cum_i) * c_i . state
        state = state_ref[h]                                      # (P, N)
        y += jax.lax.dot_general(
            cm, state, (((1,), (1,)), ((), ())), preferred_element_type=f32
        ) * jnp.exp(cum_c)

        # state = state*exp(chunk_decay) + sum_j exp(cd-cum_j) dt_j x_j b_j
        to_end = jnp.exp(chunk_decay - cum_r) * dt_r              # (1, Q)
        sloc = jnp.dot(xt * to_end, bm, preferred_element_type=f32)   # (P, N)
        decay = jnp.exp(jnp.sum(jnp.broadcast_to(dt_r, xt.shape), axis=1,
                                keepdims=True) * a)               # (P, 1)
        state_ref[h] = state * decay + sloc

        y_ref[0, h] = y.astype(y_ref.dtype)

    @pl.when(z == pl.num_programs(2) - 1)
    def _emit_state():
        fs_ref[0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("q_chunk", "head_block", "interpret"))
def ssd_scan(
    x: jax.Array,      # (B, S, H, P)
    dt: jax.Array,     # (B, S, H)
    a: jax.Array,      # (H,)
    b: jax.Array,      # (B, S, N) — single group
    c: jax.Array,      # (B, S, N)
    *,
    q_chunk: int = 128,
    head_block: int = 8,
    interpret: bool = False,
):
    """-> (y (B,S,H,P), final_state (B,H,P,N) fp32)."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    assert s % q_chunk == 0, f"S={s} not a multiple of q_chunk={q_chunk}"
    assert h % head_block == 0, f"H={h} not a multiple of head_block={head_block}"
    nz = s // q_chunk
    nhb = h // head_block
    f32 = jnp.float32
    xh = jnp.swapaxes(x, 1, 2)                        # (B, H, S, P)
    dth = jnp.swapaxes(dt.astype(f32), 1, 2)          # (B, H, S)

    y, final_state = pl.pallas_call(
        functools.partial(_kernel, q_chunk=q_chunk, head_block=head_block),
        grid=(bsz, nhb, nz),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, head_block, q_chunk, p), lambda bi, hi, z: (bi, hi, z, 0)),
            pl.BlockSpec((1, head_block, p, q_chunk), lambda bi, hi, z: (bi, hi, 0, z)),
            pl.BlockSpec((1, head_block, q_chunk, 1), lambda bi, hi, z: (bi, hi, z, 0)),
            pl.BlockSpec((1, head_block, 1, q_chunk), lambda bi, hi, z: (bi, hi, 0, z)),
            pl.BlockSpec((1, q_chunk, n), lambda bi, hi, z: (bi, z, 0)),
            pl.BlockSpec((1, q_chunk, n), lambda bi, hi, z: (bi, z, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, head_block, q_chunk, p), lambda bi, hi, z: (bi, hi, z, 0)),
            pl.BlockSpec((1, head_block, p, n), lambda bi, hi, z: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), f32),
        ],
        scratch_shapes=[pltpu.VMEM((head_block, p, n), f32)],
        interpret=interpret,
    )(a.astype(f32), xh, jnp.swapaxes(xh, 2, 3), dth[..., None], dth[:, :, None],
      b, c)
    return jnp.swapaxes(y, 1, 2), final_state
