"""Fused gated-delta-rule Pallas kernel (GDN prefill/decode path).

The paper's §6.1 order-of-magnitude GDN prefill penalty is an artefact of
unfused eager execution: every token launches a zoo of elementwise kernels
and round-trips the (K, V) state through HBM. This kernel keeps the state
resident in VMEM scratch for the whole sequence: grid = (B, H, S/Q), chunk
axis sequential, inputs streamed once, the per-token rank-1 delta update
running entirely on-chip.

The recurrence itself is sequential (delta rule is order-dependent), so
within a chunk we iterate tokens with ``fori_loop`` — the fusion win is the
elimination of HBM state traffic and dispatch, which is exactly what the
paper attributes the gap to.

Layout: operands are head-major, (B, H, S, K) for q/k/v/y, so every
block's last two dims are a (Q, K) tile; the f32 gates ride in SMEM as
(B, H, 1, S), one scalar per token. Each chunk is staged into f32 VMEM scratch; token rows are read with
dynamic sublane slices, and k's column for the rank-1 update comes from a
transposed copy of the chunk.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(q_ref, k_ref, v_ref, beta_ref, alpha_ref, y_ref, fs_ref,
            state_ref, q_s, k_s, kt_s, v_s, y_s, *, q_chunk):
    z = pl.program_id(2)

    @pl.when(z == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    f32 = jnp.float32
    q_s[...] = q_ref[0, 0].astype(f32)              # (Q, K)
    k = k_ref[0, 0].astype(f32)
    k_s[...] = k
    kt_s[...] = k.T                                 # (K, Q)
    v_s[...] = v_ref[0, 0].astype(f32)
    lane = jax.lax.broadcasted_iota(jnp.int32, kt_s.shape, 1)

    def body(t, carry):
        kt = k_s[pl.ds(t, 1), :]                    # (1, K)
        vt = v_s[pl.ds(t, 1), :]                    # (1, V)
        qt = q_s[pl.ds(t, 1), :]
        bt = beta_ref[0, 0, 0, t]                   # scalars, from SMEM
        at = alpha_ref[0, 0, 0, t]
        k_col = jnp.sum(jnp.where(lane == t, kt_s[...], 0.0), axis=1,
                        keepdims=True)              # (K, 1)
        s = state_ref[...]                          # (K, V)
        ks = jnp.dot(kt, s, preferred_element_type=f32)          # (1, V)
        # alpha (S - beta k k^T S) + beta k v^T, as one rank-1 update
        s_new = at * s + k_col * (bt * (vt - at * ks))
        state_ref[...] = s_new
        y_s[pl.ds(t, 1), :] = jnp.dot(qt, s_new, preferred_element_type=f32)
        return carry

    jax.lax.fori_loop(0, q_chunk, body, 0)
    y_ref[0, 0] = y_s[...].astype(y_ref.dtype)

    @pl.when(z == pl.num_programs(2) - 1)
    def _emit():
        fs_ref[0, 0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("q_chunk", "interpret"))
def gdn_scan(
    q: jax.Array,       # (B, S, H, K)
    k: jax.Array,
    v: jax.Array,
    beta: jax.Array,    # (B, S, H)
    alpha: jax.Array,
    *,
    q_chunk: int = 128,
    interpret: bool = False,
):
    """-> (y (B,S,H,K) fp32-accurate, final_state (B,H,K,K) fp32)."""
    bsz, s, h, kd = q.shape
    assert s % q_chunk == 0, f"S={s} not a multiple of q_chunk={q_chunk}"
    nz = s // q_chunk
    f32 = jnp.float32
    heads = lambda x: jnp.swapaxes(x, 1, 2)         # (B, S, H, ..) -> (B, H, S, ..)
    gate = lambda x: heads(x.astype(f32))[:, :, None]   # (B, H, 1, S)
    row = pl.BlockSpec((1, 1, q_chunk, kd), lambda bi, hi, z: (bi, hi, z, 0))
    gates = pl.BlockSpec((1, 1, 1, q_chunk), lambda bi, hi, z: (bi, hi, 0, z),
                         memory_space=pltpu.SMEM)

    y, fs = pl.pallas_call(
        functools.partial(_kernel, q_chunk=q_chunk),
        grid=(bsz, h, nz),
        in_specs=[row, row, row, gates, gates],
        out_specs=[
            row,
            pl.BlockSpec((1, 1, kd, kd), lambda bi, hi, z: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, s, kd), q.dtype),
            jax.ShapeDtypeStruct((bsz, h, kd, kd), f32),
        ],
        scratch_shapes=[
            pltpu.VMEM((kd, kd), f32),              # recurrent state
            pltpu.VMEM((q_chunk, kd), f32),         # q chunk
            pltpu.VMEM((q_chunk, kd), f32),         # k chunk
            pltpu.VMEM((kd, q_chunk), f32),         # k chunk, transposed
            pltpu.VMEM((q_chunk, kd), f32),         # v chunk
            pltpu.VMEM((q_chunk, kd), f32),         # y chunk
        ],
        interpret=interpret,
    )(heads(q), heads(k), heads(v), gate(beta), gate(alpha))
    return heads(y), fs
