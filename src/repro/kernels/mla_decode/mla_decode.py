"""Fused MLA latent-decode Pallas kernel.

The TPU answer to the paper's MLA decode tax (§6.2): vLLM's path emits
hundreds of cat/copy/reshape kernels per step reconstructing full KV heads
from latents — 90 % of the MLA–GQA gap. Here attention runs *directly on
the compressed cache*: one kernel, latent tiles streamed HBM->VMEM once,
online softmax in VMEM scratch, no decompression traffic at all.

Structure: MQA with a single shared latent "head". The rope and nope score
contributions are fused by concatenating along the feature axis at the
caller ([q_lat; q_rope] vs [ckv; kr]); the kernel contracts (H, rank+rope)
x (block_l, rank+rope) tiles on the MXU and weights ckv tiles for the
context. Grid = (B, L/block_l) with the L axis innermost/sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38


def _kernel(valid_ref, q_ref, kcat_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, block_l, rank):
    j = pl.program_id(1)
    nl = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                  # (H, rank+rope)
    kcat = kcat_ref[0].astype(jnp.float32)            # (block_l, rank+rope)

    s = jax.lax.dot_general(
        q, kcat, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                         # (H, block_l)
    kpos = j * block_l + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos < valid_ref[pl.program_id(0)], s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    # context accumulates against the latent (first `rank` features of kcat)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, kcat[:, :rank], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(j == nl - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _paged_kernel(bt_ref, valid_ref, qlat_ref, qrope_ref, ckv_ref, kr_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale, block_size):
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lat = qlat_ref[0].astype(jnp.float32)           # (H, rank)
    q_rope = qrope_ref[0].astype(jnp.float32)         # (H, rope)
    ckv = ckv_ref[0].astype(jnp.float32)              # (block_size, rank)
    kr = kr_ref[0].astype(jnp.float32)                # (block_size, rope)

    # rope and latent score contributions summed tile-locally — the two
    # page arrays stay separate operands so NOTHING outside the table's
    # pages is ever copied or streamed
    s = (
        jax.lax.dot_general(q_lat, ckv, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        + jax.lax.dot_general(q_rope, kr, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    ) * scale                                         # (H, block_size)
    # logical position of this table slot; valid_ref is whole-array
    # scalar-prefetch, indexed by the batch grid coordinate
    kpos = j * block_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos < valid_ref[pl.program_id(0)], s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, ckv, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(j == nb - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def mla_paged_latent_decode(
    q_lat: jax.Array,         # (B, H, rank)
    q_rope: jax.Array,        # (B, H, rope)
    ckv_pages: jax.Array,     # (P, bs, rank) physical latent pages
    kr_pages: jax.Array,      # (P, bs, rope)
    block_tables: jax.Array,  # (B, nb) logical block -> physical page
    valid_len: jax.Array,     # (B,)
    *,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """Absorbed-MLA latent decode over a PAGED compressed cache.

    Same math as ``mla_latent_decode``, but the latent tiles are gathered
    through the per-request block table: the scalar-prefetched table drives
    the BlockSpec index maps, so each grid step streams exactly one ckv and
    one kr page HBM->VMEM — the page arrays are separate operands (unlike
    the dense kernel's host-side concat, which would copy the WHOLE pool
    every call) and the rope/latent score halves are summed tile-locally.
    Grid = (B, nb), logical-block axis innermost carrying the
    online-softmax scratch. Table entries past the last block point at the
    reserved null page 0 and are masked by ``valid_len``.
    """
    b, h, rank = q_lat.shape
    rope = q_rope.shape[-1]
    bs = ckv_pages.shape[1]
    nb = block_tables.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,              # block table + valid lengths
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec((1, h, rank), lambda bi, j, bt, vl: (bi, 0, 0)),
            pl.BlockSpec((1, h, rope), lambda bi, j, bt, vl: (bi, 0, 0)),
            pl.BlockSpec((1, bs, rank), lambda bi, j, bt, vl: (bt[bi, j], 0, 0)),
            pl.BlockSpec((1, bs, rope), lambda bi, j, bt, vl: (bt[bi, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, rank), lambda bi, j, bt, vl: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, rank), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, block_size=bs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, rank), q_lat.dtype),
        interpret=interpret,
    )(block_tables, valid_len, q_lat, q_rope, ckv_pages, kr_pages)
    return out


@functools.partial(jax.jit, static_argnames=("scale", "block_l", "interpret"))
def mla_latent_decode(
    q_lat: jax.Array,      # (B, H, rank)
    q_rope: jax.Array,     # (B, H, rope)
    ckv: jax.Array,        # (B, L, rank)
    kr: jax.Array,         # (B, L, rope)
    valid_len: jax.Array,  # (B,)
    *,
    scale: float,
    block_l: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, h, rank = q_lat.shape
    rope = q_rope.shape[-1]
    l = ckv.shape[1]
    assert l % block_l == 0, f"L={l} not a multiple of block_l={block_l}"
    nl = l // block_l

    q_cat = jnp.concatenate([q_lat, q_rope], axis=-1)            # (B,H,rank+rope)
    k_cat = jnp.concatenate([ckv, kr], axis=-1)                  # (B,L,rank+rope)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,              # valid lengths
        grid=(b, nl),
        in_specs=[
            pl.BlockSpec((1, h, rank + rope), lambda bi, j, vl: (bi, 0, 0)),
            pl.BlockSpec((1, block_l, rank + rope), lambda bi, j, vl: (bi, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, rank), lambda bi, j, vl: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, rank), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_l=block_l, rank=rank),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, rank), q_lat.dtype),
        interpret=interpret,
    )(valid_len, q_cat, k_cat)
    return out
