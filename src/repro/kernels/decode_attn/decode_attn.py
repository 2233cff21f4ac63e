"""Flash-decode Pallas TPU kernel: online-softmax attention over a blocked
KV cache for single-token decode.

TPU mapping of the paper's critical decode path (§4.1): the cache never
leaves HBM wholesale — it streams through VMEM in ``block_k``-row tiles
while the (G, Dk) query tile and the (G, Dv) accumulator stay resident in
VMEM scratch. Grid = (batch, kv_head, L/block_k); the KV-block axis is the
innermost (sequential) dimension, so scratch carries the online-softmax
state (m, l, acc) across blocks — the canonical TPU flash-decode schedule.
Per-request valid lengths (and the paged block table) are scalar-prefetch
operands in SMEM.

Layout: the TPU tiles the last two dims of every block, so a block that
takes ONE KV head must not have the head axis there. The model's caches
are (.., rows, KV, D); both entry points transpose them to (.., KV, rows,
D) before the call, making each block a (rows, D) tile. That transpose
copies the cache once per call — acceptable while no served path calls
these kernels; a head-major cache layout would remove it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38


def _kernel(valid_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, block):
    """One KV tile of online-softmax attention for one (batch, KV head):
    q (G, Dk) against k (block, Dk) / v (block, Dv). Tile j covers LOGICAL
    positions [j*block, (j+1)*block) — for the paged kernel, whichever
    physical page holds them — and positions >= the request's valid length
    are masked."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)               # (G, Dk)
    k = k_ref[0, 0].astype(jnp.float32)               # (block, Dk)
    v = v_ref[0, 0].astype(jnp.float32)               # (block, Dv)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                         # (G, block)

    kpos = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos < valid_ref[pl.program_id(0)], s, NEG_INF)

    m_prev = m_ref[...]                               # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                            # (G, block)
    corr = jnp.exp(m_prev - m_new)                    # (G, 1)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _paged_kernel(bt_ref, *refs, scale, block):
    # the block table only steers the index maps; the body is the dense one
    _kernel(*refs, scale=scale, block=block)


def _scratch(g: int, dv: int):
    return [
        pltpu.VMEM((g, 1), jnp.float32),
        pltpu.VMEM((g, 1), jnp.float32),
        pltpu.VMEM((g, dv), jnp.float32),
    ]


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention(
    q: jax.Array,             # (B, H, Dk)
    k_pages: jax.Array,       # (P, bs, KV, Dk) physical pages
    v_pages: jax.Array,       # (P, bs, KV, Dv)
    block_tables: jax.Array,  # (B, nb) int32: logical block -> physical page
    valid_len: jax.Array,     # (B,) int32
    *,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """Flash decode over a PAGED cache: K/V pages are gathered through the
    per-request block table instead of assuming contiguous rows.

    The table is a scalar-prefetch operand, so the page id is known before
    each grid step's DMA is issued — the (j -> block_tables[b, j]) indirection
    happens in the BlockSpec index map and the HBM->VMEM stream touches
    exactly the pages the table names. Table entries past a request's last
    block point at page 0 (the reserved null page); their rows are masked by
    ``valid_len`` like padding in the dense kernel. Grid = (batch, kv_head,
    nb) with the logical-block axis innermost carrying the online-softmax
    scratch, exactly like the dense schedule.
    """
    b, h, dk = q.shape
    bs, kv = k_pages.shape[1], k_pages.shape[2]
    dv = v_pages.shape[-1]
    nb = block_tables.shape[1]
    g = h // kv

    qg = q.reshape(b, kv, g, dk)
    kt = jnp.swapaxes(k_pages, 1, 2)                  # (P, KV, bs, Dk)
    vt = jnp.swapaxes(v_pages, 1, 2)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,              # block table + valid lengths
        grid=(b, kv, nb),
        in_specs=[
            pl.BlockSpec((1, 1, g, dk), lambda bi, ki, j, bt, vl: (bi, ki, 0, 0)),
            pl.BlockSpec((1, 1, bs, dk), lambda bi, ki, j, bt, vl: (bt[bi, j], ki, 0, 0)),
            pl.BlockSpec((1, 1, bs, dv), lambda bi, ki, j, bt, vl: (bt[bi, j], ki, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dv), lambda bi, ki, j, bt, vl: (bi, ki, 0, 0)),
        scratch_shapes=_scratch(g, dv),
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, block=bs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, dv), q.dtype),
        interpret=interpret,
    )(block_tables, valid_len, qg, kt, vt)
    return out.reshape(b, h, dv)


@functools.partial(jax.jit, static_argnames=("scale", "block_k", "interpret"))
def decode_attention(
    q: jax.Array,          # (B, H, Dk)
    k: jax.Array,          # (B, L, KV, Dk)
    v: jax.Array,          # (B, L, KV, Dv)
    valid_len: jax.Array,  # (B,) int32
    *,
    scale: float,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, h, dk = q.shape
    l, kv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = h // kv
    assert l % block_k == 0, f"L={l} must be a multiple of block_k={block_k}"
    nk = l // block_k

    qg = q.reshape(b, kv, g, dk)
    kt = jnp.swapaxes(k, 1, 2)                        # (B, KV, L, Dk)
    vt = jnp.swapaxes(v, 1, 2)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,              # valid lengths
        grid=(b, kv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g, dk), lambda bi, ki, j, vl: (bi, ki, 0, 0)),
            pl.BlockSpec((1, 1, block_k, dk), lambda bi, ki, j, vl: (bi, ki, j, 0)),
            pl.BlockSpec((1, 1, block_k, dv), lambda bi, ki, j, vl: (bi, ki, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dv), lambda bi, ki, j, vl: (bi, ki, 0, 0)),
        scratch_shapes=_scratch(g, dv),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block=block_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, dv), q.dtype),
        interpret=interpret,
    )(valid_len, qg, kt, vt)
    return out.reshape(b, h, dv)
