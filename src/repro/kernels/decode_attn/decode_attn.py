"""Flash-decode Pallas TPU kernels: online-softmax attention of one new
token per slot over a blocked KV cache.

TPU mapping of the paper's critical decode path (§4.1): the cache never
leaves HBM wholesale — it streams through VMEM in blocks of rows while the
query tile and the fp32 accumulator stay resident in VMEM scratch. The
KV-block axis is the innermost (sequential) grid dimension, so scratch
carries the online-softmax state (m, l, acc) across blocks. What steers
the DMAs (lengths, the layer's index, the paged block table) is a
scalar-prefetch operand in SMEM.

``decode_attention`` (dense) is the one the model serves. It reads one
layer of the stacked ``(n_units, B, L, KV, D)`` cache where it lies, in
the layout the cache already has: a block is ``block_rows(L)`` positions
with all KV heads, ``(rows * KV, D)``, so every q head is scored against
every row of the block and the rows of the other KV heads are masked out.
Per slot it streams only the blocks that hold its ``lengths`` rows: a
block past them is clamped to the slot's last one, so the pipeline issues
no DMA for it, and an empty slot (inactive) points at the block the grid
step before it read. The new token's K/V row comes in on its own and
opens the online softmax. Under ``jax.vmap`` (replicas batched on one
device) a batching rule folds the replicas into the kernel's slots, so
each replica's stacked cache is still read where it lies.

``paged_decode_attention`` takes one KV head per block, so its wrapper
transposes the pages head-major first, a copy of the cache per call; no
served path calls it.
"""
from __future__ import annotations

import functools
import math

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


# Positions per block of the dense kernel: each grid step streams this many
# rows of K and of V, all KV heads (qwen3-4b: 512 x 8 x 128 bf16, 1 MiB each).
BLOCK_ROWS = 512


def block_rows(max_len: int) -> int:
    """Positions per block of the dense kernel over a cache of ``max_len``
    rows: ``BLOCK_ROWS``, or the largest power of two below it that divides
    ``max_len``."""
    return math.gcd(max_len, BLOCK_ROWS)


def kv_rows_read(lengths, active, max_len: int):
    """Rows of each slot's cache the dense kernel streams: the rows the slot
    holds (``lengths``, at most ``max_len``) rounded up to whole blocks, and
    none for an inactive slot. NumPy or JAX arrays."""
    blk = block_rows(max_len)
    return (lengths.clip(0, max_len) + blk - 1) // blk * blk * active


def _dense_kernel(rep_ref, unit_ref, len_ref, nblk_ref, src_ref, lo_ref, hi_ref,
                  q_ref, kn_ref, vn_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, scale, softcap, window, block, max_len):
    """Slot b, block j: q (H, Dk) against the block's (block * KV, Dk) rows,
    row r holding position ``j * block + r // KV`` of KV head ``r % KV``.
    Block 0's step first folds in the new token's row, which sits at
    position ``lengths[b]`` (left out where that is ``max_len``: the slot
    is full and its row is not cached)."""
    del rep_ref, unit_ref, src_ref, lo_ref, hi_ref   # they steer the DMAs only
    b, j = pl.program_id(0), pl.program_id(1)
    n = len_ref[b]
    q = q_ref[0]                                   # (H, Dk)
    n_kv = kn_ref.shape[1]
    group = q.shape[0] // n_kv

    def scores(k):
        """Scores of every q head against rows k (R, Dk), and the mask of
        the rows of each head's own KV head."""
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        return s, col, col % n_kv == row // group

    def fold(s, valid, v, m_prev, l_prev, acc_prev):
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_prev * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _new_row():
        s, _, own = scores(kn_ref[0])              # (H, KV)
        fold(s, own & (n < max_len), vn_ref[0],
             jnp.full(m_ref.shape, NEG_INF, jnp.float32),
             jnp.zeros(l_ref.shape, jnp.float32), jnp.zeros(acc_ref.shape, jnp.float32))

    @pl.when(j < nblk_ref[b])
    def _block():
        s, col, own = scores(k_ref[...])           # (H, block * KV)
        first = j * block                          # position of row 0
        valid = own & (col < (n - first) * n_kv)   # position < lengths
        if window > 0:                             # lengths - position < window
            valid &= col >= (n - window + 1 - first) * n_kv
        fold(s, valid, v_ref[...], m_ref[...], l_ref[...], acc_ref[...])

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _dense_call(q, k_new, v_new, k_cache, v_cache, rep, unit, lengths, active, *,
                scale, softcap, window, interpret):
    """The dense kernel over slots in groups of B: group g's slots are the
    B slots of layer ``unit[g]`` of replica ``rep[g]`` of
    ``k_cache`` / ``v_cache`` (R, n_units, B, L, KV, D)."""
    _, n_units, b, l, n_kv, dk = k_cache.shape
    s, h, dv = q.shape[0], q.shape[1], v_cache.shape[-1]
    blk = block_rows(l)
    nk = l // blk
    lengths = lengths.astype(jnp.int32)
    nblk = (kv_rows_read(lengths, active, l) // blk).astype(jnp.int32)
    # slot i reads its blocks [0, nblk); an empty slot re-points at the last
    # block read before it (the latest slot with blocks, by a running max of
    # slot * nk + last block), so the pipeline issues no DMA for it
    has = nblk > 0
    last = jnp.maximum(jax.lax.cummax(
        jnp.where(has, jnp.arange(s, dtype=jnp.int32) * nk + nblk - 1, -1)), 0)
    src, hi = last // nk, last % nk
    lo = jnp.where(has, 0, hi)

    def kv_map(i, j, rep, unit, lens, nblk, src, lo, hi):
        g = src[i] // b
        return rep[g], unit[g], src[i] % b, jnp.minimum(jnp.maximum(j, lo[i]), hi[i]), 0

    def slot_map(i, j, *_):
        return i, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(s, nk),
        in_specs=[
            pl.BlockSpec((1, h, dk), slot_map),
            pl.BlockSpec((1, n_kv, dk), slot_map),
            pl.BlockSpec((1, n_kv, dv), slot_map),
            pl.BlockSpec((None, None, None, blk * n_kv, dk), kv_map),
            pl.BlockSpec((None, None, None, blk * n_kv, dv), kv_map),
        ],
        out_specs=pl.BlockSpec((1, h, dv), slot_map),
        scratch_shapes=_scratch(h, dv),
    )
    kernel = functools.partial(_dense_kernel, scale=scale, softcap=softcap,
                               window=window, block=blk, max_len=l)
    r = k_cache.shape[0]
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        # inside shard_map the output varies over the mesh axes q does
        out_shape=jax.ShapeDtypeStruct((s, h, dv), q.dtype, vma=jax.typeof(q).vma),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(rep.astype(jnp.int32), unit.astype(jnp.int32), lengths, nblk, src, lo, hi,
      q, k_new, v_new,
      k_cache.reshape(r, n_units, b, l * n_kv, dk),
      v_cache.reshape(r, n_units, b, l * n_kv, dv))


def fold_vmapped_axis(x: jax.Array, batched: bool, n: int) -> jax.Array:
    """In a ``custom_vmap`` rule: ``x`` with the vmapped axis (leading, of
    size ``n``; repeated where ``x`` is not batched) merged into its first
    axis, which leading axes of a tiled layout do as a bitcast."""
    if not batched:
        x = jnp.broadcast_to(x, (n,) + x.shape)
    return x.reshape((-1,) + x.shape[2:])


@functools.lru_cache(maxsize=None)
def _dense(scale, softcap, window, interpret):
    """``_dense_call`` with a batching rule: a vmapped axis folds into the
    slot axis (and, where the cache carries it, into the replica axis), so
    K replicas batched by ``jax.vmap`` run as one kernel over K x B slots,
    each reading its own replica's cache where it lies."""
    kw = dict(scale=scale, softcap=softcap, window=window, interpret=interpret)

    @jax.custom_batching.custom_vmap
    def dense(q, k_new, v_new, k_cache, v_cache, rep, unit, lengths, active):
        return _dense_call(q, k_new, v_new, k_cache, v_cache, rep, unit, lengths,
                           active, **kw)

    @dense.def_vmap
    def _rule(n, batched, q, k_new, v_new, k_cache, v_cache, rep, unit, lengths,
              active):
        fold = lambda x, bat: fold_vmapped_axis(x, bat, n)
        (q_b, kn_b, vn_b, kc_b, vc_b, rep_b, unit_b, len_b, act_b) = batched
        if kc_b or vc_b:                # replica r of batch member i: i * R + r
            r = k_cache.shape[kc_b]
            rep = (rep if rep_b else rep[None]) + r * jnp.arange(n, dtype=rep.dtype)[:, None]
            rep_b = True
            k_cache, v_cache = fold(k_cache, kc_b), fold(v_cache, vc_b)
        out = dense(fold(q, q_b), fold(k_new, kn_b), fold(v_new, vn_b), k_cache, v_cache,
                    fold(rep, rep_b), fold(unit, unit_b), fold(lengths, len_b),
                    fold(active, act_b))
        return out.reshape((n, -1) + out.shape[1:]), True

    return dense


@functools.partial(jax.jit, static_argnames=("scale", "softcap", "window", "interpret"))
def decode_attention(
    q: jax.Array,          # (B, H, Dk)
    k_new: jax.Array,      # (B, KV, Dk) the new token's rows
    v_new: jax.Array,      # (B, KV, Dv)
    k_cache: jax.Array,    # (n_units, B, L, KV, Dk) stacked over layers
    v_cache: jax.Array,    # (n_units, B, L, KV, Dv)
    unit: jax.Array,       # () int: the layer to read
    lengths: jax.Array,    # (B,) int: rows cached before the new token
    active: jax.Array,     # (B,) bool: an inactive slot reads no row
    *,
    scale: float,
    softcap: float = 0.0,
    window: int = 0,
    interpret: bool = False,
) -> jax.Array:            # (B, H, Dv)
    """Attention of each slot's new token over layer ``unit``'s cached rows
    ``[0, lengths)`` and its own row: ``_decode_attend`` over
    ``_with_row_at_lengths`` (``models/attention.py``), reading only the
    blocks that hold those rows. ``window > 0`` keeps the positions less
    than ``window`` behind the new token. Under ``jax.vmap`` (replicas
    batched on one device) it stays one kernel over every replica's slots,
    reading each replica's stacked cache in place."""
    return _dense(scale, softcap, window, interpret)(
        q, k_new, v_new, k_cache[None], v_cache[None], jnp.zeros((1,), jnp.int32),
        jnp.reshape(unit, (1,)), lengths, active)
