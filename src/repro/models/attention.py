"""Self-attention (GQA/MQA, sliding-window, softcap) and cross-attention.

Cache convention
----------------
A self-attention cache is a dict ``{"k": (B, L_max, n_kv, hd), "v": ...}``
plus an external per-example ``lengths: (B,) int32`` giving the number of
valid tokens already cached. A decode step attends over ``lengths + 1``
entries, the cached rows ``[0, lengths)`` and its new token's row, and
returns that row: the model writes every layer's rows into the cache after
its layer scan (``_write_at_lengths``). Which rows a dense decode step
reads depends on the path:

* the XLA path (``_decode_attend`` over ``_with_row_at_lengths``: the new
  row laid over the cache at ``lengths``) reads all ``L_max`` rows of
  every slot and masks the ones past ``lengths``. Paged decode, heads
  narrower than the TPU's 128 lanes, and every platform but the TPU take
  it;
* on the TPU, where ``decode_reads_stack(cfg)``, the layer scan hands the
  step the whole stacked cache and the layer's index, and the flash-decode
  kernel (``kernels/decode_attn``) streams only the blocks that hold each
  active slot's ``lengths`` rows (``kv_rows_read``), straight out of the
  stack, and folds the new row in on its own.

Cross-attention caches encoder K/V once at prefill; decode reuses them
unchanged (the paper's vision-layer semantics).

GQA is computed grouped: queries are reshaped to (B, S, n_kv, group, hd) so
the kv tensors are never materialised repeated — the same trick the fused
kernels use, keeping HLO bytes honest for the roofline analysis.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.decode_attn import fold_vmapped_axis, gqa_decode_attention, kv_rows_read
from repro.models.flash import attention_prefill_auto
from repro.models.layers import apply_rope, softcap_logits
from repro.models.unroll import unroll_enabled

NEG_INF = -2.3819763e38  # large negative, safe in bf16/fp32


def init_attention(key, cfg, dtype) -> Dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = 1.0 / np.sqrt(d)
    so = 1.0 / np.sqrt(h * hd)
    return {
        "wq": (jax.random.normal(k1, (d, h, hd)) * s).astype(dtype),
        "wk": (jax.random.normal(k2, (d, kv, hd)) * s).astype(dtype),
        "wv": (jax.random.normal(k3, (d, kv, hd)) * s).astype(dtype),
        "wo": (jax.random.normal(k4, (h, hd, d)) * so).astype(dtype),
    }


def _attn_scale(cfg) -> float:
    return cfg.attn_scale if cfg.attn_scale else 1.0 / np.sqrt(cfg.head_dim)


def _rope(x, positions, cfg):
    """Rotary positions on q or k rows; a NoPE config (``use_rope`` off)
    leaves them as they are and adds no op."""
    return apply_rope(x, positions, cfg.rope_theta) if cfg.use_rope else x


def _grouped_scores(q, k, scale, softcap):
    """q: (B,S,H,hd), k: (B,L,KV,hd) -> scores (B,KV,G,S,L) fp32."""
    b, s, h, hd = q.shape
    n_kv = k.shape[2]
    g = h // n_kv
    qg = q.reshape(b, s, n_kv, g, hd)
    scores = jnp.einsum(
        "bskgd,blkd->bkgsl", qg, k, preferred_element_type=jnp.float32
    )
    scores = scores * scale
    return softcap_logits(scores, softcap)


def _attend(scores, v, mask, out_dtype):
    """scores (B,KV,G,S,L) fp32; v (B,L,KV,hd); mask broadcastable to scores."""
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bkgsl,blkd->bskgd", probs.astype(v.dtype), v)
    b, s, n_kv, g, hd = ctx.shape
    return ctx.reshape(b, s, n_kv * g, hd).astype(out_dtype)


def _causal_mask(s: int, l: int, offset: int, window: int) -> jax.Array:
    """(s, l) mask: query i (global pos offset+i) may see key j iff j <= pos
    and, with a sliding window, pos - j < window."""
    qpos = offset + jnp.arange(s)[:, None]
    kpos = jnp.arange(l)[None, :]
    m = kpos <= qpos
    if window > 0:
        m &= (qpos - kpos) < window
    return m


def self_attention_prefill(
    params: Dict,
    x: jax.Array,                    # (B, S, d)
    cfg,
    *,
    is_global: bool,
    positions: Optional[jax.Array] = None,
    cache: Optional[Dict] = None,    # written at [0:S] when provided
) -> Tuple[jax.Array, Optional[Dict]]:
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    with jax.named_scope("attn"):
        q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
        k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
        q = _rope(q, positions, cfg)
        k = _rope(k, positions, cfg)

        window = 0 if is_global else cfg.sliding_window
        ctx = attention_prefill_auto(
            q, k, v,
            scale=_attn_scale(cfg),
            causal=True,
            window=window,
            softcap=cfg.attn_softcap,
        ).astype(x.dtype)
        out = jnp.einsum("bshk,hkd->bsd", ctx, params["wo"])

    if cache is not None:
        with jax.named_scope("kv_write"):
            cache = {
                "k": jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)),
                "v": jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)),
            }
    return out, cache


def self_attention_prefill_suffix(
    params: Dict,
    x: jax.Array,                    # (1, S, d) — suffix tokens only
    cache: Dict,                     # holds valid K/V for [0, prefix_len)
    prefix_len: jax.Array,           # (1,) int32, traced
    cfg,
    *,
    is_global: bool,
) -> Tuple[jax.Array, Dict]:
    """Prefill a suffix on top of an already-populated cache prefix.

    Prefix sharing hands admission a cache whose first ``prefix_len``
    positions were gathered from shared pages; only the un-shared suffix is
    projected and written (at positions ``prefix_len + i`` via a dynamic
    slice), and its queries attend over the whole buffer with the same
    logical-position mask ``_decode_attend`` uses — so the math matches a
    full prefill position-for-position. Batch is 1 (serving prefill shape):
    the write offset is per-example, so a batched version would need a
    ragged scatter.
    """
    b, s, _ = x.shape
    if b != 1:
        raise ValueError(f"suffix prefill is batch-1 (got batch={b})")
    positions = prefix_len[:, None] + jnp.arange(s)[None, :]   # (1, S)
    with jax.named_scope("attn"):
        q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
        k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
        q = _rope(q, positions, cfg)
        k = _rope(k, positions, cfg)

    off = prefix_len[0]
    with jax.named_scope("kv_write"):
        k_buf = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, off, 0, 0))
        v_buf = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, off, 0, 0))

    with jax.named_scope("attn"):
        l_max = k_buf.shape[1]
        kpos = jnp.arange(l_max)[None, None, :]                    # (1, 1, L)
        valid = kpos <= positions[:, :, None]                      # (1, S, L)
        if not is_global and cfg.sliding_window > 0:
            valid &= (positions[:, :, None] - kpos) < cfg.sliding_window
        mask = valid[:, None, None, :, :]                          # (1,1,1,S,L)

        scores = _grouped_scores(
            q, k_buf.astype(x.dtype), _attn_scale(cfg), cfg.attn_softcap)
        ctx = _attend(scores, v_buf.astype(x.dtype), mask, x.dtype)
        out = jnp.einsum("bshk,hkd->bsd", ctx, params["wo"])
    return out, {"k": k_buf, "v": v_buf}


@jax.named_scope("kv_write")
def _paged_token_write(
    pages: jax.Array,         # (n_units, P, bs, ...) physical pages; page 0 reserved/null
    new: jax.Array,           # (n_units, B, 1, ...) each layer's new row per request
    block_tables: jax.Array,  # (B, nb) logical block -> physical page id
    lengths: jax.Array,       # (B,) tokens already cached (write position)
    active: jax.Array,        # (B,) bool; inactive slots write to the null page
) -> jax.Array:
    """Per-request cache write through the block table, every layer at once.

    The dense path writes slot-private rows, so stale lengths on inactive
    slots are harmless; with paging a stale table could point at a page
    since reallocated to another request, so inactive writes are routed to
    the reserved null page 0 instead. So is a slot at ``lengths == nb * bs``,
    which, as on the dense path, writes nothing of its own.
    """
    bs = pages.shape[2]
    nb = block_tables.shape[1]
    blk = jnp.clip(lengths // bs, 0, nb - 1)
    phys = jnp.take_along_axis(block_tables, blk[:, None], axis=1)[:, 0]
    phys = jnp.where(active & (lengths < nb * bs), phys, 0)
    return pages.at[:, phys, lengths % bs].set(new[:, :, 0].astype(pages.dtype))


def _gather_pages(pages: jax.Array, block_tables: jax.Array) -> jax.Array:
    """(P, bs, ...) pages + (B, nb) table -> contiguous (B, nb*bs, ...) view."""
    b, nb = block_tables.shape
    bs = pages.shape[1]
    return pages[block_tables].reshape(b, nb * bs, *pages.shape[2:])


@jax.named_scope("kv_write")
def _write_at_lengths(buf: jax.Array, new: jax.Array, lengths: jax.Array) -> jax.Array:
    """Per-example cache write at ragged positions, every layer at once:
    buf (n_units, B, L, ...), new (n_units, B, 1, ...), lengths (B,).

    A loop of one dynamic-update-slice per slot, each holding every layer's
    row, which XLA writes in place when ``buf`` is donated, in any device
    layout (a scatter into a leaf narrower than 128 lanes, whose sequence
    axis the device lays out minor, copies the whole leaf first). A slot at
    ``lengths == L`` puts back the row it would clip to. Under ``jax.vmap``
    (replicas batched on one device) the loop runs over every replica's
    slots with scalar indices: a vmapped dynamic-slice would be a gather,
    for which XLA lays the whole cache out anew.
    """
    return _write_one(buf, new, lengths)


def _write_slots(buf: jax.Array, new: jax.Array, lengths: jax.Array) -> jax.Array:
    """The loop of ``_write_at_lengths`` over ``buf``'s slots, or, with a
    leading replica axis on all three (``lengths`` (R, B)), over every
    replica's slots."""
    rep = lengths.ndim - 1
    n_units, b, l = buf.shape[rep:rep + 3]
    tail = (0,) * (buf.ndim - rep - 3)
    size = (1,) * rep + (n_units, 1, 1) + buf.shape[rep + 3:]

    def write(i, buf):
        r = (i // b,) if rep else ()
        n = lengths[r + (i % b,)]
        at = r + (0, i % b, jnp.minimum(n, l - 1)) + tail
        old = jax.lax.dynamic_slice(buf, at, size)
        row = jax.lax.dynamic_slice(new, r + (0, i % b, 0) + tail, size)
        return jax.lax.dynamic_update_slice(
            buf, jnp.where(n < l, row.astype(buf.dtype), old), at)

    return jax.lax.fori_loop(0, lengths.size, write, buf)


# the vmapped axis becomes the replica axis, then folds into it
_write_one = jax.custom_batching.custom_vmap(_write_slots)
_write_replicas = jax.custom_batching.custom_vmap(_write_slots)


@_write_one.def_vmap
def _write_one_vmapped(n, batched, buf, new, lengths):
    lead = lambda x, bat: x if bat else jnp.broadcast_to(x, (n,) + x.shape)
    return _write_replicas(*map(lead, (buf, new, lengths), batched)), True


@_write_replicas.def_vmap
def _write_replicas_vmapped(n, batched, buf, new, lengths):
    out = _write_replicas(*(fold_vmapped_axis(x, bat, n)
                            for x, bat in zip((buf, new, lengths), batched)))
    return out.reshape((n, -1) + out.shape[1:]), True


@jax.named_scope("attn")
def _with_row_at_lengths(buf: jax.Array, new: jax.Array, lengths: jax.Array) -> jax.Array:
    """The view a decode step attends over: buf (B, L, ...) with each
    slot's new row new (B, 1, ...) in place of position ``lengths``. Only
    the attention reads it, so it fuses there and the cache is not written."""
    l = buf.shape[1]
    mask = jnp.arange(l)[None, :] == lengths[:, None]          # (B, L)
    mask = mask.reshape(mask.shape + (1,) * (buf.ndim - 2))
    return jnp.where(mask, new.astype(buf.dtype), buf)


@jax.named_scope("attn")
def _decode_qkv(params, x, lengths, cfg):
    """Shared decode-step projections: q and new-token k/v rows, q and k
    rope'd unless the config is NoPE."""
    positions = lengths[:, None]     # new token's position
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k_new = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v_new = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    q = _rope(q, positions, cfg)
    k_new = _rope(k_new, positions, cfg)
    return q, k_new, v_new


@jax.named_scope("attn")
def _decode_attend(params, q, k_buf, v_buf, lengths, cfg, is_global, out_dtype):
    """Masked grouped attention of one query row over a contiguous buffer —
    the buffer may be a dense slot row or a gathered page view; the mask is
    on LOGICAL positions either way."""
    l_max = k_buf.shape[1]
    kpos = jnp.arange(l_max)[None, :]                       # (1, L)
    valid = kpos <= lengths[:, None]                        # include new token
    if not is_global and cfg.sliding_window > 0:
        valid &= (lengths[:, None] - kpos) < cfg.sliding_window
    mask = valid[:, None, None, None, :]                    # (B,1,1,1,L)

    scores = _grouped_scores(q, k_buf.astype(out_dtype), _attn_scale(cfg), cfg.attn_softcap)
    ctx = _attend(scores, v_buf.astype(out_dtype), mask, out_dtype)
    return jnp.einsum("bshk,hkd->bsd", ctx, params["wo"])


def decode_reads_stack(cfg) -> bool:
    """Whether a dense decode step hands this config's attention the whole
    stacked cache and the layer's index, for the TPU kernel: heads of a
    multiple of the 128 lanes and KV heads of a multiple of the 8
    sublanes, in the scanned program (not the unrolled accounting
    lowering). A narrower leaf is laid out with its sequence axis minor,
    which the kernel would relayout every step; the kernel views a block's
    (rows, KV, hd) as (rows * KV, hd), a bitcast only where the KV heads
    fill the sublane tile (and Mosaic refuses the new row's scores for a
    single KV head)."""
    return (cfg.head_dim > 0 and cfg.head_dim % 128 == 0 and cfg.n_kv_heads % 8 == 0
            and not unroll_enabled())


def decode_rows_read(cfg, platform: str, lengths, active, max_len: int):
    """Rows of each slot's dense cache a decode step's attention streams on
    ``platform``, from the step's ``lengths`` and ``active`` (NumPy): the
    kernel's (``kv_rows_read``) where it runs, else all ``max_len``."""
    if platform == "tpu" and decode_reads_stack(cfg):
        return kv_rows_read(lengths, active, max_len)
    return np.full(np.shape(lengths), max_len)


@jax.named_scope("attn")
def _decode_attend_kernel(params, q, k_new, v_new, cache, unit, lengths, active,
                          cfg, is_global, out_dtype):
    """``_decode_attend`` over ``_with_row_at_lengths`` by the flash-decode
    kernel, reading layer ``unit`` of the stacked cache in place."""
    ctx = gqa_decode_attention(
        q, k_new, v_new, cache["k"], cache["v"], unit, lengths, active,
        scale=_attn_scale(cfg), softcap=cfg.attn_softcap,
        window=0 if is_global else cfg.sliding_window,
    )
    return jnp.einsum("bshk,hkd->bsd", ctx.astype(out_dtype), params["wo"])


def self_attention_decode(
    params: Dict,
    x: jax.Array,                    # (B, 1, d)
    cache: Dict,
    lengths: jax.Array,              # (B,) valid tokens already in cache
    cfg,
    *,
    is_global: bool,
    unit: Optional[jax.Array] = None,
    active: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict]:
    """One decode step over a dense cache, read only: returns the output
    and the new token's K/V rows ``{"k", "v"}: (B, 1, KV, hd)``, which the
    caller writes at ``lengths``. With ``unit``, ``cache`` is the whole
    stack ``(n_units, B, L, KV, hd)`` and the step reads layer ``unit``:
    by the kernel on the TPU, which reads no row of a slot that is not
    ``active`` (default: every slot), by the XLA path elsewhere."""
    q, k_new, v_new = _decode_qkv(params, x, lengths, cfg)
    k_new = k_new.astype(cache["k"].dtype)
    v_new = v_new.astype(cache["v"].dtype)

    def xla(cache):
        k_buf = _with_row_at_lengths(cache["k"], k_new, lengths)
        v_buf = _with_row_at_lengths(cache["v"], v_new, lengths)
        return _decode_attend(params, q, k_buf, v_buf, lengths, cfg, is_global, x.dtype)

    if unit is None:
        out = xla(cache)
    else:
        if active is None:
            active = jnp.ones(lengths.shape, bool)
        out = jax.lax.platform_dependent(
            tpu=lambda: _decode_attend_kernel(params, q, k_new, v_new, cache, unit,
                                              lengths, active, cfg, is_global, x.dtype),
            default=lambda: xla({n: c[unit] for n, c in cache.items()}))
    return out, {"k": k_new, "v": v_new}


def self_attention_decode_paged(
    params: Dict,
    x: jax.Array,                    # (B, 1, d)
    cache: Dict,                     # {"k": (P, bs, KV, hd), "v": ...} pages
    block_tables: jax.Array,         # (B, nb)
    lengths: jax.Array,              # (B,)
    cfg,
    *,
    is_global: bool,
) -> Tuple[jax.Array, Dict]:
    """Decode over the PAGED cache layout, read only: gather the table's
    pages to a contiguous view, lay the new token's row over it, attend;
    returns the output and the new K/V rows, which the caller writes
    through the block table (``_paged_token_write``).

    Same math as ``self_attention_decode`` — paging is pure layout — which
    is what the paged==dense property tests pin down. (On TPU the gather+
    attend is ``kernels.decode_attn.gqa_paged_decode_attention``, which
    streams exactly the pages the table names.)
    """
    q, k_new, v_new = _decode_qkv(params, x, lengths, cfg)
    k_new = k_new.astype(cache["k"].dtype)
    v_new = v_new.astype(cache["v"].dtype)
    k_buf = _with_row_at_lengths(_gather_pages(cache["k"], block_tables), k_new, lengths)
    v_buf = _with_row_at_lengths(_gather_pages(cache["v"], block_tables), v_new, lengths)
    out = _decode_attend(params, q, k_buf, v_buf, lengths, cfg, is_global, x.dtype)
    return out, {"k": k_new, "v": v_new}


# ----------------------------------------------------------------- cross-attn
def init_cross_attention(key, cfg, dtype) -> Dict:
    """Cross-attention to encoder states (vision/audio frontends).

    Encoder states arrive already projected to d_model (frontend stub), so
    K/V projections map d_model -> kv heads.
    """
    p = init_attention(key, cfg, dtype)
    k5 = jax.random.fold_in(key, 5)
    p["gate"] = jnp.zeros((), dtype=dtype)  # llama-3.2 zero-init attn gate
    return p


def cross_attention_encode(params: Dict, encoder_states: jax.Array) -> Dict:
    """Precompute encoder K/V once; reused across all decode steps."""
    k = jnp.einsum("bsd,dhk->bshk", encoder_states, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", encoder_states, params["wv"])
    return {"k": k, "v": v}


def cross_attention_apply(params: Dict, x: jax.Array, enc_cache: Dict, cfg) -> jax.Array:
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    scores = _grouped_scores(q, enc_cache["k"].astype(x.dtype), _attn_scale(cfg), cfg.attn_softcap)
    mask = jnp.ones(scores.shape[-2:], dtype=bool)[None, None, None]
    ctx = _attend(scores, enc_cache["v"].astype(x.dtype), mask, x.dtype)
    out = jnp.einsum("bshk,hkd->bsd", ctx, params["wo"])
    gate = jnp.tanh(params["gate"].astype(jnp.float32)).astype(x.dtype)
    return out * gate
