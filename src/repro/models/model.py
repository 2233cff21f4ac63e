"""Decoder assembly: stages of scanned units.

Public API (all pure functions):

    init_params(cfg, key)                  -> param pytree (concrete)
    init_params_jit(cfg, key, sharding)    -> the same, as one compiled program
    abstract_params(cfg)                   -> ShapeDtypeStruct pytree
    init_cache(cfg, batch, max_len)        -> cache pytree (concrete zeros)
    abstract_cache(cfg, batch, max_len)    -> ShapeDtypeStruct pytree
    init_paged_cache(cfg, batch, n_pages, block_size)  -> paged cache pytree
    paged_layout(cfg)                      -> bool pytree (paged vs slot leaves)
    forward(params, cfg, tokens/embeds, enc_states=None)       # train: (B,S,d) final hidden
    prefill(params, cfg, tokens, cache, enc_states=None)       # -> (last_logits, cache, lengths)
    decode_step(params, cfg, token, cache, lengths, enc_states=None, active=None)  # -> (logits, cache, lengths+1)
    decode_step_paged(params, cfg, token, cache, lengths, active, block_tables)

Depth is organised as ``cfg.stages``: each stage scans ``n_units`` copies of
a short block tuple, with per-unit params (and caches) stacked on a leading
axis. ``shared_attn`` blocks read params from the single, non-stacked
``params["shared_block"]`` (zamba2 semantics) while keeping per-position KV
caches in the scanned stack.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import gdn as gdn_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.config import ModelConfig, StageSpec
from repro.models.sharding_hints import constrain_batch
from repro.models.unroll import unroll_enabled
from repro.models.layers import (
    embed,
    init_embedding,
    init_mlp,
    init_rmsnorm,
    mlp,
    rmsnorm,
    softcap_logits,
    unembed,
)


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def _cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


# ------------------------------------------------------------------- params
def _init_block(kind: str, cfg: ModelConfig, key, dtype) -> Dict:
    d = cfg.d_model
    keys = jax.random.split(key, 4)
    if kind in ("attn", "attn_global"):
        return {
            "norm1": init_rmsnorm(d, dtype),
            "attn": attn.init_attention(keys[0], cfg, dtype),
            "norm2": init_rmsnorm(d, dtype),
            "mlp": init_mlp(keys[1], d, cfg.d_ff, cfg.mlp_type, dtype),
        }
    if kind == "cross_attn":
        return {
            "norm1": init_rmsnorm(d, dtype),
            "xattn": attn.init_cross_attention(keys[0], cfg, dtype),
            "norm2": init_rmsnorm(d, dtype),
            "mlp": init_mlp(keys[1], d, cfg.d_ff, cfg.mlp_type, dtype),
        }
    if kind == "mla":
        return {
            "norm1": init_rmsnorm(d, dtype),
            "mla": mla_mod.init_mla(keys[0], cfg, dtype),
            "norm2": init_rmsnorm(d, dtype),
            "mlp": init_mlp(keys[1], d, cfg.d_ff, cfg.mlp_type, dtype),
        }
    if kind == "mla_moe":
        return {
            "norm1": init_rmsnorm(d, dtype),
            "mla": mla_mod.init_mla(keys[0], cfg, dtype),
            "norm2": init_rmsnorm(d, dtype),
            "moe": moe_mod.init_moe(keys[1], cfg, dtype),
        }
    if kind == "ssm":
        return {
            "norm1": init_rmsnorm(d, dtype),
            "ssm": ssm_mod.init_ssm(keys[0], cfg, dtype),
        }
    if kind == "ssm_mlp":
        return {
            "norm1": init_rmsnorm(d, dtype),
            "ssm": ssm_mod.init_ssm(keys[0], cfg, dtype),
            "norm2": init_rmsnorm(d, dtype),
            "mlp": init_mlp(keys[1], d, cfg.d_ff, cfg.mlp_type, dtype),
        }
    if kind == "gdn":
        return {
            "norm1": init_rmsnorm(d, dtype),
            "gdn": gdn_mod.init_gdn(keys[0], cfg, dtype),
            "norm2": init_rmsnorm(d, dtype),
            "mlp": init_mlp(keys[1], d, cfg.d_ff, cfg.mlp_type, dtype),
        }
    if kind == "shared_attn":
        return {}  # params live in params["shared_block"]
    raise ValueError(kind)


def init_params(cfg: ModelConfig, key) -> Dict:
    dtype = _dtype(cfg)
    n_stage_keys = len(cfg.stages)
    keys = jax.random.split(key, n_stage_keys + 3)
    params: Dict[str, Any] = {
        "embed": init_embedding(keys[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": init_rmsnorm(cfg.d_model, dtype),
    }
    kinds = set(cfg.block_kinds_flat())
    if "shared_attn" in kinds:
        params["shared_block"] = {
            "norm1": init_rmsnorm(cfg.d_model, dtype),
            "attn": attn.init_attention(keys[1], cfg, dtype),
            "norm2": init_rmsnorm(cfg.d_model, dtype),
            "mlp": init_mlp(keys[2], cfg.d_model, cfg.d_ff, cfg.mlp_type, dtype),
        }
    stages = []
    for si, stage in enumerate(cfg.stages):
        def init_unit(unit_key, _stage=stage):
            uks = jax.random.split(unit_key, len(_stage.unit))
            return {
                f"b{i}": _init_block(kind, cfg, uks[i], dtype)
                for i, kind in enumerate(_stage.unit)
            }
        unit_keys = jax.random.split(jax.random.fold_in(keys[-1], si), stage.n_units)
        stages.append(jax.vmap(init_unit)(unit_keys))
    params["stages"] = stages
    return params


def init_params_jit(cfg: ModelConfig, key, sharding=None) -> Dict:
    """``init_params`` as one compiled program: each weight is drawn and
    cast in place, so no float32 copy of a whole stacked weight is ever
    held (eagerly, qwen3-4b's ``w_up`` alone is a 3.6 GB transient).
    ``sharding`` places the result, e.g. replicated over a mesh so every
    device draws its own copy instead of receiving one."""
    return jax.jit(init_params, static_argnums=0, out_shardings=sharding)(cfg, key)


def abstract_params(cfg: ModelConfig):
    key = jax.random.PRNGKey(0)
    return jax.eval_shape(lambda: init_params(cfg, key))


# -------------------------------------------------------------------- cache
def _block_cache(kind: str, cfg: ModelConfig, batch: int, max_len: int):
    cd = _cdtype(cfg)
    if kind in ("attn", "attn_global", "shared_attn"):
        shape = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shape, cd), "v": jnp.zeros(shape, cd)}
    if kind == "cross_attn":
        shape = (batch, cfg.n_media_tokens, cfg.n_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shape, cd), "v": jnp.zeros(shape, cd)}
    if kind in ("mla", "mla_moe"):
        return {
            "ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), cd),
            "kr": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), cd),
        }
    if kind in ("ssm", "ssm_mlp"):
        d_inner, heads, p, n, g, conv_dim = ssm_mod._dims(cfg)
        return {
            "ssm": jnp.zeros((batch, heads, p, n), jnp.float32),
            "conv": jnp.zeros((batch, cfg.ssm_conv_kernel - 1, conv_dim), cd),
        }
    if kind == "gdn":
        return {
            "gdn": jnp.zeros((batch, cfg.gdn_heads, cfg.gdn_head_dim, cfg.gdn_head_dim), jnp.float32)
        }
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    stages = []
    for stage in cfg.stages:
        unit = {
            f"b{i}": _block_cache(kind, cfg, batch, max_len)
            for i, kind in enumerate(stage.unit)
        }
        stages.append(
            jax.tree.map(lambda a, n=stage.n_units: jnp.zeros((n,) + a.shape, a.dtype), unit)
        )
    return {"stages": stages}


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int):
    return jax.eval_shape(lambda: init_cache(cfg, batch, max_len))


# -------------------------------------------------------------- paged cache
# Block kinds whose cache grows per token and therefore lives in pages;
# O(1)-state kinds (ssm/gdn) and the fixed encoder cache (cross_attn) stay
# slot-indexed dense even in a paged cache.
PAGED_KINDS = ("attn", "attn_global", "shared_attn", "mla", "mla_moe")
# Block kinds whose O(1) state a decode step replaces whole: the decode scan
# carries their stacked state and writes each unit's in place.
STATE_KINDS = ("ssm", "ssm_mlp", "gdn")
# Block kinds whose dense decode reads its layer out of the whole stacked
# cache where ``attn.decode_reads_stack`` holds: the decode scan closes over
# the stack and passes the layer's index instead of slicing the layer out.
STACKED_KINDS = ("attn", "attn_global", "shared_attn")


def _block_paged_cache(kind: str, cfg: ModelConfig, batch: int, n_pages: int,
                       block_size: int):
    cd = _cdtype(cfg)
    if kind in ("attn", "attn_global", "shared_attn"):
        shape = (n_pages, block_size, cfg.n_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shape, cd), "v": jnp.zeros(shape, cd)}
    if kind in ("mla", "mla_moe"):
        return {
            "ckv": jnp.zeros((n_pages, block_size, cfg.kv_lora_rank), cd),
            "kr": jnp.zeros((n_pages, block_size, cfg.qk_rope_head_dim), cd),
        }
    return _block_cache(kind, cfg, batch, block_size)


def init_paged_cache(cfg: ModelConfig, batch: int, n_pages: int, block_size: int) -> Dict:
    """Paged decode cache: per-token caches live in ``n_pages`` physical
    pages of ``block_size`` tokens (page 0 reserved as the null/trash page),
    shared by all requests through per-request block tables; O(1) state
    stays a dense ``batch``-row array. Same pytree structure as
    ``init_cache``, so the scanned stages are oblivious to the layout."""
    stages = []
    for stage in cfg.stages:
        unit = {
            f"b{i}": _block_paged_cache(kind, cfg, batch, n_pages, block_size)
            for i, kind in enumerate(stage.unit)
        }
        stages.append(
            jax.tree.map(lambda a, n=stage.n_units: jnp.zeros((n,) + a.shape, a.dtype), unit)
        )
    return {"stages": stages}


def paged_layout(cfg: ModelConfig) -> Dict:
    """Boolean pytree matching the cache structure: True leaves are paged
    (block-table indexed), False leaves are slot indexed. The serving layer
    maps over (cache, layout) to scatter migrations leaf-appropriately."""
    stages = []
    for stage in cfg.stages:
        unit = {}
        for i, kind in enumerate(stage.unit):
            struct = jax.eval_shape(lambda k=kind: _block_cache(k, cfg, 1, 1))
            unit[f"b{i}"] = jax.tree.map(lambda _, k=kind: k in PAGED_KINDS, struct)
        stages.append(unit)
    return {"stages": stages}


# ------------------------------------------------------------------ forward
def _scaled(x: jax.Array, multiplier: float) -> jax.Array:
    """``x * multiplier`` rounded once to ``x``'s dtype; no op at 1.0."""
    if multiplier == 1.0:
        return x
    return (x.astype(jnp.float32) * multiplier).astype(x.dtype)


def _residual(cfg: ModelConfig, x: jax.Array, branch: jax.Array) -> jax.Array:
    """The residual add of one branch, scaled by ``residual_multiplier``."""
    return x + _scaled(branch, cfg.residual_multiplier)


def _block_apply(
    kind: str,
    bp: Dict,
    x: jax.Array,
    cfg: ModelConfig,
    mode: str,                      # train | prefill | decode
    cache: Optional[Dict],
    lengths: Optional[jax.Array],
    shared_params: Optional[Dict],
    enc_states: Optional[jax.Array],
    block_tables: Optional[jax.Array] = None,   # paged decode only
    prefix_len: Optional[jax.Array] = None,     # suffix prefill only
    unit: Optional[jax.Array] = None,           # dense decode over a stacked cache
    active: Optional[jax.Array] = None,         # with ``unit``: the live slots
) -> Tuple[jax.Array, Optional[Dict]]:
    """One block: ``(x, what it writes)``. In train and prefill mode that is
    the block's new cache; in decode mode it is what ``_decode_write``
    persists after the layer scan: the new token's rows for a per-token
    cache, the whole new state for ssm/gdn, nothing for cross-attention.
    With ``unit``, an attention block's ``cache`` is its whole stack and it
    reads layer ``unit`` of it (``attn.self_attention_decode``)."""
    if kind == "shared_attn":
        bp = shared_params
        kind_eff = "attn_global"
    else:
        kind_eff = kind

    if prefix_len is not None and kind_eff not in ("attn", "attn_global"):
        raise NotImplementedError(
            f"suffix prefill (prefix sharing) supports attention-family "
            f"blocks only, got {kind!r}")

    if kind_eff in ("attn", "attn_global"):
        is_global = kind_eff == "attn_global"
        h = rmsnorm(bp["norm1"], x, cfg.rms_eps)
        if mode == "decode" and block_tables is not None:
            a_out, new_cache = attn.self_attention_decode_paged(
                bp["attn"], h, cache, block_tables, lengths, cfg,
                is_global=is_global,
            )
        elif mode == "decode":
            a_out, new_cache = attn.self_attention_decode(
                bp["attn"], h, cache, lengths, cfg, is_global=is_global,
                unit=unit, active=active,
            )
        elif mode == "prefill" and prefix_len is not None:
            a_out, new_cache = attn.self_attention_prefill_suffix(
                bp["attn"], h, cache, prefix_len, cfg, is_global=is_global,
            )
        else:
            a_out, new_cache = attn.self_attention_prefill(
                bp["attn"], h, cfg, is_global=is_global,
                cache=cache if mode == "prefill" else None,
            )
        x = _residual(cfg, x, a_out)
        h = rmsnorm(bp["norm2"], x, cfg.rms_eps)
        x = _residual(cfg, x, mlp(bp["mlp"], h, cfg.mlp_type))
        return x, new_cache

    if kind_eff == "cross_attn":
        h = rmsnorm(bp["norm1"], x, cfg.rms_eps)
        if mode == "train":
            enc_cache = attn.cross_attention_encode(bp["xattn"], enc_states)
            new_cache = None
        elif mode == "prefill":
            enc_cache = attn.cross_attention_encode(bp["xattn"], enc_states)
            new_cache = {
                "k": enc_cache["k"].astype(cache["k"].dtype),
                "v": enc_cache["v"].astype(cache["v"].dtype),
            }
        else:  # decode: reuse cached encoder K/V, write nothing
            enc_cache = cache
            new_cache = None
        a_out = attn.cross_attention_apply(bp["xattn"], h, enc_cache, cfg)
        x = _residual(cfg, x, a_out)
        h = rmsnorm(bp["norm2"], x, cfg.rms_eps)
        x = _residual(cfg, x, mlp(bp["mlp"], h, cfg.mlp_type))
        return x, new_cache

    if kind_eff in ("mla", "mla_moe"):
        h = rmsnorm(bp["norm1"], x, cfg.rms_eps)
        if mode == "decode" and block_tables is not None:
            a_out, new_cache = mla_mod.mla_decode_paged(
                bp["mla"], h, cache, block_tables, lengths, cfg, absorb=True
            )
        elif mode == "decode":
            a_out, new_cache = mla_mod.mla_decode(
                bp["mla"], h, cache, lengths, cfg, absorb=True
            )
        else:
            a_out, new_cache = mla_mod.mla_prefill(
                bp["mla"], h, cfg,
                cache=cache if mode == "prefill" else None,
                absorb=True,
            )
        x = _residual(cfg, x, a_out)
        h = rmsnorm(bp["norm2"], x, cfg.rms_eps)
        if kind_eff == "mla_moe":
            m_out, _aux = moe_mod.moe_mlp(bp["moe"], h, cfg)
        else:
            m_out = mlp(bp["mlp"], h, cfg.mlp_type)
        x = _residual(cfg, x, m_out)
        return x, new_cache

    if kind_eff in ("ssm", "ssm_mlp"):
        h = rmsnorm(bp["norm1"], x, cfg.rms_eps)
        with jax.named_scope("ssm"):
            if mode == "decode":
                s_out, new_cache = ssm_mod.ssm_decode(bp["ssm"], h, cache, cfg)
            else:
                s_out, new_cache = ssm_mod.ssm_prefill(
                    bp["ssm"], h, cfg, cache=cache if mode == "prefill" else None,
                    lengths=lengths if mode == "prefill" else None,
                )
        x = _residual(cfg, x, s_out)
        if kind_eff == "ssm_mlp":
            h = rmsnorm(bp["norm2"], x, cfg.rms_eps)
            x = _residual(cfg, x, mlp(bp["mlp"], h, cfg.mlp_type))
        return x, new_cache

    if kind_eff == "gdn":
        h = rmsnorm(bp["norm1"], x, cfg.rms_eps)
        if mode == "decode":
            g_out, new_cache = gdn_mod.gdn_decode(bp["gdn"], h, cache, cfg)
        else:
            g_out, new_cache = gdn_mod.gdn_prefill(
                bp["gdn"], h, cfg, cache=cache if mode == "prefill" else None
            )
        x = _residual(cfg, x, g_out)
        h = rmsnorm(bp["norm2"], x, cfg.rms_eps)
        x = _residual(cfg, x, mlp(bp["mlp"], h, cfg.mlp_type))
        return x, new_cache

    raise ValueError(kind)


def _state_scope(kind: str):
    """The scopes of a stacked state's read and write: the block's mixer,
    then ``kv_write``, so that every pass over the state is under both."""
    scope = "ssm" if kind in ("ssm", "ssm_mlp") else kind
    return jax.named_scope(scope), jax.named_scope("kv_write")


def _state_read(kind: str, stack: Dict, unit: jax.Array) -> Dict:
    """Unit ``unit``'s state out of its block's stacked state."""
    outer, inner = _state_scope(kind)
    with outer, inner:
        return jax.tree.map(lambda a: a[unit], stack)


def _state_write(kind: str, stack: Dict, new: Dict, unit: jax.Array) -> Dict:
    """Unit ``unit``'s new state into its block's stacked state (leaves
    ``(n_units, ...)``), carried by the decode scan and so written in place."""
    outer, inner = _state_scope(kind)
    with outer, inner:
        return jax.tree.map(lambda a, n: a.at[unit].set(n.astype(a.dtype)), stack, new)


def _decode_write(kind: str, cache: Dict, out: Dict, lengths: jax.Array,
                  block_tables: Optional[jax.Array],
                  active: Optional[jax.Array]) -> Dict:
    """One block's stacked decode cache (leaves ``(n_units, ...)``) after a
    step, from what its units returned: per-token caches get every layer's
    new row written in place, at ``lengths`` or through the block table;
    ssm/gdn states come whole (written in place by the scan, or stacked by
    the unrolled lowering); cross-attention's stays as it is."""
    if kind in PAGED_KINDS:
        if block_tables is None:
            return {n: attn._write_at_lengths(cache[n], out[n], lengths) for n in cache}
        return {n: attn._paged_token_write(cache[n], out[n], block_tables, lengths, active)
                for n in cache}
    return cache if kind == "cross_attn" else out


def _run_stages(
    params: Dict,
    cfg: ModelConfig,
    x: jax.Array,
    mode: str,
    cache: Optional[Dict],
    lengths: Optional[jax.Array],
    enc_states: Optional[jax.Array],
    remat: bool,
    block_tables: Optional[jax.Array] = None,
    active: Optional[jax.Array] = None,
    prefix_len: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Dict]]:
    """Scan each stage's units. Train and prefill stream the stacked cache
    through the scan (``xs`` in, ``ys`` out). Decode only reads a per-token
    cache inside the scan: the units' new rows leave as a small ``ys`` and
    are written once, in place, afterwards (``_decode_write``); the stacked
    state of ``STATE_KINDS`` rides in the scan's carry and each unit reads
    its own and writes it back in place (``_state_read``, ``_state_write``).
    A dense decode over ``STACKED_KINDS`` with ``attn.decode_reads_stack``
    does not slice its layer out of the scan's ``xs``: the scan closes over
    the stack and each unit reads its layer by index, which the TPU kernel
    does in place, only as far as each slot's rows go (``active`` marks the
    slots that have any). So no cache is copied whole.
    ``lengths`` in prefill: the prompts' true lengths (recurrent blocks keep
    padding out of their state)."""
    shared = params.get("shared_block")
    new_stage_caches = []
    for si, stage in enumerate(cfg.stages):
        sp = params["stages"][si]
        sc = cache["stages"][si] if cache is not None else None
        scanned = mode == "decode" and sc is not None and not unroll_enabled()
        carried = tuple(f"b{i}" for i, kind in enumerate(stage.unit)
                        if kind in STATE_KINDS) if scanned else ()
        stacked = tuple(f"b{i}" for i, kind in enumerate(stage.unit)
                        if kind in STACKED_KINDS) \
            if scanned and block_tables is None and attn.decode_reads_stack(cfg) else ()

        def unit_fn(carry, xs, _stage=stage, _carried=carried, _stacked=stacked,
                    _sc=sc):
            if _carried or _stacked:  # ((x, stacked states), (params, caches, unit))
                (carry_x, states), (up, uc, u) = carry, xs
                states = dict(states)
            else:
                carry_x, (up, uc) = carry, xs
            new_uc = {}
            for i, kind in enumerate(_stage.unit):
                b = f"b{i}"
                if b in _carried:
                    bc = _state_read(kind, states[b], u)
                elif b in _stacked:
                    bc = _sc[b]
                else:
                    bc = uc[b] if uc is not None else None
                carry_x, nbc = _block_apply(
                    kind, up[b], carry_x, cfg, mode, bc, lengths, shared,
                    enc_states, block_tables, prefix_len,
                    unit=u if b in _stacked else None, active=active,
                )
                if b in _carried:
                    states[b] = _state_write(kind, states[b], nbc, u)
                else:
                    new_uc[b] = nbc if nbc is not None else {}
            # keep activations batch-sharded across unit boundaries (no-op
            # unless the launch layer configured batch axes)
            carry_x = constrain_batch(carry_x)
            return ((carry_x, states) if _carried or _stacked else carry_x), new_uc

        body = jax.checkpoint(unit_fn) if (remat and mode == "train") else unit_fn
        if unroll_enabled():
            # accounting mode: python-loop over units for exact HLO costs
            new_units = []
            for u in range(stage.n_units):
                up_u = jax.tree.map(lambda a, _u=u: a[_u], sp)
                uc_u = jax.tree.map(lambda a, _u=u: a[_u], sc) if sc is not None else None
                x, nuc = body(x, (up_u, uc_u))
                new_units.append(nuc)
            new_sc = jax.tree.map(lambda *ls: jnp.stack(ls), *new_units)
        elif carried or stacked:
            states = {b: sc[b] for b in carried}
            rows = {b: c for b, c in sc.items() if b not in carried + stacked}
            (x, states), new_sc = jax.lax.scan(
                body, (x, states), (sp, rows, jnp.arange(stage.n_units)))
            new_sc = {**new_sc, **states}
        elif sc is not None:
            x, new_sc = jax.lax.scan(body, x, (sp, sc))
        else:
            x, _ = jax.lax.scan(lambda c, p, _b=body: (_b(c, (p, None))[0], None), x, sp)
        if sc is not None:
            if mode == "decode":
                new_sc = {f"b{i}": _decode_write(kind, sc[f"b{i}"], new_sc[f"b{i}"], lengths,
                                                 block_tables, active)
                          for i, kind in enumerate(stage.unit)}
            new_stage_caches.append(new_sc)
    new_cache = {"stages": new_stage_caches} if cache is not None else None
    return x, new_cache


def _embed_inputs(params, cfg: ModelConfig, inputs):
    cd = _cdtype(cfg)
    if cfg.input_is_embeddings:
        return inputs.astype(cd)
    x = embed(params["embed"], inputs, cfg.embed_scale, cfg.d_model, cd)
    return _scaled(x, cfg.embedding_multiplier)


def forward(
    params: Dict,
    cfg: ModelConfig,
    inputs: jax.Array,
    *,
    enc_states: Optional[jax.Array] = None,
    remat: bool = True,
) -> jax.Array:
    """Training/eval forward -> final hidden states (B, S, d).

    Logits are intentionally not materialised here: the training loss uses a
    chunked softmax-xent over the (possibly 256 k) vocabulary; sampling-side
    callers use ``logits()``.
    """
    x = _embed_inputs(params, cfg, inputs)
    x, _ = _run_stages(params, cfg, x, "train", None, None, enc_states, remat)
    return rmsnorm(params["final_norm"], x, cfg.rms_eps)


@jax.named_scope("lm_head")
def logits(params: Dict, cfg: ModelConfig, hidden: jax.Array) -> jax.Array:
    if cfg.logits_scaling == 1.0:
        return unembed(params["embed"], hidden, cfg.final_softcap)
    return softcap_logits(unembed(params["embed"], hidden) / cfg.logits_scaling,
                          cfg.final_softcap)


def prefill(
    params: Dict,
    cfg: ModelConfig,
    inputs: jax.Array,
    cache: Dict,
    *,
    prompt_lengths: Optional[jax.Array] = None,
    enc_states: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict, jax.Array]:
    """Process the prompt, fill caches, return last-valid-token logits."""
    b, s = inputs.shape[0], inputs.shape[1]
    true_lengths = prompt_lengths
    if prompt_lengths is None:
        prompt_lengths = jnp.full((b,), s, dtype=jnp.int32)
    x = _embed_inputs(params, cfg, inputs)
    x, new_cache = _run_stages(params, cfg, x, "prefill", cache, true_lengths, enc_states,
                               False)
    x = rmsnorm(params["final_norm"], x, cfg.rms_eps)
    last = jnp.take_along_axis(x, (prompt_lengths - 1)[:, None, None], axis=1)[:, 0]
    return logits(params, cfg, last[:, None])[:, 0], new_cache, prompt_lengths


def prefill_suffix(
    params: Dict,
    cfg: ModelConfig,
    inputs: jax.Array,                # (1, S) suffix tokens, bucket-padded
    cache: Dict,
    *,
    prefix_len: jax.Array,            # (1,) int32 — positions already cached
    suffix_lengths: jax.Array,        # (1,) int32 — valid suffix tokens
) -> Tuple[jax.Array, Dict, jax.Array]:
    """Prefill only the un-shared suffix of a prompt (prefix sharing).

    ``cache`` already holds valid K/V for positions ``[0, prefix_len)`` —
    gathered from shared pages by the serving pool. The suffix is processed
    at positions ``prefix_len + i`` and written into the cache there; the
    returned logits are the last valid suffix token's, i.e. the same
    first-token logits a full prefill of the whole prompt would produce.
    Attention-family configs only (KV-cache semantics); other block kinds
    raise loudly at trace time."""
    b = inputs.shape[0]
    if b != 1:
        raise ValueError(f"suffix prefill is batch-1 (got batch={b})")
    x = _embed_inputs(params, cfg, inputs)
    x, new_cache = _run_stages(
        params, cfg, x, "prefill", cache, None, None, False,
        prefix_len=prefix_len,
    )
    x = rmsnorm(params["final_norm"], x, cfg.rms_eps)
    last = jnp.take_along_axis(x, (suffix_lengths - 1)[:, None, None], axis=1)[:, 0]
    return (logits(params, cfg, last[:, None])[:, 0], new_cache,
            prefix_len + suffix_lengths)


def decode_step(
    params: Dict,
    cfg: ModelConfig,
    token: jax.Array,                 # (B,) int32 or (B, 1, d) embeddings
    cache: Dict,
    lengths: jax.Array,               # (B,) tokens already cached
    *,
    enc_states: Optional[jax.Array] = None,
    active: Optional[jax.Array] = None,  # (B,) bool; default every slot
) -> Tuple[jax.Array, Dict, jax.Array]:
    """One decode step: append token, return (logits (B,V), cache, lengths+1).
    Attention may skip the cached rows of a slot that is not ``active``
    (its logits are then not those of its cache)."""
    if cfg.input_is_embeddings:
        x = token.astype(_cdtype(cfg))
    else:
        x = _embed_inputs(params, cfg, token[:, None])
    x, new_cache = _run_stages(params, cfg, x, "decode", cache, lengths, enc_states, False,
                               active=active)
    x = rmsnorm(params["final_norm"], x, cfg.rms_eps)
    return logits(params, cfg, x)[:, 0], new_cache, lengths + 1


def decode_step_paged(
    params: Dict,
    cfg: ModelConfig,
    token: jax.Array,                 # (B,) int32 or (B, 1, d) embeddings
    cache: Dict,                      # init_paged_cache layout
    lengths: jax.Array,               # (B,) tokens already cached
    active: jax.Array,                # (B,) bool — live slots
    block_tables: jax.Array,          # (B, nb) logical block -> physical page
    *,
    enc_states: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict, jax.Array]:
    """One decode step over the PAGED cache: per-token caches are read and
    written through the block table; O(1) state stays slot indexed. Paging
    is pure layout, so logits are bit-identical to ``decode_step`` on the
    equivalent dense cache."""
    if cfg.input_is_embeddings:
        x = token.astype(_cdtype(cfg))
    else:
        x = _embed_inputs(params, cfg, token[:, None])
    x, new_cache = _run_stages(
        params, cfg, x, "decode", cache, lengths, enc_states, False,
        block_tables=block_tables, active=active,
    )
    x = rmsnorm(params["final_norm"], x, cfg.rms_eps)
    return logits(params, cfg, x)[:, 0], new_cache, lengths + 1


# ---------------------------------------------------------------------------
# Replica batching. A fleet of K homogeneous replicas stepping at the same
# instant is K independent evaluations of the SAME program over stacked
# state — exactly what ``jax.vmap`` expresses: params broadcast, everything
# else (tokens, caches, lengths, RNG keys) carries a leading replica axis,
# and XLA sees ONE batched graph instead of K copies of the per-replica one.
# ``shard_map_replicas`` lays the same batched call out over a device mesh so
# a multi-device host runs replica shards in parallel; with one device it is
# the identity layout (and bitwise-identical to the plain vmap).


def vmap_replicas(step_fn: Any, n_args: int, n_broadcast: int = 1):
    """Batch a per-replica step function over a leading replica axis.

    The first ``n_broadcast`` arguments broadcast unchanged (weights shared
    by the whole group); the remaining ``n_args - n_broadcast`` are stacked
    per replica (axis 0). Outputs all carry the replica axis."""
    axes = (None,) * n_broadcast + (0,) * (n_args - n_broadcast)
    return jax.vmap(step_fn, in_axes=axes)


def shard_map_replicas(step_fn: Any, n_args: int, n_broadcast: int = 1,
                       *, axis_name: str = "replica", devices=None):
    """``vmap_replicas`` laid out over the host's devices: the replica axis
    is sharded across a 1-D mesh, so each device runs its shard of the
    group concurrently. The replica count must divide the device count's
    shard evenly (pow2 group padding guarantees this for pow2 device
    counts). Per-replica computations never communicate, so the result is
    bitwise the single-device vmap's."""
    import numpy as _np
    from jax.sharding import Mesh, PartitionSpec

    if devices is None:
        devices = jax.devices()
    mesh = Mesh(_np.asarray(devices), (axis_name,))
    spec_in = ((PartitionSpec(),) * n_broadcast
               + (PartitionSpec(axis_name),) * (n_args - n_broadcast))
    vf = vmap_replicas(step_fn, n_args, n_broadcast)
    return jax.shard_map(vf, mesh=mesh, in_specs=spec_in,
                         out_specs=PartitionSpec(axis_name))


def decode_step_batched(
    params: Dict,
    cfg: ModelConfig,
    tokens: jax.Array,                # (K, B) int32 — replica-stacked
    cache: Dict,                      # leaves (K, ...) — replica-stacked
    lengths: jax.Array,               # (K, B)
) -> Tuple[jax.Array, Dict, jax.Array]:
    """K replicas' ``decode_step`` as one batched call (params shared)."""
    fn = vmap_replicas(
        lambda p, tk, c, ln: decode_step(p, cfg, tk, c, ln), 4)
    return fn(params, tokens, cache, lengths)


def prefill_batched(
    params: Dict,
    cfg: ModelConfig,
    inputs: jax.Array,                # (K, B, S) int32 — replica-stacked
    cache: Dict,                      # leaves (K, ...) — replica-stacked
    prompt_lengths: jax.Array,        # (K, B)
) -> Tuple[jax.Array, Dict, jax.Array]:
    """K replicas' ``prefill`` as one batched call (params shared)."""
    fn = vmap_replicas(
        lambda p, inp, c, pl: prefill(p, cfg, inp, c, prompt_lengths=pl), 4)
    return fn(params, inputs, cache, prompt_lengths)
