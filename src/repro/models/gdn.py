"""Gated DeltaNet (GDN) — linear-recurrent attention replacement.

The paper's "compute-light" DVFS class: decode is two-thirds elementwise
work (1.8 % tensor-core utilisation), so it tolerates the most aggressive
underclocking unconditionally.

Recurrence (gated delta rule), state S_t in R^{K x V} per head:

    S_t = alpha_t * ( S_{t-1} - beta_t * k_t (k_t^T S_{t-1}) ) + beta_t * k_t v_t^T
    y_t = S_t^T q_t

Prefill here is the faithful *unfused eager* scan (the paper's vLLM
baseline, whose order-of-magnitude prefill penalty §6.1 measures);
``repro.kernels.gdn`` provides the fused chunked Pallas kernel that §7.2
predicts "could substantially close the gap".
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import init_rmsnorm, rmsnorm


def _dims(cfg):
    h, k = cfg.gdn_heads, cfg.gdn_head_dim
    return h, k, h * k


def init_gdn(key, cfg, dtype) -> Dict:
    d = cfg.d_model
    h, k, inner = _dims(cfg)
    keys = jax.random.split(key, 7)
    s = 1.0 / np.sqrt(d)
    return {
        "wq": (jax.random.normal(keys[0], (d, h, k)) * s).astype(dtype),
        "wk": (jax.random.normal(keys[1], (d, h, k)) * s).astype(dtype),
        "wv": (jax.random.normal(keys[2], (d, h, k)) * s).astype(dtype),
        "w_beta": (jax.random.normal(keys[3], (d, h)) * s).astype(dtype),
        "w_alpha": (jax.random.normal(keys[4], (d, h)) * s).astype(dtype),
        "w_gate": (jax.random.normal(keys[5], (d, h, k)) * s).astype(dtype),
        "norm": init_rmsnorm(inner, dtype),
        "w_out": (jax.random.normal(keys[6], (inner, d)) * (1.0 / np.sqrt(inner))).astype(dtype),
    }


def _l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def _qkv_gates(params, x, cfg):
    q = _l2norm(jnp.einsum("bsd,dhk->bshk", x, params["wq"]).astype(jnp.float32))
    k = _l2norm(jnp.einsum("bsd,dhk->bshk", x, params["wk"]).astype(jnp.float32))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"]).astype(jnp.float32)
    beta = jax.nn.sigmoid((x @ params["w_beta"]).astype(jnp.float32))          # (B,S,H)
    # decay gate in (0,1), biased toward 1 (slow forgetting) at init
    alpha = jax.nn.sigmoid((x @ params["w_alpha"]).astype(jnp.float32) + 4.0)  # (B,S,H)
    return q, k, v, beta, alpha


def gdn_scan(q, k, v, beta, alpha, initial_state=None):
    """Sequential gated-delta-rule scan.

    q,k,v: (B,S,H,K) fp32; beta,alpha: (B,S,H).
    -> y (B,S,H,K), final state (B,H,K,K).
    """
    bsz, s, h, kd = q.shape
    init = (
        jnp.zeros((bsz, h, kd, kd), dtype=jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )

    def step(state, inp):
        qt, kt, vt, bt, at = inp        # (B,H,K) x3, (B,H) x2
        ks = jnp.einsum("bhk,bhkv->bhv", kt, state)           # k^T S
        state = at[..., None, None] * (
            state - bt[..., None, None] * jnp.einsum("bhk,bhv->bhkv", kt, ks)
        ) + bt[..., None, None] * jnp.einsum("bhk,bhv->bhkv", kt, vt)
        yt = jnp.einsum("bhkv,bhk->bhv", state, qt)
        return state, yt

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, beta, alpha))
    final, ys = jax.lax.scan(step, init, xs)
    return jnp.moveaxis(ys, 0, 1), final


def gdn_step(q, k, v, beta, alpha, state):
    """Single decode step. q,k,v: (B,H,K); beta,alpha: (B,H); state (B,H,K,K)."""
    ks = jnp.einsum("bhk,bhkv->bhv", k, state)
    state = alpha[..., None, None] * (
        state - beta[..., None, None] * jnp.einsum("bhk,bhv->bhkv", k, ks)
    ) + beta[..., None, None] * jnp.einsum("bhk,bhv->bhkv", k, v)
    y = jnp.einsum("bhkv,bhk->bhv", state, q)
    return y, state


def _finish(params, y, z_gate, x, cfg):
    bsz, s = y.shape[0], y.shape[1]
    h, kd, inner = _dims(cfg)
    y = y.astype(x.dtype) * jax.nn.silu(z_gate)
    y = rmsnorm(params["norm"], y.reshape(bsz, s, inner), cfg.rms_eps)
    return y @ params["w_out"]


def gdn_prefill(
    params: Dict,
    x: jax.Array,
    cfg,
    *,
    cache: Optional[Dict] = None,
) -> Tuple[jax.Array, Optional[Dict]]:
    q, k, v, beta, alpha = _qkv_gates(params, x, cfg)
    y, final = gdn_scan(q, k, v, beta, alpha)
    z_gate = jnp.einsum("bsd,dhk->bshk", x, params["w_gate"])
    out = _finish(params, y, z_gate, x, cfg)
    if cache is not None:
        cache = {"gdn": final}
    return out, cache


def gdn_decode(
    params: Dict,
    x: jax.Array,            # (B, 1, d)
    cache: Dict,
    cfg,
) -> Tuple[jax.Array, Dict]:
    q, k, v, beta, alpha = _qkv_gates(params, x, cfg)
    with jax.named_scope("kv_write"):      # the recurrent state's update
        y, new_state = gdn_step(q[:, 0], k[:, 0], v[:, 0], beta[:, 0], alpha[:, 0], cache["gdn"])
    z_gate = jnp.einsum("bsd,dhk->bshk", x, params["w_gate"])
    out = _finish(params, y[:, None], z_gate, x, cfg)
    return out, {"gdn": new_state}
