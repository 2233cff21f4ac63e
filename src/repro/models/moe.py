"""Mixture-of-Experts MLP (DeepSeek-V2 style: shared + routed top-k).

Dispatch is capacity-based scatter/gather (Switch-style), so the compiled
FLOPs are proportional to *active* experts (top-k + shared), not the full
expert count — this keeps the dry-run cost_analysis honest for the
MODEL_FLOPS / HLO_FLOPs ratio in the roofline table. Routed experts are
stacked on a leading expert axis which shards over the mesh 'model' axis
(expert parallelism).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import init_mlp, mlp


def init_moe(key, cfg, dtype) -> Dict:
    d, e, ff = cfg.d_model, cfg.n_routed_experts, cfg.moe_d_ff
    keys = jax.random.split(key, 5)
    s_in = 1.0 / np.sqrt(d)
    s_out = 1.0 / np.sqrt(ff)
    p = {
        "router": (jax.random.normal(keys[0], (d, e)) * s_in).astype(jnp.float32),
        "w_gate": (jax.random.normal(keys[1], (e, d, ff)) * s_in).astype(dtype),
        "w_up": (jax.random.normal(keys[2], (e, d, ff)) * s_in).astype(dtype),
        "w_down": (jax.random.normal(keys[3], (e, ff, d)) * s_out).astype(dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(
            keys[4], d, cfg.n_shared_experts * ff, "swiglu", dtype
        )
    return p


def _capacity(cfg, n_tokens: int) -> int:
    per = n_tokens * cfg.moe_top_k / cfg.n_routed_experts
    return max(8, int(np.ceil(per * cfg.moe_capacity_factor)))


@jax.named_scope("mlp")
def moe_mlp(params: Dict, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out (B, S, d), aux load-balance loss scalar)."""
    b, s, d = x.shape
    e, k = cfg.n_routed_experts, cfg.moe_top_k
    xf = x.reshape(b * s, d)
    t = b * s
    cap = _capacity(cfg, t)

    gates = jax.nn.softmax((xf.astype(jnp.float32) @ params["router"]), axis=-1)  # (T,E)
    topw, topi = jax.lax.top_k(gates, k)                                          # (T,k)

    # position of each (token, slot) within its expert, via one-hot cumsum
    flat_e = topi.reshape(-1)                                  # (T*k,)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)        # (T*k, E)
    pos = (jnp.cumsum(onehot, axis=0) - 1) * onehot            # rank within expert
    pos = jnp.sum(pos, axis=-1)                                # (T*k,)
    keep = pos < cap
    # out-of-capacity entries are dropped by scatter mode='drop'
    pos_c = jnp.where(keep, pos, cap)

    tok_idx = jnp.repeat(jnp.arange(t), k)
    disp = jnp.zeros((e, cap, d), dtype=x.dtype)
    disp = disp.at[flat_e, pos_c].add(xf[tok_idx], mode="drop")

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", disp, params["w_gate"])) * jnp.einsum(
        "ecd,edf->ecf", disp, params["w_up"]
    )
    y_exp = jnp.einsum("ecf,efd->ecd", h, params["w_down"])    # (E, cap, d)

    gathered = y_exp.at[flat_e, pos_c].get(mode="drop", fill_value=0.0)  # (T*k, d)
    weights = jnp.where(keep, topw.reshape(-1), 0.0).astype(x.dtype)
    combined = jnp.zeros((t, d), dtype=x.dtype).at[tok_idx].add(gathered * weights[:, None])

    out = combined.reshape(b, s, d)
    if "shared" in params:
        out = out + mlp(params["shared"], x, "swiglu")

    # Switch-style load balance aux: E * sum_e f_e * p_e
    density = jnp.mean(jax.nn.one_hot(topi, e, dtype=jnp.float32), axis=(0, 1))
    mean_prob = jnp.mean(gates, axis=0)
    aux = e * jnp.sum(density * mean_prob)
    return out, aux
