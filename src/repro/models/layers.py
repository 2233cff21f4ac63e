"""Shared neural layers: RMSNorm, RoPE, MLP variants, embeddings.

All layers are functional: ``init_*`` returns a param pytree (dict of
jnp arrays), ``apply`` style functions are pure. Dtypes follow the config's
``param_dtype`` / ``compute_dtype``; normalisation statistics and softmax are
always fp32.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def dt(name: str):
    return jnp.dtype(name)


# --------------------------------------------------------------------- norm
def init_rmsnorm(d: int, dtype) -> Dict[str, jax.Array]:
    return {"scale": jnp.zeros((d,), dtype=dtype)}


def rmsnorm(params, x, eps: float = 1e-6):
    """RMSNorm with (1 + scale) parameterisation (gemma-style zeros init)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + params["scale"].astype(jnp.float32))).astype(x.dtype)


# --------------------------------------------------------------------- rope
def rope_frequencies(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding.

    x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq).
    """
    head_dim = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(head_dim, theta), dtype=jnp.float32)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------- mlp
def init_mlp(key, d_model: int, d_ff: int, mlp_type: str, dtype) -> Dict:
    gated = mlp_type in ("swiglu", "geglu")
    k1, k2, k3 = jax.random.split(key, 3)
    scale_in = 1.0 / np.sqrt(d_model)
    scale_out = 1.0 / np.sqrt(d_ff)
    params = {
        "w_up": (jax.random.normal(k1, (d_model, d_ff)) * scale_in).astype(dtype),
        "w_down": (jax.random.normal(k2, (d_ff, d_model)) * scale_out).astype(dtype),
    }
    if gated:
        params["w_gate"] = (jax.random.normal(k3, (d_model, d_ff)) * scale_in).astype(dtype)
    return params


@jax.named_scope("mlp")
def mlp(params, x, mlp_type: str):
    up = x @ params["w_up"]
    if mlp_type == "swiglu":
        act = jax.nn.silu(x @ params["w_gate"]) * up
    elif mlp_type == "geglu":
        act = jax.nn.gelu(x @ params["w_gate"], approximate=True) * up
    elif mlp_type == "squared_relu":
        act = jnp.square(jax.nn.relu(up))
    else:
        raise ValueError(mlp_type)
    return act @ params["w_down"]


# --------------------------------------------------------------- embeddings
def init_embedding(key, vocab: int, d_model: int, dtype) -> Dict:
    return {"table": (jax.random.normal(key, (vocab, d_model)) * 0.02).astype(dtype)}


def embed(params, tokens: jax.Array, scale: bool, d_model: int, compute_dtype):
    x = jnp.take(params["table"], tokens, axis=0).astype(compute_dtype)
    if scale:
        x = x * jnp.asarray(np.sqrt(d_model), dtype=compute_dtype)
    return x


def unembed(params, x: jax.Array, softcap: float = 0.0):
    logits = (x @ params["table"].T.astype(x.dtype)).astype(jnp.float32)
    if softcap > 0:
        logits = softcap * jnp.tanh(logits / softcap)
    return logits


def softcap_logits(logits: jax.Array, cap: float) -> jax.Array:
    if cap <= 0:
        return logits
    return cap * jnp.tanh(logits / cap)
