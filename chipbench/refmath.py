"""Plain float32 building blocks of the references, and the scoring.

Matrix products run at ``HIGHEST`` precision: on a TPU a float32 product
otherwise runs in bfloat16 passes. ``quant="fp8"`` turns a reference into
its control: every weight and every product's input is rounded to
float8_e4m3 with one scale per tensor, products still accumulate in
float32 (how an fp8 serving path computes).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def fp8(x):
    """Round to float8_e4m3 with a per-tensor scale, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def qin(x, quant: Optional[str]):
    return fp8(x) if quant == "fp8" else x


def einsum(spec: str, a, b, quant: Optional[str] = None):
    return jnp.einsum(spec, qin(a, quant), qin(b, quant), precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def rmsnorm(x, weight, eps: float):
    """``x * rsqrt(mean(x^2) + eps) * weight``; ``weight`` already holds the
    offset of 1."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rotate_half_rope(x, positions, theta: float):
    """Rotary embedding over the last axis, halves rotated (the Hugging
    Face ``rotate_half`` form). ``x``: (..., T, heads, dim); ``positions``:
    (T,)."""
    dim = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    half = dim // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def causal_softmax(scores):
    """Softmax over the last axis of (..., T, T) scores with a causal mask."""
    t = scores.shape[-1]
    mask = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(mask, scores, -jnp.inf)
    return jax.nn.softmax(scores, axis=-1)


@functools.partial(jax.jit, static_argnames=("quant",))
def _score(h, tok, table, hc, ctrl_table, quant):
    ref = einsum("md,vd->mv", h, table)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, tok[:, None], axis=-1)[:, 0]
    if hc is None:
        return best - got, None
    pick = jnp.argmax(einsum("md,vd->mv", hc, ctrl_table, quant), axis=-1)
    return best - got, best - jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]


def score_rows(hidden, table, served, ctrl_hidden=None, ctrl_table=None,
               quant: Optional[str] = None, chunk: int = 128):
    """For each row: the reference's best logit minus the logit of the
    served token, and (with a control) minus the logit of the control's
    first choice. ``hidden``: (m, d) final hidden rows; ``table``: (V, d)."""
    m = hidden.shape[0]
    pad = lambda x, e, s: jnp.pad(x[s:e], ((0, chunk - (e - s)),) + ((0, 0),) * (x.ndim - 1))
    gaps, ctrl_gaps = [], []
    for s in range(0, m, chunk):
        e = min(m, s + chunk)
        hc = None if ctrl_hidden is None else pad(ctrl_hidden, e, s)
        g, c = _score(pad(hidden, e, s), pad(jnp.asarray(served, jnp.int32), e, s),
                      table, hc, ctrl_table, quant)
        gaps.append(np.asarray(g)[: e - s])
        if c is not None:
            ctrl_gaps.append(np.asarray(c)[: e - s])
    return (np.concatenate(gaps),
            np.concatenate(ctrl_gaps) if ctrl_gaps else None)
