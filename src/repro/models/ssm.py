"""Mamba2 / SSD (state-space duality) block.

Prefill uses the chunked SSD algorithm (intra-chunk quadratic attention-like
term + inter-chunk state passing via ``lax.scan``), which is the
MXU-friendly TPU formulation; ``repro.kernels.ssd`` provides the Pallas
version of the same math. Decode is the O(1) recurrent step the paper's §6.2
credits for Mamba2's flat energy-vs-context curve.

State cache: ``{"ssm": (B, H, P, N) fp32, "conv": (B, K-1, conv_dim)}`` —
constant size, no per-token growth.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import init_rmsnorm, rmsnorm
from repro.models.unroll import scan_unroll_arg


def _dims(cfg):
    d_inner = cfg.ssm_expand * cfg.d_model
    heads = cfg.ssm_heads
    p = d_inner // heads
    n = cfg.ssm_state
    g = cfg.ssm_groups
    conv_dim = d_inner + 2 * g * n
    return d_inner, heads, p, n, g, conv_dim


def init_ssm(key, cfg, dtype) -> Dict:
    d = cfg.d_model
    d_inner, heads, p, n, g, conv_dim = _dims(cfg)
    keys = jax.random.split(key, 4)
    s = 1.0 / np.sqrt(d)
    proj_dim = 2 * d_inner + 2 * g * n + heads  # [z, x, B, C, dt]
    return {
        "w_in": (jax.random.normal(keys[0], (d, proj_dim)) * s).astype(dtype),
        "conv_w": (jax.random.normal(keys[1], (cfg.ssm_conv_kernel, conv_dim)) * 0.2).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype=dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, heads)).astype(jnp.float32),
        "d_skip": jnp.ones((heads,), dtype=jnp.float32),
        "dt_bias": jnp.zeros((heads,), dtype=jnp.float32),
        "norm": init_rmsnorm(d_inner, dtype),
        "w_out": (jax.random.normal(keys[2], (d_inner, d)) * (1.0 / np.sqrt(d_inner))).astype(dtype),
    }


def _split_proj(cfg, proj):
    d_inner, heads, p, n, g, _ = _dims(cfg)
    z, xs, b, c, dt = jnp.split(
        proj, [d_inner, 2 * d_inner, 2 * d_inner + g * n, 2 * d_inner + 2 * g * n], axis=-1
    )
    return z, xs, b, c, dt


def _causal_conv(u: jax.Array, w: jax.Array, b: jax.Array, state: Optional[jax.Array],
                 lengths: Optional[jax.Array] = None):
    """Depthwise causal conv1d. u: (B,S,C), w: (K,C). Returns (y, new_state):
    the window of the last K-1 inputs, or with ``lengths`` (B,) of the last
    K-1 before each row's true length (a bucket-padded prompt)."""
    k = w.shape[0]
    if state is None:
        pad = jnp.zeros((u.shape[0], k - 1, u.shape[2]), dtype=u.dtype)
    else:
        pad = state.astype(u.dtype)
    full = jnp.concatenate([pad, u], axis=1)          # (B, S+K-1, C)
    # window sum: y_t = sum_j w_j * full[t+j]
    y = sum(full[:, j : j + u.shape[1], :] * w[j] for j in range(k)) + b
    if k == 1:
        new_state = None
    elif lengths is None:
        new_state = full[:, -(k - 1) :, :]
    else:   # input t sits at full[t + K-1]: the window is full[n : n+K-1]
        new_state = jax.vmap(
            lambda f, n: jax.lax.dynamic_slice_in_dim(f, n, k - 1, axis=0))(full, lengths)
    return jax.nn.silu(y), new_state


def ssd_chunked(x, dt, a, b, c, chunk: int, initial_state=None):
    """Chunked SSD scan.

    x:  (B, S, H, P)   per-head inputs
    dt: (B, S, H)      positive step sizes (already softplus'ed)
    a:  (H,)           negative decay rates (A = -exp(a_log))
    b:  (B, S, G, N)   input projections  (grouped, H % G == 0)
    c:  (B, S, G, N)   output projections
    -> y (B, S, H, P), final_state (B, H, P, N) fp32
    """
    bsz, s_orig, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    # pad to a chunk multiple; dt=0 rows are exact no-ops (decay 1, weight 0)
    pad = (-s_orig) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
    s = s_orig + pad
    nc = s // chunk
    rep = h // g

    # Perf note (§Perf iteration 1): the whole chunked computation lives in
    # a scan over chunks so only ONE chunk's (B, Q, Q, H) tensors are live —
    # the all-chunks formulation materialised (B, nc, Q, Q, H) fp32
    # intermediates and made zamba2/mamba2 training pathologically
    # memory-bound (~13 TB/device HBM traffic at train_4k).
    f32 = jnp.float32
    xc = jnp.moveaxis(x.reshape(bsz, nc, chunk, h, p), 1, 0).astype(f32)
    dtc = jnp.moveaxis(dt.reshape(bsz, nc, chunk, h), 1, 0).astype(f32)
    bc = jnp.moveaxis(b.reshape(bsz, nc, chunk, g, n), 1, 0).astype(f32)
    cc = jnp.moveaxis(c.reshape(bsz, nc, chunk, g, n), 1, 0).astype(f32)

    causal = jnp.tril(jnp.ones((chunk, chunk), dtype=bool))
    a32 = a.astype(f32)

    init = (
        jnp.zeros((bsz, h, p, n), dtype=f32)
        if initial_state is None
        else initial_state.astype(f32)
    )

    def step(state, inp):
        xz, dtz, bz, cz = inp                    # (B,Q,H,P) (B,Q,H) (B,Q,G,N)x2
        bzh = jnp.repeat(bz, rep, axis=2)        # (B,Q,H,N)
        czh = jnp.repeat(cz, rep, axis=2)
        da = dtz * a32[None, None, :]            # (B,Q,H) log-decays
        cum = jnp.cumsum(da, axis=1)             # inclusive
        cd = cum[:, -1, :]                       # (B,H) chunk decay (log)

        # intra-chunk: L[i,j] = exp(cum_i - cum_j), i >= j. Mask INSIDE the
        # exp: masked exponents are large-positive (inf poisons the VJP).
        exponent = jnp.where(
            causal[None, :, :, None], cum[:, :, None, :] - cum[:, None, :, :], -jnp.inf
        )
        cb = jnp.einsum("bihn,bjhn->bijh", czh, bzh)
        w = cb * jnp.exp(exponent) * dtz[:, None, :, :]
        y = jnp.einsum("bijh,bjhp->bihp", w, xz)

        # inter-chunk: y_i += exp(cum_i) * C_i . state
        y += jnp.einsum("bihn,bhpn->bihp", czh * jnp.exp(cum)[..., None], state)

        # state pass: S' = S*exp(cd) + sum_j exp(cd - cum_j) dt_j B_j x_j^T
        to_end = jnp.exp(cd[:, None, :] - cum) * dtz
        sloc = jnp.einsum("bjh,bjhn,bjhp->bhpn", to_end, bzh, xz)
        new_state = state * jnp.exp(cd)[:, :, None, None] + sloc
        return new_state, y

    final_state, ys = jax.lax.scan(
        step, init, (xc, dtc, bc, cc), unroll=scan_unroll_arg()
    )
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, s, h, p)[:, :s_orig]
    return y, final_state


def ssd_step(x, dt, a, b, c, state):
    """Single-token recurrent step (decode).

    x: (B,H,P), dt: (B,H), b,c: (B,G,N), state: (B,H,P,N) fp32.
    """
    h = x.shape[1]
    g = b.shape[1]
    rep = h // g
    bh = jnp.repeat(b, rep, axis=1).astype(jnp.float32)     # (B,H,N)
    ch = jnp.repeat(c, rep, axis=1).astype(jnp.float32)
    dt32 = dt.astype(jnp.float32)
    decay = jnp.exp(dt32 * a[None, :])                      # (B,H)
    x32 = x.astype(jnp.float32)
    new_state = state * decay[:, :, None, None] + jnp.einsum(
        "bh,bhn,bhp->bhpn", dt32, bh, x32
    )
    y = jnp.einsum("bhpn,bhn->bhp", new_state, ch)
    return y, new_state


def ssm_prefill(
    params: Dict,
    x: jax.Array,
    cfg,
    *,
    cache: Optional[Dict] = None,
    lengths: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Dict]]:
    """Prefill over ``x`` (B, S, d). ``lengths`` (B,): each row's true
    length, past which it is padding that must not reach the state or the
    conv window (None: every row is S long)."""
    bsz, s, _ = x.shape
    d_inner, heads, p, n, g, conv_dim = _dims(cfg)
    proj = x @ params["w_in"]
    z, xs, b, c, dtp = _split_proj(cfg, proj)
    conv_in = jnp.concatenate([xs, b, c], axis=-1)
    conv_out, conv_state = _causal_conv(conv_in, params["conv_w"], params["conv_b"], None,
                                        lengths)
    xs, b, c = jnp.split(conv_out, [d_inner, d_inner + g * n], axis=-1)

    dtv = jax.nn.softplus(dtp.astype(jnp.float32) + params["dt_bias"])
    if lengths is not None:
        # past the true length dt is 0: the state decays by exp(0) = 1 and
        # takes no input, so the final state is the last real token's
        dtv = jnp.where(jnp.arange(s)[None, :, None] < lengths[:, None, None], dtv, 0.0)
    a = -jnp.exp(params["a_log"])
    y, final_state = ssd_chunked(
        xs.reshape(bsz, s, heads, p),
        dtv,
        a,
        b.reshape(bsz, s, g, n),
        c.reshape(bsz, s, g, n),
        cfg.ssm_chunk,
    )
    y = y + params["d_skip"][None, None, :, None] * xs.reshape(bsz, s, heads, p).astype(jnp.float32)
    y = y.reshape(bsz, s, d_inner).astype(x.dtype)
    y = rmsnorm(params["norm"], y * jax.nn.silu(z), cfg.rms_eps)
    out = y @ params["w_out"]
    if cache is not None:
        cache = {"ssm": final_state, "conv": conv_state.astype(cache["conv"].dtype)}
    return out, cache


def ssm_decode(
    params: Dict,
    x: jax.Array,              # (B, 1, d)
    cache: Dict,
    cfg,
) -> Tuple[jax.Array, Dict]:
    bsz = x.shape[0]
    d_inner, heads, p, n, g, conv_dim = _dims(cfg)
    proj = x @ params["w_in"]
    z, xs, b, c, dtp = _split_proj(cfg, proj)
    conv_in = jnp.concatenate([xs, b, c], axis=-1)          # (B,1,conv_dim)
    with jax.named_scope("kv_write"):      # the conv window's update
        conv_out, conv_state = _causal_conv(
            conv_in, params["conv_w"], params["conv_b"], cache["conv"]
        )
    xs, b, c = jnp.split(conv_out[:, 0], [d_inner, d_inner + g * n], axis=-1)

    dtv = jax.nn.softplus(dtp[:, 0].astype(jnp.float32) + params["dt_bias"])
    a = -jnp.exp(params["a_log"])
    with jax.named_scope("kv_write"):      # the recurrent state's update
        y, new_state = ssd_step(
            xs.reshape(bsz, heads, p), dtv, a, b.reshape(bsz, g, n), c.reshape(bsz, g, n),
            cache["ssm"],
        )
    y = y + params["d_skip"][None, :, None] * xs.reshape(bsz, heads, p).astype(jnp.float32)
    y = y.reshape(bsz, 1, d_inner).astype(x.dtype)
    y = rmsnorm(params["norm"], y * jax.nn.silu(z), cfg.rms_eps)
    out = y @ params["w_out"]
    return out, {"ssm": new_state, "conv": conv_state.astype(cache["conv"].dtype)}
