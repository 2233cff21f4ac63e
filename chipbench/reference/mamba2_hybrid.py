"""Plain float32 reference of a hybrid of Mamba2 and grouped-query-attention
layers (granite-4.0-h, Hugging Face ``granitemoehybrid`` without experts).

The embeddings are scaled by ``embedding_multiplier``. Every layer is
pre-norm: RMSNorm, a mixer, its output scaled by ``residual_multiplier``
and added; RMSNorm, a SiLU-gated MLP, scaled and added likewise. The mixer
is, by ``layer_types``:

* Mamba2: one input projection to the gate z, the conv channels (x, B, C)
  and dt; a depthwise causal conv with bias and SiLU over the channels;
  dt = softplus(dt + dt_bias), A = -exp(A_log); the recurrence
  h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t + D x_t, run
  token by token (not the chunked form); the gated RMSNorm
  norm(y * silu(z)); the output projection;
* attention: no position embedding (NoPE), ``num_key_value_heads`` shared
  by groups of query heads, softmax of q.k times ``attention_multiplier``.

A final RMSNorm, and the tied output head, whose logits are divided by
``logits_scaling``: ``final_hidden`` hands back the table so divided. It
runs one layer at a time over a batch of whole sequences, drawing that
layer's weights from the seed.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from chipbench import refmath as rm
from chipbench.weights import (Leaf, draw_layer, global_weights, norm_std,
                               std_for_fan_in)

# The program holds each run of one kind of layer as a stage: granite's
# period of 5 Mamba2 layers, 1 attention layer and 4 Mamba2 layers gives
# stages of 5, 1, 9, 1, ... layers.
PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
LAYERS = "layers"
# the float32 mixer parameters the program takes as they are: drawn around
# 0, then moved by these constants, in the program's copy and here alike
SHIFT = {"a_log": 0.5, "d_skip": 1.0, "dt_bias": -3.0}


def kinds(m: Dict) -> List[str]:
    """The layer kinds, which must follow ``PERIOD`` (the program's stages)."""
    out = list(m["layer_types"][: m["num_hidden_layers"]])
    if out != [PERIOD[l % len(PERIOD)] for l in range(len(out))]:
        raise ValueError("the layer pattern is not the program's period")
    return out


def dims(m: Dict) -> Dict[str, int]:
    d, h = m["hidden_size"], m["num_attention_heads"]
    di, p, n = m["mamba_expand"] * d, m["mamba_d_head"], m["mamba_d_state"]
    g = m["mamba_n_groups"]
    return dict(d=d, h=h, kv=m["num_key_value_heads"], hd=d // h,
                ff=m["intermediate_size"], v=m["vocab_size"], di=di, mh=di // p, p=p,
                n=n, g=g, k=m["mamba_d_conv"], conv=di + 2 * g * n)


def leaves(m: Dict) -> List[Leaf]:
    """The parameter tree's leaves, each stacked over all layers (a layer
    draws the leaves of its own kind), with each leaf's scale."""
    kinds(m)
    if not m["tie_word_embeddings"]:
        raise ValueError("an untied output head is not in the program's tree")
    z = dims(m)
    d, di, mh = z["d"], z["di"], z["mh"]
    L = lambda *p: (LAYERS,) + p  # noqa: E731
    return [
        # drawn embedding_multiplier times smaller, so that the scaled
        # embeddings start the residual stream at the spread a branch adds
        Leaf(("embed", "table"), (z["v"], d), std_for_fan_in(d) / m["embedding_multiplier"],
             stacked=False),
        Leaf(("final_norm", "scale"), (d,), norm_std(), stacked=False, offset=1.0),
        Leaf(L("norm1", "scale"), (d,), norm_std(), offset=1.0),
        Leaf(L("norm2", "scale"), (d,), norm_std(), offset=1.0),
        Leaf(L("mlp", "w_up"), (d, z["ff"]), std_for_fan_in(d)),
        Leaf(L("mlp", "w_gate"), (d, z["ff"]), std_for_fan_in(d)),
        Leaf(L("mlp", "w_down"), (z["ff"], d), std_for_fan_in(z["ff"])),
        Leaf(L("ssm", "w_in"), (d, 2 * di + 2 * z["g"] * z["n"] + mh), std_for_fan_in(d)),
        Leaf(L("ssm", "conv_w"), (z["k"], z["conv"]), std_for_fan_in(z["k"])),
        Leaf(L("ssm", "conv_b"), (z["conv"],), 0.1),
        Leaf(L("ssm", "a_log"), (mh,), 0.25, offset=SHIFT["a_log"]),
        Leaf(L("ssm", "d_skip"), (mh,), 0.1, offset=SHIFT["d_skip"]),
        Leaf(L("ssm", "dt_bias"), (mh,), 0.5, offset=SHIFT["dt_bias"]),
        Leaf(L("ssm", "norm", "scale"), (di,), norm_std(), offset=1.0),
        Leaf(L("ssm", "w_out"), (di, d), std_for_fan_in(di)),
        Leaf(L("attn", "wq"), (d, z["h"], z["hd"]), std_for_fan_in(d)),
        Leaf(L("attn", "wk"), (d, z["kv"], z["hd"]), std_for_fan_in(d)),
        Leaf(L("attn", "wv"), (d, z["kv"], z["hd"]), std_for_fan_in(d)),
        Leaf(L("attn", "wo"), (z["h"], z["hd"], d), std_for_fan_in(z["h"] * z["hd"])),
    ]


def eps(m: Dict) -> float:
    return float(m["rms_norm_eps"])


def _residual(m: Dict, x, branch):
    return x + branch * m["residual_multiplier"]


def mamba(m: Dict, w: Dict, a, quant: Optional[str] = None):
    """The Mamba2 mixer on normed ``a``: (n, T, d) float32."""
    z_ = dims(m)
    n_, t, _ = a.shape
    di, mh, p, n, g = z_["di"], z_["mh"], z_["p"], z_["n"], z_["g"]
    s = lambda name: w[(LAYERS, "ssm", name)]  # noqa: E731
    proj = rm.einsum("ntd,de->nte", a, s("w_in"), quant)
    gate, xbc, dt = jnp.split(proj, [di, 2 * di + 2 * g * n], axis=-1)
    k = z_["k"]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    # causal depthwise conv: the window's last tap meets the current token
    xbc = sum(padded[:, j:j + t] * s("conv_w")[j] for j in range(k)) + s("conv_b")
    xbc = jax.nn.silu(xbc)
    xs, b, c = jnp.split(xbc, [di, di + g * n], axis=-1)
    xs = xs.reshape(n_, t, mh, p)
    rep = mh // g                        # heads reading each group's B and C
    b = jnp.repeat(b.reshape(n_, t, g, n), rep, axis=2)
    c = jnp.repeat(c.reshape(n_, t, g, n), rep, axis=2)
    dt = jax.nn.softplus(dt + s("dt_bias"))                  # (n, T, H)
    decay = jnp.exp(dt * -jnp.exp(s("a_log")))

    def step(h, inp):
        x_t, b_t, c_t, dt_t, dec_t = inp                     # (n,H,P) (n,H,N) x2 (n,H) x2
        h = h * dec_t[..., None, None] + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return h, jnp.einsum("nhpk,nhk->nhp", h, c_t, precision=rm.HIGHEST)

    seq = lambda x: jnp.moveaxis(x, 1, 0)  # noqa: E731
    h0 = jnp.zeros((n_, mh, p, n), jnp.float32)
    _, y = jax.lax.scan(step, h0, (seq(xs), seq(b), seq(c), seq(dt), seq(decay)),
                        unroll=16)
    y = jnp.moveaxis(y, 0, 1) + s("d_skip")[:, None] * xs
    y = rm.rmsnorm(y.reshape(n_, t, di) * jax.nn.silu(gate), w[(LAYERS, "ssm", "norm", "scale")],
                   eps(m))
    return rm.einsum("nte,ed->ntd", y, s("w_out"), quant)


def attention(m: Dict, w: Dict, a, quant: Optional[str] = None):
    """Causal NoPE attention on normed ``a``: (n, T, d) float32."""
    z = dims(m)
    q = rm.einsum("ntd,dhk->nthk", a, w[(LAYERS, "attn", "wq")], quant)
    k = rm.einsum("ntd,dhk->nthk", a, w[(LAYERS, "attn", "wk")], quant)
    v = rm.einsum("ntd,dhk->nthk", a, w[(LAYERS, "attn", "wv")], quant)
    # query head j reads key/value head j // (h // kv)
    k = jnp.repeat(k, z["h"] // z["kv"], axis=2)
    v = jnp.repeat(v, z["h"] // z["kv"], axis=2)

    def attend(args):
        qi, ki, vi = args                                    # (T, h, hd)
        s = rm.einsum("thk,shk->hts", qi, ki, quant) * m["attention_multiplier"]
        return rm.einsum("hts,shk->thk", rm.causal_softmax(s), vi, quant)

    ctx = jax.lax.map(attend, (q, k, v))
    return rm.einsum("nthk,hkd->ntd", ctx, w[(LAYERS, "attn", "wo")], quant)


def block(m: Dict, kind: str, w: Dict, x, quant: Optional[str] = None):
    """One layer of ``kind`` on ``x``: (n, T, d) float32."""
    a = rm.rmsnorm(x, w[(LAYERS, "norm1", "scale")], eps(m))
    mixer = mamba if kind == "mamba" else attention
    x = _residual(m, x, mixer(m, w, a, quant))
    a = rm.rmsnorm(x, w[(LAYERS, "norm2", "scale")], eps(m))
    up = rm.einsum("ntd,df->ntf", a, w[(LAYERS, "mlp", "w_up")], quant)
    g = rm.einsum("ntd,df->ntf", a, w[(LAYERS, "mlp", "w_gate")], quant)
    return _residual(m, x, rm.einsum("ntf,fd->ntd", jax.nn.silu(g) * up,
                                     w[(LAYERS, "mlp", "w_down")], quant))


def _uses(path, kind: str) -> bool:
    return path[1] not in ("ssm", "attn") or path[1] == ("ssm" if kind == "mamba" else "attn")


def final_hidden(m: Dict, root, tokens, quant: Optional[str] = None):
    """Final-normed hidden states (n, T, d) of the token batch ``tokens``,
    and the output head's table divided by ``logits_scaling``."""
    lv = leaves(m)
    g = global_weights(lv, root, jnp.float32)
    table = g[("embed", "table")]
    if quant == "fp8":
        table = rm.fp8(table)
    x = jnp.take(table, jnp.asarray(tokens), axis=0) * m["embedding_multiplier"]

    def draw(kind):
        picked = [(i, lf) for i, lf in enumerate(lv) if lf.stacked and _uses(lf.path, kind)]
        return jax.jit(lambda r, l: {lf.path: draw_layer(r, lv, i, l, jnp.float32) + lf.offset
                                     for i, lf in picked})

    draws = {kind: draw(kind) for kind in set(PERIOD)}
    steps = {kind: jax.jit(lambda w, x, kind=kind: block(
        m, kind, {k: (rm.fp8(a) if quant == "fp8" else a) for k, a in w.items()}, x, quant))
        for kind in set(PERIOD)}
    for layer, kind in enumerate(kinds(m)):
        x = steps[kind](draws[kind](root, layer), x)
    return (rm.rmsnorm(x, g[("final_norm", "scale")], eps(m)),
            table / m["logits_scaling"])


def wrap(tree: Dict) -> Dict:
    """The program's tree from the leaves stacked over all layers: one stage
    per run of a kind (``PERIOD``), each a block ``b0`` holding its run of
    layers; the float32 mixer parameters moved by ``SHIFT``."""
    layers = tree.pop(LAYERS)
    n = layers["norm1"]["scale"].shape[0]
    ssm = dict(layers["ssm"])
    for name, shift in SHIFT.items():
        ssm[name] = ssm[name].astype(jnp.float32) + shift
    runs: List[List] = []
    for l in range(n):
        kind = PERIOD[l % len(PERIOD)]
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, l, 1])
    stages = []
    for kind, start, count in runs:
        mixer = ("ssm", ssm) if kind == "mamba" else ("attn", layers["attn"])
        part = {"norm1": layers["norm1"], "norm2": layers["norm2"], "mlp": layers["mlp"],
                mixer[0]: mixer[1]}
        stages.append({"b0": jax.tree.map(lambda a: a[start:start + count], part)})
    tree["stages"] = stages
    return tree
