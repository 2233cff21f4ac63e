"""Plain float32 reference of a grouped-query-attention decoder.

Pre-norm blocks: RMSNorm, attention with rotary positions on all of each
head's dimensions (``num_key_value_heads`` shared by groups of query
heads), a residual add; RMSNorm, a SiLU-gated MLP (or squared ReLU), a
residual add. A final RMSNorm, and the output head (the embedding table
where ``tie_word_embeddings``). It runs one layer at a time over a batch of
whole sequences, drawing that layer's weights from the seed, so the whole
model never sits in float32 at once.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from chipbench import refmath as rm
from chipbench.weights import Leaf, global_weights, layer_weights, norm_std, std_for_fan_in

LAYER = ("stages", "b0")


def leaves(m: Dict) -> List[Leaf]:
    """The parameter tree as the program holds it, with each leaf's scale."""
    d, h, kv, hd, ff, v = (m["hidden_size"], m["num_attention_heads"],
                           m["num_key_value_heads"], m["head_dim"],
                           m["intermediate_size"], m["vocab_size"])
    if not m["tie_word_embeddings"]:
        raise ValueError("an untied output head is not in the program's tree")
    out = [
        Leaf(("embed", "table"), (v, d), std_for_fan_in(d), stacked=False),
        Leaf(("final_norm", "scale"), (d,), norm_std(), stacked=False, offset=1.0),
        Leaf(LAYER + ("norm1", "scale"), (d,), norm_std(), offset=1.0),
        Leaf(LAYER + ("attn", "wq"), (d, h, hd), std_for_fan_in(d)),
        Leaf(LAYER + ("attn", "wk"), (d, kv, hd), std_for_fan_in(d)),
        Leaf(LAYER + ("attn", "wv"), (d, kv, hd), std_for_fan_in(d)),
        Leaf(LAYER + ("attn", "wo"), (h, hd, d), std_for_fan_in(h * hd)),
        Leaf(LAYER + ("norm2", "scale"), (d,), norm_std(), offset=1.0),
        Leaf(LAYER + ("mlp", "w_up"), (d, ff), std_for_fan_in(d)),
        Leaf(LAYER + ("mlp", "w_down"), (ff, d), std_for_fan_in(ff)),
    ]
    if gated(m):
        out.append(Leaf(LAYER + ("mlp", "w_gate"), (d, ff), std_for_fan_in(d)))
    return out


def gated(m: Dict) -> bool:
    return m["hidden_act"] == "silu"


def eps(m: Dict) -> float:
    return float(m["rms_norm_eps"])


def block(m: Dict, w: Dict, x, quant: Optional[str] = None):
    """One decoder layer on ``x``: (n, T, d) float32."""
    n, t, d = x.shape
    h, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    pos = jnp.arange(t)
    a = rm.rmsnorm(x, w[LAYER + ("norm1", "scale")], eps(m))
    q = rm.einsum("ntd,dhk->nthk", a, w[LAYER + ("attn", "wq")], quant)
    k = rm.einsum("ntd,dhk->nthk", a, w[LAYER + ("attn", "wk")], quant)
    v = rm.einsum("ntd,dhk->nthk", a, w[LAYER + ("attn", "wv")], quant)
    q = rm.rotate_half_rope(q, pos, float(m["rope_theta"]))
    k = rm.rotate_half_rope(k, pos, float(m["rope_theta"]))
    # query head j reads key/value head j // (h // kv)
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)

    def attend(args):
        qi, ki, vi = args                                    # (T, h, hd)
        s = rm.einsum("thk,shk->hts", qi, ki, quant) / jnp.sqrt(jnp.float32(hd))
        p = rm.causal_softmax(s)
        return rm.einsum("hts,shk->thk", p, vi, quant)

    ctx = jax.lax.map(attend, (q, k, v))
    x = x + rm.einsum("nthk,hkd->ntd", ctx, w[LAYER + ("attn", "wo")], quant)
    a = rm.rmsnorm(x, w[LAYER + ("norm2", "scale")], eps(m))
    up = rm.einsum("ntd,df->ntf", a, w[LAYER + ("mlp", "w_up")], quant)
    if gated(m):
        g = rm.einsum("ntd,df->ntf", a, w[LAYER + ("mlp", "w_gate")], quant)
        act = jax.nn.silu(g) * up
    else:
        act = jnp.square(jax.nn.relu(up))
    return x + rm.einsum("ntf,fd->ntd", act, w[LAYER + ("mlp", "w_down")], quant)


def final_hidden(m: Dict, root, tokens, quant: Optional[str] = None):
    """Final-normed hidden states (n, T, d) of the token batch ``tokens``."""
    lv = leaves(m)
    g = global_weights(lv, root, jnp.float32)
    table = g[("embed", "table")]
    if quant == "fp8":
        table = rm.fp8(table)
    x = jnp.take(table, jnp.asarray(tokens), axis=0)
    draw = jax.jit(lambda r, l: layer_weights(lv, r, l, jnp.float32))
    step = jax.jit(lambda w, x: block(
        m, {k: (rm.fp8(a) if quant == "fp8" else a) for k, a in w.items()}, x, quant))
    for layer in range(m["num_hidden_layers"]):
        x = step(draw(root, layer), x)
    return rm.rmsnorm(x, g[("final_norm", "scale")], eps(m)), table


def wrap(tree: Dict) -> Dict:
    """The program keeps its layer stack in a list of stages."""
    tree["stages"] = [tree["stages"]]
    return tree
