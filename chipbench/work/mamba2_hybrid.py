"""FLOPs and bytes that a hybrid of Mamba2 layers and grouped-query-attention
layers (granite-4.0-h) needs for one call, from its configuration's shapes
(Hugging Face key names).

Every layer is a mixer (Mamba2 or attention, by ``layer_types``) and a
SwiGLU MLP. Bytes count what the algorithm has to move, not what a program
moves: every weight once per call (a tied output head reads the embedding
table once); for each active sequence the Mamba2 layers' float32 state and
conv window, read and written; the attention layers' valid cache rows read
and the new rows written.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
STATE_BYTES = 4                     # the Mamba2 state is float32


def _kinds(m: Dict) -> Tuple[int, int]:
    """(Mamba2 layers, attention layers) among the first ``num_hidden_layers``."""
    kinds = m["layer_types"][: m["num_hidden_layers"]]
    return kinds.count("mamba"), kinds.count("attention")


def _attn_dims(m: Dict) -> Tuple[int, int, int]:
    h = m["num_attention_heads"]
    return h, m["num_key_value_heads"], m["hidden_size"] // h


def _mamba_dims(m: Dict) -> Tuple[int, int, int, int, int]:
    """(d_inner, heads, head size, state size, conv channels)."""
    d_inner = m["mamba_expand"] * m["hidden_size"]
    p, n = m["mamba_d_head"], m["mamba_d_state"]
    return d_inner, d_inner // p, p, n, d_inner + 2 * m["mamba_n_groups"] * n


def mamba_matmul_params(m: Dict) -> int:
    """The in and out projections of one Mamba2 mixer."""
    d = m["hidden_size"]
    d_inner, heads, _, n, _ = _mamba_dims(m)
    return d * (2 * d_inner + 2 * m["mamba_n_groups"] * n + heads) + d_inner * d


def attn_matmul_params(m: Dict) -> int:
    d = m["hidden_size"]
    h, kv, hd = _attn_dims(m)
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def mlp_params(m: Dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def weight_bytes(m: Dict) -> int:
    d, v = m["hidden_size"], m["vocab_size"]
    b = BYTES[m["torch_dtype"]]
    n_mamba, n_attn = _kinds(m)
    d_inner, heads, _, _, conv = _mamba_dims(m)
    k = m["mamba_d_conv"]
    mamba = b * (mamba_matmul_params(m) + conv * (k + 1) + d_inner) + STATE_BYTES * 3 * heads
    per_layer = b * (mlp_params(m) + 2 * d)             # MLP and the two norms
    table = v * d * (1 if m["tie_word_embeddings"] else 2)
    return (n_mamba * mamba + n_attn * b * attn_matmul_params(m)
            + (n_mamba + n_attn) * per_layer + b * (table + d))


def state_bytes_per_seq(m: Dict) -> int:
    """One sequence's Mamba2 state (float32) and conv window, all layers."""
    n_mamba, _ = _kinds(m)
    _, heads, p, n, conv = _mamba_dims(m)
    return n_mamba * (heads * p * n * STATE_BYTES
                      + (m["mamba_d_conv"] - 1) * conv * BYTES[m["torch_dtype"]])


def kv_bytes_per_token(m: Dict) -> int:
    """Cache bytes one token adds to the attention layers (K and V)."""
    _, n_attn = _kinds(m)
    _, kv, hd = _attn_dims(m)
    return n_attn * 2 * kv * hd * BYTES[m["torch_dtype"]]


def cache_bytes_per_token(m: Dict) -> float:
    """One token's KV plus one sequence's state spread over
    ``state_spread_tokens`` tokens: times a mix's ``max_seq_len`` equal to
    that spread, one sequence's whole cache."""
    return kv_bytes_per_token(m) + state_bytes_per_seq(m) / m["state_spread_tokens"]


def _token_flops(m: Dict) -> float:
    """FLOPs of one token through every layer's matrix products, its state
    update (decay, input and read-out: 5 per state element) and conv."""
    n_mamba, n_attn = _kinds(m)
    _, heads, p, n, conv = _mamba_dims(m)
    matmul = (n_mamba * mamba_matmul_params(m) + n_attn * attn_matmul_params(m)
              + (n_mamba + n_attn) * mlp_params(m))
    return 2.0 * matmul + n_mamba * (5.0 * heads * p * n + 2.0 * m["mamba_d_conv"] * conv)


def decode(m: Dict, lengths: Sequence[int]) -> Tuple[float, float]:
    """One decode step over the active slots; ``lengths`` are the tokens
    each slot had cached before the step."""
    d, v = m["hidden_size"], m["vocab_size"]
    b = BYTES[m["torch_dtype"]]
    _, n_attn = _kinds(m)
    h, _, hd = _attn_dims(m)
    tokens = len(lengths)
    ctx = sum(int(x) + 1 for x in lengths)              # keys incl. the new one
    flops = tokens * (_token_flops(m) + 2.0 * v * d) + 4.0 * n_attn * h * hd * ctx
    row = kv_bytes_per_token(m)
    nbytes = (weight_bytes(m) + 2 * tokens * state_bytes_per_seq(m)
              + row * sum(int(x) for x in lengths) + row * tokens + 2 * b * tokens * d)
    return flops, float(nbytes)


def prefill(m: Dict, prompt_len: int) -> Tuple[float, float]:
    """One prefill of ``prompt_len`` real tokens, logits for the last: the
    state and conv window written once, the KV rows of every token."""
    d, v = m["hidden_size"], m["vocab_size"]
    b = BYTES[m["torch_dtype"]]
    _, n_attn = _kinds(m)
    h, _, hd = _attn_dims(m)
    t = int(prompt_len)
    pairs = t * (t + 1) / 2                               # causal query-key pairs
    flops = t * _token_flops(m) + 4.0 * n_attn * h * hd * pairs + 2.0 * v * d
    nbytes = (weight_bytes(m) + state_bytes_per_seq(m) + kv_bytes_per_token(m) * t
              + b * t * d)
    return flops, float(nbytes)
