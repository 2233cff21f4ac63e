"""FLOPs and bytes that a grouped-query-attention decoder needs for one
call, from its configuration's shapes (Hugging Face key names).

Bytes count what the algorithm has to move, not what a program moves:
every weight once per call (a tied output head reads the embedding table
once), the cache rows that are valid, and the rows written. The rows of
the cache buffer beyond a sequence's length are not counted.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _dims(m: Dict) -> Tuple[int, int, int, int, int, int, int]:
    return (m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"],
            m["head_dim"], m["intermediate_size"], m["num_hidden_layers"],
            m["vocab_size"])


def _gated(m: Dict) -> bool:
    return m["hidden_act"] in ("silu", "gelu", "gelu_pytorch_tanh")


def layer_matmul_params(m: Dict) -> int:
    d, h, kv, hd, ff, _, _ = _dims(m)
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp = d * ff * (3 if _gated(m) else 2)
    return attn + mlp


def weight_bytes(m: Dict) -> int:
    d, _, _, _, _, n, v = _dims(m)
    b = BYTES[m["torch_dtype"]]
    per_layer = layer_matmul_params(m) + 2 * d          # + two norms
    table = v * d * (1 if m["tie_word_embeddings"] else 2)
    return b * (n * per_layer + table + d)


def cache_bytes_per_token(m: Dict) -> int:
    """Cache bytes one token adds, over all layers (K and V)."""
    _, _, kv, hd, _, n, _ = _dims(m)
    return n * 2 * kv * hd * BYTES[m["torch_dtype"]]


def decode(m: Dict, lengths: Sequence[int]) -> Tuple[float, float]:
    """One decode step over the active slots; ``lengths`` are the tokens
    each slot had cached before the step."""
    d, h, _, hd, _, n, v = _dims(m)
    b = BYTES[m["torch_dtype"]]
    tokens = len(lengths)
    ctx = sum(int(x) + 1 for x in lengths)              # keys incl. the new one
    flops = (2.0 * tokens * (n * layer_matmul_params(m) + v * d)
             + 4.0 * n * h * hd * ctx)
    row = cache_bytes_per_token(m)
    nbytes = (weight_bytes(m) + row * sum(int(x) for x in lengths)
              + row * tokens + 2 * b * tokens * d)
    return flops, float(nbytes)


def prefill(m: Dict, prompt_len: int) -> Tuple[float, float]:
    """One prefill of ``prompt_len`` real tokens, logits for the last."""
    d, h, _, hd, _, n, v = _dims(m)
    b = BYTES[m["torch_dtype"]]
    t = int(prompt_len)
    pairs = t * (t + 1) / 2                               # causal query-key pairs
    flops = 2.0 * t * n * layer_matmul_params(m) + 4.0 * n * h * hd * pairs \
        + 2.0 * v * d
    nbytes = weight_bytes(m) + cache_bytes_per_token(m) * t + b * t * d
    return flops, float(nbytes)
