from repro.kernels.decode_attn.decode_attn import (
    block_rows, decode_attention, fold_vmapped_axis, kv_rows_read, paged_decode_attention,
)
from repro.kernels.decode_attn.ops import gqa_decode_attention, gqa_paged_decode_attention
from repro.kernels.decode_attn.ref import decode_attention_ref, paged_decode_attention_ref

__all__ = [
    "block_rows", "fold_vmapped_axis", "kv_rows_read",
    "decode_attention", "paged_decode_attention",
    "gqa_decode_attention", "gqa_paged_decode_attention",
    "decode_attention_ref", "paged_decode_attention_ref",
]
