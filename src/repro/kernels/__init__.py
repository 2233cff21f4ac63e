"""Pallas TPU kernels for the paper's decode/prefill hot spots.

Four kernels, each with <name>.py (pl.pallas_call + BlockSpec), ops.py
(jit'd wrapper) and ref.py (pure-jnp oracle):

* decode_attn — flash-decode over a blocked KV cache (GQA/MQA): the dense
                one reads a layer of the model's stacked cache in place,
                as far as each slot's rows go (served on the TPU); a paged
                variant gathers K/V pages through a per-request block table
                (scalar-prefetched BlockSpec index map)
* mla_decode  — fused absorbed-MLA attention on the COMPRESSED latent cache
                (the paper's §6.2 "fused decompression kernel"), dense and
                paged
* ssd         — chunked Mamba2/SSD scan, state resident in VMEM
* gdn         — fused gated-delta-rule recurrence (the §7.2 counterfactual
                for the eager-mode prefill penalty)

``common.py`` holds the shared wrapper plumbing (tile clamping / padding).
Every kernel and wrapper compiles for the TPU by default
(``interpret=False``); CPU callers (the oracle tests, the CPU micro
benchmark) pass ``interpret=True``. ``tests/test_tpu_compile.py`` compiles
each one for v5e at paper widths.
"""
from repro.kernels.common import clamp_block, largest_divisor_block, pad_to_multiple
from repro.kernels.decode_attn import (
    block_rows,
    decode_attention,
    decode_attention_ref,
    gqa_decode_attention,
    gqa_paged_decode_attention,
    kv_rows_read,
    paged_decode_attention,
    paged_decode_attention_ref,
)
from repro.kernels.mla_decode import (
    mla_fused_decode,
    mla_latent_decode,
    mla_latent_decode_ref,
    mla_paged_fused_decode,
    mla_paged_latent_decode,
    mla_paged_latent_decode_ref,
)
from repro.kernels.ssd import ssd_scan, ssd_prefill, ssd_scan_ref
from repro.kernels.gdn import gdn_scan, gdn_prefill, gdn_scan_ref

__all__ = [
    "clamp_block", "largest_divisor_block", "pad_to_multiple",
    "block_rows", "kv_rows_read",
    "decode_attention", "paged_decode_attention",
    "gqa_decode_attention", "gqa_paged_decode_attention",
    "decode_attention_ref", "paged_decode_attention_ref",
    "mla_latent_decode", "mla_paged_latent_decode",
    "mla_fused_decode", "mla_paged_fused_decode",
    "mla_latent_decode_ref", "mla_paged_latent_decode_ref",
    "ssd_scan", "ssd_prefill", "ssd_scan_ref",
    "gdn_scan", "gdn_prefill", "gdn_scan_ref",
]
