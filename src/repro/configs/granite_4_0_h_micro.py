"""granite-4.0-h-micro [hybrid] — Mamba2 layers beside NoPE GQA layers.

40L d_model=2048, vocab=100352, tied, RMSNorm eps 1e-5
[huggingface.co/ibm-granite/granite-4.0-h-micro, config.json]. Attention at
layers 5, 15, 25, 35: four periods of (5 Mamba2, 1 attention, 4 Mamba2).
Every layer is a mixer then a SwiGLU MLP of width 8192. Mamba2: 64 heads of
64, d_state 128, 1 group, expand 2, conv 4. Attention: 32 q / 8 kv heads of
64, no rotary, softmax scale 1/64. muP: embeddings x12, each branch x0.22
before its residual add, logits / 8.
"""
from repro.models.config import ModelConfig, StageSpec

PERIOD = ("ssm_mlp",) * 5 + ("attn",) + ("ssm_mlp",) * 4


def stages(periods: int):
    """``periods`` periods of the layer pattern as stages of one-block
    units, one stage per run of a block kind: (5, 1, 9, 1, ..., 4)."""
    out = []
    for kind in PERIOD * periods:
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return tuple(StageSpec(unit=(kind,), n_units=n) for kind, n in out)


def config() -> ModelConfig:
    return ModelConfig(
        name="granite-4.0-h-micro",
        family="hybrid",
        d_model=2048,
        vocab_size=100352,
        stages=stages(4),
        n_heads=32,
        n_kv_heads=8,
        head_dim=64,
        use_rope=False,
        attn_scale=0.015625,
        d_ff=8192,
        mlp_type="swiglu",
        ssm_state=128,
        ssm_heads=64,
        ssm_head_dim=64,
        ssm_groups=1,
        ssm_expand=2,
        ssm_conv_kernel=4,
        ssm_chunk=256,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=8.0,
        rms_eps=1e-5,
        tie_embeddings=True,
        notes="hybrid: recurrent state beside paged KV in one decode pool",
    )
