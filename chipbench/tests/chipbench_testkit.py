"""Shared helpers of the benchmark's own tests: small CPU-sized models and
mixes for driving a whole run without a chip."""
from __future__ import annotations

import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import core  # noqa: E402


def reduced_model(config_name: str, limit=None, man=None):
    """The configuration at the program's reduced size (``reduced_config``
    of the same architecture): float32, width 64, vocabulary 512."""
    from repro.configs import reduced_config

    man = man or core.manifest()
    m = dict(core.load_config(man, config_name))
    cfg = reduced_config(m["arch"])
    m.update(hidden_size=cfg.d_model, vocab_size=cfg.vocab_size,
             num_hidden_layers=sum(s.n_units for s in cfg.stages),
             intermediate_size=cfg.d_ff, num_attention_heads=cfg.n_heads,
             torch_dtype=cfg.param_dtype)
    if m["work"] == "gqa":
        m.update(num_key_value_heads=cfg.n_kv_heads, head_dim=cfg.head_dim)
    else:
        m.update(kv_lora_rank=cfg.kv_lora_rank, qk_rope_head_dim=cfg.qk_rope_head_dim,
                 qk_nope_head_dim=cfg.qk_nope_head_dim, v_head_dim=cfg.v_head_dim)
    wk = core.work(m["work"])
    m["cache_bytes"] = 8 * 128 * wk.cache_bytes_per_token(m)     # batch 8 at L 128
    if limit is not None:
        m["limits"] = {"max_logit_gap": limit}
    return m


def small_mix(traffic_name: str, man=None):
    """The mix with lengths cut to a 128-token context."""
    mix = dict(core.load_mix(traffic_name))
    mix.update(max_seq_len=128,
               prompt=dict(mix["prompt"], min=8, max=64, median=24),
               output=dict(mix["output"], min=4, max=24, median=8),
               check={"tokens": 48, "requests": 16})
    if "rate_rps" in mix:
        mix.update(rate_rps=8.0, drain_s=20)
    return mix


def run_small(cell_name: str, seed: int = 2**31 + 11, seconds: float = 1.5,
              trace: bool = False, limit=None, **kw):
    from chipbench.run import run_cell

    man = core.manifest()
    c = core.cell(man, cell_name)
    model = reduced_model(c["config"], limit=limit, man=man)
    return run_cell(cell_name, seed, seconds, trace, require_tpu=False, model=model,
                    mix=small_mix(c["traffic"], man), reduced=True,
                    log=lambda *_: None, **kw)
