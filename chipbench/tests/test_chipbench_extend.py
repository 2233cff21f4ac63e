"""A later change adds a configuration, a traffic mix and a per-layer metric
as new files and manifest entries alone, and the harness finds them by
name: a copy of the benchmark gains all three and runs the new cell."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
from chipbench_testkit import REPO  # noqa: E402

SCRIPT = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
from chipbench import core
from chipbench.run import run_cell
from repro.configs import reduced_config
assert core.HERE == __import__("pathlib").Path(sys.argv[1]).resolve() / "chipbench"
man = core.manifest()
m = dict(core.load_config(man, "qwen3-4b-again"))
cfg = reduced_config(m["arch"])
m.update(hidden_size=cfg.d_model, vocab_size=cfg.vocab_size, intermediate_size=cfg.d_ff,
         num_hidden_layers=2, num_attention_heads=cfg.n_heads,
         num_key_value_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
         torch_dtype="float32", limits={"max_logit_gap": 0.01})
m["cache_bytes"] = 4 * 64 * core.work("gqa").cache_bytes_per_token(m)
res = run_cell("qwen3-4b-again.tiny", 5, 1.5, True, require_tpu=False, model=m,
               reduced=True, log=lambda *_: None)
print(json.dumps(res))
"""


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    bench = tmp_path / "chipbench"
    shutil.copytree(REPO / "chipbench", bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    man = json.loads((tmp_path / "BENCHMARK.json").read_text())

    model = json.loads((bench / "configs" / "qwen3-4b.json").read_text())
    (bench / "configs" / "qwen3-4b-again.json").write_text(json.dumps(model))
    mix = json.loads((bench / "traffic" / "decode-heavy.json").read_text())
    mix.update(max_seq_len=64, requests=64, check={"tokens": 16, "requests": 8},
               prompt=dict(mix["prompt"], min=8, max=32, median=16),
               output=dict(mix["output"], min=4, max=16, median=8))
    (bench / "traffic" / "tiny.json").write_text(json.dumps(mix))
    (bench / "metrics" / "prefill_calls.py").write_text(
        '"""Scheduler: prefills over the window (a program counter)."""\n\n\n'
        "def read(run):\n    return run.stats['prefill_calls'] or None\n")

    man["configs"].append(dict(man["configs"][0], name="qwen3-4b-again",
                               file="chipbench/configs/qwen3-4b-again.json"))
    cell = "qwen3-4b-again.tiny"
    man["workloads"].append({"name": cell, "config": "qwen3-4b-again",
                             "traffic": "tiny", "chips": 1, "why": "a test"})
    for m in man["end_to_end"]:
        if m["name"] in ("tbt_p95_ms", "output_tok_s"):
            m["workloads"].append(cell)
    man["per_layer"].append({"name": "prefill_calls", "unit": "calls",
                             "better": "lower", "source": "program_counter",
                             "layer": "scheduler", "moves": "output_tok_s",
                             "workloads": [cell]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", SCRIPT, str(tmp_path), str(REPO / "src")],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True
    assert res["metrics"]["prefill_calls"]["value"] > 0
    assert set(res["metrics"]) == {"prefill_calls"}
