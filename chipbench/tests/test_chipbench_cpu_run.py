"""Whole runs on the CPU at the program's reduced size: each cell's driver,
the metric readers and the reference, through ``run_cell`` with the look
for a chip skipped. ``run.py`` itself refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
from chipbench_testkit import REPO, run_small  # noqa: E402

from chipbench import core  # noqa: E402

MAN = core.manifest()
CELLS = [w["name"] for w in MAN["workloads"] if w["chips"] == 1]


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct(cell):
    limit = core.load_config(MAN, core.cell(MAN, cell)["config"])["limits"]["max_logit_gap"]
    res = run_small(cell, limit=limit)
    assert res["correct"] is True, res["check"]
    assert res["check"]["max_logit_gap"]["value"] <= limit
    assert res["check"]["tokens_checked"]["value"] >= 48
    want = {m["name"] for m in core.metrics_for(MAN, cell, "end_to_end")}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "check" and res["failed"] == 0
    json.dumps(res)


def test_a_traced_run_reads_the_counters_and_spans():
    res = run_small("qwen3-4b.decode-heavy", trace=True, limit=1.0, seconds=2.0)
    # the CPU has no TPU planes: the device readers find nothing and are left out
    assert set(res["metrics"]) == {"decode_occupancy", "decode_mfu"}
    assert 0 < res["metrics"]["decode_occupancy"]["value"] <= 100
    assert "busy_s" in res["device"] and "window_s" in res["device"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_the_closed_loop_window_opens_with_every_slot_full(monkeypatch):
    drv = core.driver("closed_loop")
    seen = []
    measure = drv.measure

    def spy(run):
        seen.append([r.decode_pool.occupancy() for r in run.fleet.replicas])
        return measure(run)

    monkeypatch.setattr(drv, "measure", spy)
    res = run_small("qwen3-4b.decode-heavy", limit=1.0)
    assert seen == [[8]]
    assert res["metrics"]["output_tok_s"]["value"] > 0


def _run_py(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "qwen3-4b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_py_without_a_tpu_exits_nonzero_and_prints_no_result():
    p = _run_py(REPO)
    assert p.returncode != 0
    assert "needs 1 TPU" in p.stderr
    assert not p.stdout.strip().splitlines()[-1:] or \
        not p.stdout.strip().splitlines()[-1].startswith("{")


def test_run_py_alone_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
