"""The reduction from a trace to device time, busy union and idle gaps, on a
made-up trace and on a piece of a chip trace recorded on a TPU v5e."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import chipbench_testkit  # noqa: E402,F401

from chipbench import core, tracing  # noqa: E402

DATA = Path(__file__).resolve().parents[1] / "data"
MS = 1e6


def _made_up():
    return {"devices": {"0": {
        "ops": [["%while.1", 1 * MS, 3 * MS], ["fusion.1", 1 * MS, 1 * MS],
                ["fusion.2", 2.5 * MS, 1.5 * MS],
                ["fusion.1", 7 * MS, 1 * MS], ["copy.3", 9 * MS, 0.5 * MS]],
        "modules": [["jit_decode_impl(7)", 1 * MS, 3 * MS],
                    ["jit_prefill_impl(9)", 7 * MS, 2.5 * MS]]}},
        "spans": [["cb.window", 0.0, 10 * MS], ["cb.decode_once", 0.5 * MS, 3.6 * MS],
                  ["cb.tick", 4.2 * MS, 2.5 * MS], ["cb.prefill_request", 6.9 * MS, 2.5 * MS]]}


def test_busy_union_gaps_and_programs_on_a_made_up_trace():
    r = tracing.Reduced(_made_up())
    assert r.window_s == pytest.approx(0.010)
    # ops 1-4 ms (a loop and its body), 7-8 ms, 9-9.5 ms: 4.5 ms busy
    assert r.busy_s() == pytest.approx(0.0045)
    assert [p[0] for p in r.programs(r"\bjit_decode_impl\b")] == ["jit_decode_impl(7)"]
    assert r.gap_before(7 * MS) == pytest.approx(3 * MS)
    gaps = r.idle_gaps()
    assert [(g[0] / MS, g[1] / MS) for g in gaps] == [(0, 1), (4, 7), (8, 9), (9.5, 10)]
    assert [g[2] for g in gaps] == ["decode_once", "tick", "prefill_request", "host_idle"]
    bd = r.breakdown()
    # self times: the loop less its body
    assert dict(bd["device_ops"]) == pytest.approx(
        {"fusion.1": 0.002, "fusion.2": 0.0015, "%while.1": 0.0005, "copy.3": 0.0005})
    assert bd["device_ops"][0][0] == "fusion.1"
    assert dict(bd["idle_gaps"])["tick"] == pytest.approx(0.003)


def test_trim_keeps_a_window():
    t = tracing.trim(_made_up(), 0.0, 5 * MS)
    r = tracing.Reduced(t)
    assert r.window_s == pytest.approx(0.005)
    assert r.busy_s() == pytest.approx(0.003)


@pytest.fixture(scope="module")
def recorded():
    return tracing.Reduced(json.loads((DATA / "trace_v5e_chat.json").read_text()))


def test_a_recorded_chip_trace_reduces(recorded):
    r = recorded
    assert r.chips == ["0"]
    assert 0 < r.busy_s() < r.window_s
    decode = r.programs(core.metric_reader("decode_step_ms").PATTERN)
    assert decode and all(d > 0 for _, _, d in decode)
    # every decode program starts inside a host decode span
    spans = [(s, e) for s, e, n in r.host if n == "decode_once"]
    assert all(any(s <= p[1] <= e for s, e in spans) for p in decode)
    bd = r.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
    total_gaps = sum(e - s for s, e, _ in r.idle_gaps()) / 1e9
    assert total_gaps == pytest.approx(r.window_s - r.busy_s(), rel=1e-9)
