"""The manifest keeps the benchmark's contract: names, units, files, a
reader for every metric, and the chip budget."""
import re
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
from chipbench_testkit import REPO  # noqa: E402

from chipbench import core  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "head",
               "expansion", "experts_per_tok", "num_experts_per_tok")

MAN = core.manifest()
TOP = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end",
       "per_layer"}


def test_top_level_keys_and_command():
    assert set(MAN) == TOP
    assert MAN["command"] == ["python3", "chipbench/run.py"]
    assert MAN["paths"] == ["chipbench"]
    assert isinstance(MAN["run_seconds"], int) and 1 <= MAN["run_seconds"] <= 51


@pytest.mark.parametrize("entry", [e for k in ("configs", "workloads", "end_to_end",
                                               "per_layer") for e in MAN[k]],
                         ids=lambda e: e["name"])
def test_names_and_units_use_allowed_characters(entry):
    assert NAME.match(entry["name"])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]
            assert "\t" not in entry[key]


def test_names_are_unique():
    for kind in ("configs", "workloads"):
        names = [e["name"] for e in MAN[kind]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in MAN["end_to_end"] + MAN["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_cell_has_its_config_and_traffic_and_driver_files():
    for w in MAN["workloads"]:
        entry = core.config_entry(MAN, w["config"])
        assert (REPO / entry["file"]).is_file()
        assert entry["file"].startswith("chipbench/")
        mix = core.load_mix(w["traffic"])
        assert (core.HERE / "drivers" / f"{mix['driver']}.py").is_file()
        model = core.load_config(MAN, w["config"])
        assert (core.HERE / "work" / f"{model['work']}.py").is_file()
        assert (core.HERE / "reference" / f"{model['work']}.py").is_file()
        assert w["chips"] in (1, 4)


def test_every_config_is_used_and_reduces_no_width():
    used = {w["config"] for w in MAN["workloads"]}
    for c in MAN["configs"]:
        assert c["name"] in used
        model = core.load_config(MAN, c["name"])
        assert model["reduced"] == c["reduced"] and model["source"] == c["source"]
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank", "_size"))
            assert not any(w in key for w in WIDTH_WORDS)


def test_every_metric_has_a_reader_and_each_cell_reports_what_it_moves():
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert hasattr(core.metric_reader(m["name"]), "read"), m["name"]
    for m in MAN["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter",
                               "host_clock")
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in {w["name"] for w in MAN["workloads"]}
            assert "workloads" not in moved or cell in moved["workloads"], (m, cell)
    for w in MAN["workloads"]:
        reports = core.metrics_for(MAN, w["name"], "end_to_end")
        assert len(reports) >= 2
        assert core.metrics_for(MAN, w["name"], "per_layer")


def test_rooflines_have_a_whole_step_share_beside_them():
    for m in MAN["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
            assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                       and set(m["workloads"]) <= set(o["workloads"])
                       for o in MAN["per_layer"])


def test_bounds_are_within_the_contract():
    for m in MAN["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in MAN["workloads"])
    assert four <= max(1, len(MAN["workloads"]) // 2)


def test_a_full_check_of_24_cells_fits_the_time():
    s = MAN["run_seconds"]
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200
