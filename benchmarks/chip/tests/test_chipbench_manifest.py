"""BENCHMARK.json against the contract it is written to, on the CPU:
every cell, configuration and metric resolves to its file, names and
units keep to their characters, every per-layer metric lists the cells
that report it, and at most half of the cells take four chips."""
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import correct, harness  # noqa: E402

MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in MAN["workloads"]]


def test_top_level_keys_and_command():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["command"] == ["python3", "benchmarks/chip/run.py"]
    assert MAN["paths"] == ["benchmarks/chip"]
    assert 1 <= MAN["run_seconds"] <= 51
    for word in MAN["command"]:
        assert not word.startswith("/") and ".." not in word


def test_names_and_units_use_allowed_characters():
    names = [c["name"] for c in MAN["configs"]] + CELLS + \
        [m["name"] for m in MAN["end_to_end"] + MAN["per_layer"]]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in MAN["workloads"]]:
        assert NAME.match(n), n
    for c in MAN["configs"]:
        for key in c["reduced"]:
            assert NAME.match(key), key
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = harness.load_cell(cell)
    assert c.chips in (1, 4)
    # limits are set from chip readings (calibrate.py); null until then
    assert c.limits is None or set(c.limits) <= set(correct.NUMBERS)
    assert c.layer_metrics
    entry = next(w for w in MAN["workloads"] if w["name"] == cell)
    assert len(entry["why"]) <= 200


@pytest.mark.parametrize("config", MAN["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cuts(config):
    path = ROOT / config["file"]
    assert path.is_file() and path.parts[-3:-1] == ("chip", "configs")
    body = json.loads(path.read_text())
    assert body["source"] == config["source"]
    assert sorted(body["reduced"]) == sorted(config["reduced"])
    for key, cut in body["reduced"].items():
        assert body[key] == cut["run"] != cut["published"], key
    assert harness.family(body["reference"]).dims(body)


def test_every_metric_has_its_reader():
    for m in MAN["per_layer"]:
        mod = harness.metric_module(m["name"])
        assert mod.LAYER == m["layer"] and mod.UNIT == m["unit"]
        assert mod.MOVES == m["moves"]
        assert callable(mod.read)


def test_per_layer_workloads_report_what_they_move():
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    for m in MAN["per_layer"]:
        assert m["workloads"], m["name"]
        for cell in m["workloads"]:
            assert cell in CELLS
            assert cell in e2e[m["moves"]].get("workloads", CELLS)


def test_every_cell_reports_setup_another_e2e_and_a_layer_metric():
    for cell in CELLS:
        e2e = [m["name"] for m in MAN["end_to_end"]
               if cell in m.get("workloads", CELLS)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.layer_metrics(MAN, cell)


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in MAN["workloads"])
    assert all(w["chips"] in (1, 4) for w in MAN["workloads"])
    assert four <= max(1, len(MAN["workloads"]) // 2)


def test_bounds():
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert next(m for m in MAN["end_to_end"]
                if m["name"] == "setup_s")["bound"] <= 0.25
