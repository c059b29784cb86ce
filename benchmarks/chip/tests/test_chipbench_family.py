"""A model family is found by its name alone: each cell takes its CPU
preset (``TINY``) from its reference family, and a family that exists
only as a module of its own gives a cell that runs, with no edit to any
file of the benchmark.

The family made for the test is the ``qwen3`` reference's functions
registered under a new name, with a ``TINY`` of its own (a vocabulary of
384 rows), a configuration file, the ``dml.k2`` traffic and limits, and
a manifest entry, all in a copy of the benchmark's directory."""
import json
import shutil
import sys
import types

import pytest

import chipbench_cells as cells
from benchmarks.chip import harness
from benchmarks.chip.reference import qwen3

FAMILY = "newfamily"
FUNCTIONS = ("dims", "init", "forward", "to_program", "from_program",
             "matmul_params", "attention_flops_per_token")


@pytest.mark.parametrize("cell", cells.CELLS)
def test_each_cell_takes_its_preset_from_its_family(cell):
    c = cells.tiny(cell)
    assert c.config is c.family.TINY
    assert c.family.__name__ == \
        f"benchmarks.chip.reference.{c.config['reference']}"
    # the preset is in the schema of the family's configuration files
    assert c.family.dims(c.config)["L"] == 2


@pytest.fixture
def new_family(tmp_path, monkeypatch):
    mod = types.ModuleType(f"benchmarks.chip.reference.{FAMILY}")
    for name in FUNCTIONS:
        setattr(mod, name, getattr(qwen3, name))
    program = dict(qwen3.TINY["program"])
    program["replace"] = dict(program["replace"], vocab_size=384)
    mod.TINY = dict(qwen3.TINY, reference=FAMILY, program=program,
                    vocab_size=384)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)

    here = tmp_path / "chip"
    for sub in ("configs", "traffic", "workloads"):
        (here / sub).mkdir(parents=True)
    config = json.loads((harness.HERE / "configs" /
                         "qwen3-4b.2L.v1of8.json").read_text())
    (here / "configs" / f"{FAMILY}.json").write_text(
        json.dumps(dict(config, reference=FAMILY)))
    shutil.copy(harness.HERE / "traffic" / "dml.k2.json",
                here / "traffic" / "dml.k2.json")
    cell = f"{FAMILY}.dml.k2"
    shutil.copy(harness.HERE / "workloads" / "qwen3-4b.dml.k2.json",
                here / "workloads" / f"{cell}.json")
    man = harness.manifest()
    man["workloads"].append({"name": cell, "config": FAMILY,
                             "traffic": "dml.k2", "chips": 1,
                             "why": "a family made for the test"})
    monkeypatch.setattr(harness, "HERE", here)
    monkeypatch.setattr(harness, "manifest", lambda: man)
    return mod, cell


def test_a_new_family_needs_no_edit(new_family, monkeypatch):
    mod, cell = new_family
    c = cells.tiny(cell)
    assert c.family is mod and c.config is mod.TINY
    assert c.traffic["seq"] == 64 and c.limits
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "ref")
    out = harness.run(c, 2 ** 31 + 11, 0.05, False, on_chip=False)
    assert out["correct"] is True
    for name, check in out["checks"].items():
        assert check["value"] < 1e-4, (name, check)
