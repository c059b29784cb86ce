"""Cells of the benchmark cut to a size the CPU tests can hold.

The model is the ``TINY`` configuration that the cell's reference family
(``reference/<family>.py``) exports in a configuration file's schema, so
a new family brings its own; the traffic is the cell's own with batch 4,
sequence 64 and public batch 2, and the limits are the cell's.  Only the
sizes differ from what the chip runs.  A four-chip cell's program runs
its sharded round on a ``clients`` mesh of the one CPU device, which then
holds all of the cell's clients (``on_one_device``).

``HELD_OUT`` are cells whose configuration and reference stay under the
benchmark's directory but which ``BENCHMARK.json`` does not run (PERF.md
says why); their reference is still held to the program here, and they
have no limits."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chip import harness  # noqa: E402

# cell -> (configuration, traffic)
HELD_OUT = {"mamba2-780m.dml.k2": ("mamba2-780m.8L", "dml.k2")}

ONE_CHIP = [w["name"] for w in harness.manifest()["workloads"]
            if w["chips"] == 1] + sorted(HELD_OUT)
FOUR_CHIP = [w["name"] for w in harness.manifest()["workloads"]
             if w["chips"] == 4]
CELLS = ONE_CHIP + FOUR_CHIP


def on_one_device(monkeypatch) -> None:
    """Give a cell's program a ``clients`` mesh of one device, whatever
    number of chips the cell asks for."""
    from benchmarks.chip import program
    from repro.launch.mesh import make_client_mesh
    monkeypatch.setattr(program, "make_client_mesh",
                        lambda n: make_client_mesh(1))


def _cell(cell_name: str) -> harness.Cell:
    if cell_name not in HELD_OUT:
        return harness.load_cell(cell_name)
    config, traffic = HELD_OUT[cell_name]
    body = harness._json(harness.HERE / "configs" / f"{config}.json")
    return harness.Cell(cell_name, 1, body,
                        harness._json(harness.HERE / "traffic" /
                                      f"{traffic}.json"),
                        None, harness.family(body["reference"]), [])


def tiny(cell_name: str) -> harness.Cell:
    real = _cell(cell_name)
    traffic = dict(real.traffic, batch=4, seq=64, public_batch=2)
    return harness.Cell(real.name, real.chips, real.family.TINY, traffic,
                        real.limits, real.family, [])
