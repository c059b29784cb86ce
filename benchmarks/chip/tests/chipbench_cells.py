"""Cells of the benchmark cut to a size the CPU tests can hold.

The model is the registry's ``reduced()`` preset of the cell's
architecture (float32), the traffic is the cell's own with batch 4,
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

# the registry's reduced() presets, by the published names
TINY = {
    "qwen3": {
        "reference": "qwen3",
        "program": {"registry": "qwen3-4b", "preset": "reduced",
                    "replace": {"tie_embeddings": True, "rms_eps": 1e-6}},
        "hidden_size": 128, "intermediate_size": 256,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
        "num_hidden_layers": 2, "vocab_size": 512, "rope_theta": 1000000,
        "rms_norm_eps": 1e-6, "tie_word_embeddings": True},
    "mamba2": {
        "reference": "mamba2",
        "program": {"registry": "mamba2-780m", "preset": "reduced"},
        "d_model": 128, "n_layer": 2, "vocab_size": 512, "d_state": 16,
        "d_conv": 4, "expand": 2, "headdim": 32, "ngroups": 1,
        "chunk_size": 32, "dt_min": 0.001, "dt_max": 0.1,
        "rms_norm_eps": 1e-5},
}

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
    config = TINY[real.config["reference"]]
    traffic = dict(real.traffic, batch=4, seq=64, public_batch=2)
    return harness.Cell(real.name, real.chips, config, traffic, real.limits,
                        harness.family(config["reference"]), [])
