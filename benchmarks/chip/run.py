"""On-chip benchmark of the federated DML round: run one cell once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for.  See ``harness.py`` for what a run does and prints, and
``BENCHMARK.json`` for the cells.  Exits non-zero, printing no result,
when JAX finds no TPU or fewer chips than the cell needs.
"""
import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[2]
# import the benchmark as a package from the checkout's root, and never
# let this directory shadow a standard module (it holds ``trace.py``)
sys.path[:] = [p for p in sys.path
               if Path(p or ".").resolve() != Path(__file__).parent.resolve()]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from benchmarks.chip import harness  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(harness.main(t_start=T_START))
