"""Readings that set a cell's limits of ``correct``, in one process.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 11,12,13 [--controls 11,12,13] [--out <file.jsonl>]

For every seed, the program is driven from the seed through the first
rounds exactly as a run's set-up drives it (``harness.first_rounds``, on
one session whose state is rebuilt from each seed), and its numbers are
compared with the float32 reference: these are the lower readings.  For
each control seed the reference is also run as the control, in float8
(``precision="fp8"``), and with each planted fault ("half_batch",
"no_exchange"), and each is compared with the float32 reference: these
are the upper readings.  One JSON object per seed and variant goes to
standard output (and to ``--out``).

Reading a dozen seeds in one process saves the set-up that separate runs
would each pay; the benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:] = [p for p in sys.path
               if Path(p or ".").resolve() != Path(__file__).parent.resolve()]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

VARIANTS = (("fp8", None), ("fp32", "half_batch"), ("fp32", "no_exchange"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.controls.split(",") if s}

    import jax
    import jax.numpy as jnp
    from benchmarks.chip import correct, harness, program
    from benchmarks.chip.reference import dml as ref_dml

    cell = harness.load_cell(args.workload)
    devices = harness.chips_for(cell, True)[:cell.chips]
    harness.use_cache()
    sink = open(args.out, "a") if args.out else None

    def emit(obj):
        line = json.dumps(obj)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()

    pop, fed = program.build(cell.config, cell.traffic, seeds[0], cell.chips)
    make_weights = program.weight_maker(cell.family, cell.config,
                                        cell.traffic, pop.client_params)
    opt_like = jax.tree.map(lambda x: (x.shape, x.dtype, x.sharding),
                            pop.client_opts,
                            is_leaf=lambda x: hasattr(x, "sharding"))
    fresh_opts = jax.jit(
        lambda: jax.tree.map(lambda t: jnp.zeros(t[0], t[1]), opt_like,
                             is_leaf=lambda t: isinstance(t, tuple)),
        out_shardings=jax.tree.map(lambda t: t[2], opt_like,
                                   is_leaf=lambda t: isinstance(t, tuple)))
    refs = {}

    def reference(precision, fault):
        key = (precision, fault)
        if key not in refs:
            refs[key] = ref_dml.Federation(cell.family, cell.config,
                                           cell.traffic, precision, fault,
                                           devices)
        return refs[key]

    for seed in seeds:
        t0 = time.perf_counter()
        if pop.client_params is None:
            pop.client_params = make_weights(seed)
        else:
            program.install_weights(pop, make_weights, seed)
        pop.client_opts = fresh_opts()
        pop.seed = seed
        fed = program.Federation(pop, program.strategy(cell.traffic))
        prog = harness.first_rounds(cell, pop, fed)
        pop.client_params = pop.client_opts = None
        del fed
        gc.collect()
        t1 = time.perf_counter()
        ref = reference("fp32", None).run(seed, correct.STEPS)
        t2 = time.perf_counter()
        emit({"cell": cell.name, "seed": seed, "variant": "program",
              "numbers": correct.numbers(prog, ref),
              "left_out": correct.left_out(ref),
              "program_s": t1 - t0, "reference_s": t2 - t1,
              "losses": prog["losses"].tolist(),
              "ref_losses": ref["losses"].tolist()})
        if seed in controls:
            for precision, fault in VARIANTS:
                t3 = time.perf_counter()
                other = reference(precision, fault).run(seed, correct.STEPS)
                emit({"cell": cell.name, "seed": seed,
                      "variant": fault or precision,
                      "numbers": correct.numbers(other, ref),
                      "seconds": time.perf_counter() - t3})
                del other
        del ref
        gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
