"""One run of one cell: set up, measure a window of federated rounds,
optionally trace it, then check the rounds against the plain reference.

Everything a cell is made of is found by name:

  BENCHMARK.json                  the cell: its configuration, traffic,
                                  chips, and the metrics it reports;
  configs/<config>.json           the model: the program's registry entry
                                  and replaced keys, the published values,
                                  and the reference family (reference/);
  traffic/<traffic>.json          the federated job: strategy, clients,
                                  batch, sequence, public batch, optimizer;
  workloads/<cell>.json           the limits of ``correct`` and the
                                  readings they were set from;
  metrics/<metric>.py             one per-layer metric: ``read(ctx)``;
  peaks.json                      the chip's peaks, by ``device_kind``.

A run prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (with
``--trace 1`` also ``breakdown``), and last the compared numbers beside
their limits under ``checks``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"
TRACE_ROUNDS = 6          # rounds profiled at the start of a traced window
# traced rounds before those that the metrics read: the first traced round
# can hold a host stall (about 200 ms on four chips) that no later round has
SETTLE_ROUNDS = 1
GIB = 2 ** 30


class Refused(Exception):
    """The run cannot measure what the cell asks for: no result."""


# -- the cell, found by name --------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: Optional[dict]
    family: object
    layer_metrics: List[str]

    @property
    def tokens_per_round(self) -> int:
        t = self.traffic
        return t["clients"] * (t["batch"] + t["public_batch"]) * t["seq"]


def manifest() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _json(path: Path) -> dict:
    if not path.is_file():
        raise Refused(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def family(name: str):
    return importlib.import_module(f"benchmarks.chip.reference.{name}")


def layer_metrics(man: dict, cell: str) -> List[str]:
    """The per-layer metrics whose ``workloads`` list the cell."""
    return [m["name"] for m in man["per_layer"] if cell in m["workloads"]]


def load_cell(name: str) -> Cell:
    man = manifest()
    entry = next((w for w in man["workloads"] if w["name"] == name), None)
    if entry is None:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    config = _json(HERE / "configs" / f"{entry['config']}.json")
    traffic = _json(HERE / "traffic" / f"{entry['traffic']}.json")
    limits = _json(HERE / "workloads" / f"{name}.json").get("limits")
    return Cell(name, int(entry["chips"]), config, traffic, limits,
                family(config["reference"]), layer_metrics(man, name))


def metric_module(name: str):
    path = HERE / "metrics" / f"{name}.py"
    if not path.is_file():
        raise Refused(f"missing metrics/{name}.py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.chip.metrics._" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(kind: str) -> dict:
    """The chip's peaks from ``peaks.json``; a kind not in it is an error."""
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise Refused(f"no peaks for device kind {kind!r} in peaks.json "
                      f"(known: {sorted(table)})")
    return table[kind]


# -- the device ---------------------------------------------------------------

def chips_for(cell: Cell, on_chip: bool):
    """The devices the cell runs on.  On the chip, anything but a TPU with
    enough chips refuses the run: it never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if on_chip:
        if devs[0].platform != "tpu":
            raise Refused(f"no TPU: JAX found {devs[0].platform!r} devices")
        if len(devs) < cell.chips:
            raise Refused(f"cell {cell.name} needs {cell.chips} chips, JAX "
                          f"found {len(devs)}")
    return devs


def use_cache() -> str:
    """JAX's persistent compilation cache in ``.jax_cache/`` of this
    checkout, passed to the program through JAX_COMPILATION_CACHE_DIR;
    every program is kept, however short its compile."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    from benchmarks.chip.program import use_compile_cache
    path = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts the programs compiled or loaded from the cache."""

    def __init__(self):
        import jax.monitoring as mon
        self.n = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, *_args, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _event(self, event: str, *_args, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.n += 1

    def close(self) -> None:
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._duration)
        mon.unregister_event_listener(self._event)


def memory_peak(devs) -> int:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


# -- host spans of a traced window --------------------------------------------

def _annotated(fn: Callable, label: str) -> Callable:
    import jax

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(label):
            return fn(*a, **kw)
    return wrapped


def annotate_host(pop) -> None:
    """Host spans around the calls the round makes into the population:
    the batch build and the dispatch of the round program."""
    for attr in ("_private_batch", "_public_batch"):
        setattr(pop, attr, _annotated(getattr(pop, attr), "batch build"))
    for key in list(pop._steps):
        pop._steps[key] = _annotated(pop._steps[key], "dispatch")


# -- one run ------------------------------------------------------------------

def first_rounds(cell: Cell, pop, fed) -> dict:
    """Drive the session from its weights through its first rounds with
    the window's own call, and read what ``correct`` compares: each
    round's logged losses, step 1's gradient from AdamW's first moment,
    and each leaf's change over the rounds.  The weights before the
    first round are kept on the host and every norm is taken there, so
    that no copy of the fleet's state adds to the device's peak."""
    import jax
    import numpy as np
    from benchmarks.chip import correct, program
    b1 = cell.traffic["optimizer"]["b1"]
    start = jax.device_get(pop.client_params)
    fed.run(until=1)
    grads = program.host_norms(cell.family, pop.client_opts["mu"]) / (1 - b1)
    fed.run(until=correct.STEPS)
    change = program.host_norms(cell.family, pop.client_params, start)
    return {"losses": np.stack([program.round_metrics(fed.history, r)
                                for r in range(correct.STEPS)]),
            "grad_norms": grads, "change_norms": change}


@dataclasses.dataclass
class TraceContext:
    """What a per-layer metric reads: the reduced trace, and on each of
    the cell's chips the window of the traced rounds (``trace.Window``:
    the chip's own clock, from the first run of the round program to the
    last, ``rounds`` whole rounds)."""
    trace: object
    windows: list
    cell: Cell
    peaks: dict

    @property
    def rounds(self) -> int:
        return self.windows[0].rounds if self.windows else 0

    @property
    def window_s(self) -> float:
        """The window's length, averaged over the chips."""
        if not self.windows:
            return 0.0
        return sum(w.hi - w.lo for w in self.windows) / len(self.windows) \
            * 1e-9

    def busy_s(self, w) -> float:
        from benchmarks.chip import trace as T
        return T.busy(w.device.ops, w.lo, w.hi) * 1e-9

    def mean_busy_s(self) -> float:
        if not self.windows:
            return 0.0
        return sum(self.busy_s(w) for w in self.windows) / len(self.windows)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run(cell: Cell, seed: int, seconds: float, traced: bool, *,
        on_chip: bool = True, t_start: Optional[float] = None,
        after_build: Optional[Callable] = None) -> dict:
    """One run of ``cell``; returns the result line's object.

    ``on_chip=False`` skips the look for a TPU, the peaks and the compile
    cache (the CPU tests).  ``after_build(pop, fed)``, called once the
    session holds the benchmark's weights, may break the timed path from
    outside (the fault tests).
    """
    import jax
    import numpy as np

    t_start = time.perf_counter() if t_start is None else t_start
    devs = chips_for(cell, on_chip)
    from benchmarks.chip import correct, program
    from benchmarks.chip import trace as T
    from benchmarks.chip.reference import dml as ref_dml
    used = devs[:cell.chips]
    peak_table = peaks(devs[0].device_kind) if on_chip else {}
    if on_chip:
        log(f"compile cache: {use_cache()}")
    counter = CompileCounter()

    # -- set-up: the session, the benchmark's weights, the first rounds
    pop, fed = program.build(cell.config, cell.traffic, seed, cell.chips)
    if on_chip and pop.impl != "pallas":
        raise Refused(f"the population resolved impl {pop.impl!r}, not "
                      "'pallas'")
    make_weights = program.weight_maker(cell.family, cell.config,
                                        cell.traffic, pop.client_params)
    program.install_weights(pop, make_weights, seed)
    del make_weights
    if after_build is not None:
        after_build(pop, fed)
    log(f"set-up: session and weights after "
        f"{time.perf_counter() - t_start:.1f} s")
    prog = first_rounds(cell, pop, fed)
    log(f"set-up: first {correct.STEPS} rounds after "
        f"{time.perf_counter() - t_start:.1f} s")

    # -- the window
    compiled_before = counter.n
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    r0 = r = fed.round
    trace_dir = None
    if traced:
        annotate_host(pop)
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        while (r - r0 < SETTLE_ROUNDS + TRACE_ROUNDS
               and time.perf_counter() - t0 < seconds):
            with jax.profiler.StepTraceAnnotation("round", step_num=r):
                fed.run(until=r + 1)
            r += 1
        jax.profiler.stop_trace()
    while time.perf_counter() - t0 < seconds or r == r0:
        fed.run(until=r + 1)
        r += 1
    jax.block_until_ready(pop.client_params)
    t1 = time.perf_counter()
    compiled_in_window = counter.n - compiled_before
    counter.close()
    n_rounds = r - r0
    peak = memory_peak(used)
    window_losses = [program.round_metrics(fed.history, i)
                     for i in range(r0, r)]
    failed = sum(not np.all(np.isfinite(m)) for m in window_losses)
    log(f"window: {n_rounds} rounds in {t1 - t0:.4f} s, setup {setup_s:.4f} "
        f"s, programs compiled in the window: {compiled_in_window}, peak "
        f"{peak / GIB:.4f} GiB")

    metrics: Dict[str, dict] = {}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    breakdown = None
    if traced:
        tr = T.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        n_traced = len(T.rounds(tr))
        wins = [T.device_window(d, n_traced, skip=SETTLE_ROUNDS)
                for d in tr.devices[:cell.chips]]
        ctx = TraceContext(tr, [w for w in wins if w], cell, peak_table)
        if on_chip and (len(ctx.windows) != cell.chips or
                        ctx.mean_busy_s() <= 0):
            raise Refused("the trace shows no round program on the chip")
        for name in cell.layer_metrics:
            mod = metric_module(name)
            value = mod.read(ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": mod.UNIT}
        device["busy_s"] = ctx.mean_busy_s()
        device["window_s"] = ctx.window_s
        breakdown = breakdown_of(tr, ctx.windows)
    else:
        tokens = n_rounds * cell.tokens_per_round
        metrics["tokens_per_s"] = {"value": tokens / (t1 - t0),
                                   "unit": "tokens/s"}
        metrics["peak_hbm_gib"] = {"value": peak / GIB, "unit": "GiB"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}

    # -- the reference, once the program's state is gone
    del fed, pop
    gc.collect()
    t_ref = time.perf_counter()
    ref = ref_dml.Federation(cell.family, cell.config, cell.traffic,
                             devices=used).run(seed, correct.STEPS)
    values = correct.numbers(prog, ref)
    ok, checks = correct.decide(values, cell.limits)
    if failed:
        ok = False
    log(f"reference: {time.perf_counter() - t_ref:.1f} s; left out of "
        f"change_gap: {correct.left_out(ref)}")
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    out = {"correct": ok, "attempted": n_rounds, "failed": failed,
           "metrics": metrics, "device": device,
           "compiled_in_window": compiled_in_window}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def breakdown_of(tr, windows) -> Optional[dict]:
    """The device operations that took most time, and the longest idle
    gaps with what the host was doing, on the first chip."""
    from benchmarks.chip import trace as T
    if not windows:
        return None
    w = windows[0]
    ops = T.by_name(T.leaf_ops(w.device), w.lo, w.hi)
    top = sorted(ops.items(), key=lambda kv: -kv[1][1])[:10]
    gaps = sorted(T.labelled_gaps(tr, w.device, w.lo, w.hi, w.program),
                  key=lambda g: -g[1])[:10]
    return {"device_ops": [[_op_label(n), t * 1e-9] for n, (_, t) in top],
            "idle_gaps": [[label, ns * 1e-9] for label, ns in gaps]}


def _op_label(name: str) -> str:
    from benchmarks.chip import trace as T
    call = T.parse_hlo(name)
    if call is None:
        return name[:80]
    res = ",".join(f"{a.dtype}{list(a.shape)}" for a in call.results[:2])
    kind = "pallas " if call.target == "tpu_custom_call" else ""
    return f"{kind}{call.op} -> {res}"[:120]


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        out = run(cell, args.seed, args.seconds, bool(args.trace),
                  t_start=t_start)
    except Refused as e:
        log(f"refused: {e}")
        return 2
    print(json.dumps(out), flush=True)
    return 0
