"""The one reduction from a profiler trace to busy time, idle gaps and
events by name.  Every per-layer metric reads what this module returns.

A trace is the ``*.xplane.pb`` that ``jax.profiler`` writes.  On a TPU it
holds one plane per chip (``/device:TPU:<n>``) whose lines are

  ``XLA Ops``        the operations run on the chip's core, one after the
                     other; the busy time is the union of their intervals;
  ``Async XLA Ops``  asynchronous work (copies, collectives) that overlaps
                     the core's operations;
  ``XLA Modules``    one event per run of a compiled program;

and one plane ``/host:CPU`` with the host's annotations, such as the
harness's ``round`` steps (``jax.profiler.StepTraceAnnotation``).  Host
and device events share one clock, in nanoseconds, to about a millisecond
(a chip trace shows device operations up to 1.1 ms before the host step
that issued them); so the window of the traced rounds is taken on each
device's own events (``device_window``), and the host's spans only name
what the host was doing in a gap.

An operation's name is the HLO instruction's text, with its result and
operand shapes; ``parse_hlo`` reads them back, so that a kernel's FLOPs
and bytes can be counted from the shapes of each call.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

# host annotations the harness writes, and the steps' name
ROUND = "round"
HOST_SPANS = ("batch build", "dispatch")


@dataclasses.dataclass
class Event:
    name: str
    start: float                     # ns, on the trace's clock
    dur: float                       # ns
    stats: Dict[str, str] = dataclasses.field(default_factory=dict)

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Device:
    name: str                        # "/device:TPU:0"
    ops: List[Event]
    async_ops: List[Event]
    modules: List[Event]


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    host: List[Event]                # host annotations, by start time


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {directory}")
    return found[-1]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` (or the newest one under a directory)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            lines = {ln.name: [_event(e) for e in ln.events]
                     for ln in plane.lines}
            devices.append(Device(plane.name, lines.get("XLA Ops", []),
                                  lines.get("Async XLA Ops", []),
                                  lines.get("XLA Modules", [])))
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                host.extend(_event(e) for e in ln.events
                            if e.name == ROUND or e.name in HOST_SPANS)
    devices.sort(key=lambda d: _device_index(d.name))
    host.sort(key=lambda e: e.start)
    return Trace(devices, host)


def _event(e) -> Event:
    return Event(e.name, float(e.start_ns), float(e.duration_ns),
                 {str(k): str(v) for k, v in e.stats})


def _device_index(name: str) -> int:
    m = re.search(r"(\d+)$", name)
    return int(m.group(1)) if m else 0


# -- intervals ----------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping or touching intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy(events: Sequence[Event], lo: float, hi: float) -> float:
    """ns in [lo, hi] during which at least one of ``events`` runs."""
    return sum(e - s for s, e in
               union(clip(((ev.start, ev.end) for ev in events), lo, hi)))


def gaps(events: Sequence[Event], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi]: where none of ``events`` runs."""
    out, t = [], lo
    for s, e in union(clip(((ev.start, ev.end) for ev in events), lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def overlap(a: Interval, intervals: Iterable[Interval]) -> float:
    """ns of interval ``a`` covered by the union of ``intervals``."""
    return sum(e - s for s, e in union(clip(intervals, a[0], a[1])))


def by_name(events: Iterable[Event], lo: float = float("-inf"),
            hi: float = float("inf")) -> Dict[str, Tuple[int, float]]:
    """name -> (count, summed ns) over the events that start in [lo, hi)."""
    out: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    for ev in events:
        if lo <= ev.start < hi:
            out[ev.name][0] += 1
            out[ev.name][1] += ev.dur
    return {k: (int(c), t) for k, (c, t) in out.items()}


# -- rounds -------------------------------------------------------------------

def rounds(trace: Trace) -> List[Event]:
    """The harness's ``round`` steps, in order."""
    return [e for e in trace.host if e.name == ROUND]


@dataclasses.dataclass
class Window:
    """The traced rounds on one device, on the device's own clock: from
    the start of a run of the round program (the first that is not
    skipped) to the start of the last, so ``rounds`` whole periods of
    program and gap."""
    device: Device
    program: str
    lo: float
    hi: float
    rounds: int


def device_window(device: Device, n_rounds: int, skip: int = 0
                  ) -> Optional[Window]:
    """The window of ``device`` over the ``n_rounds`` traced rounds less
    the first ``skip`` of them, or None when no program ran once a
    round."""
    prog = round_program(device, n_rounds)
    if prog is None:
        return None
    runs = sorted(m.start for m in device.modules if m.name == prog)[skip:]
    if len(runs) < 2:
        return None
    return Window(device, prog, runs[0], runs[-1], len(runs) - 1)


def round_program(device: Device, n_rounds: int) -> Optional[str]:
    """The program each round runs once: of the modules that ran
    ``n_rounds`` times or more, the one with the most device time."""
    counts = by_name(device.modules)
    best = [(t, name) for name, (c, t) in counts.items() if c >= n_rounds]
    return max(best)[1] if best else None


def inter_round_gaps(device: Device, program: str, lo: float, hi: float
                     ) -> List[float]:
    """Device-idle ns between one run of the round program and the next,
    for the runs that start in [lo, hi] (helper programs that run in
    between count as busy)."""
    runs = sorted((m for m in device.modules
                   if m.name == program and lo <= m.start <= hi),
                  key=lambda m: m.start)
    out = []
    for a, b in zip(runs, runs[1:]):
        span = b.start - a.end
        out.append(span - busy(device.ops, a.end, b.start))
    return out


def labelled_gaps(trace: Trace, device: Device, lo: float, hi: float,
                  prog: Optional[str], min_ns: float = 0.0
                  ) -> List[Tuple[str, float]]:
    """Each idle gap of ``device`` in [lo, hi], labelled by where it lies:
    inside a program run ("inside the round program" for ``prog``,
    "inside <module>" for another), or between runs, where the host span
    that covers most of it names what the host was doing."""
    spans = defaultdict(list)
    for e in trace.host:
        if e.name in HOST_SPANS:
            spans[e.name].append((e.start, e.end))
    out = []
    for g in gaps(device.ops, lo, hi):
        dur = g[1] - g[0]
        if dur <= min_ns:
            continue
        inside = [m for m in device.modules
                  if m.start <= g[0] and g[1] <= m.end]
        if inside:
            name = inside[0].name
            label = ("inside the round program" if name == prog
                     else f"inside {_module_label(name)}")
        else:
            cover = {k: overlap(g, v) for k, v in spans.items()}
            best = max(cover, key=cover.get) if cover else None
            label = ("between rounds (host)" if not best or not cover[best]
                     else f"between rounds (host): {best}")
        out.append((label, dur))
    return out


def _module_label(name: str) -> str:
    return name.split("(")[0]


# -- HLO calls ----------------------------------------------------------------

_ARRAY = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|s64|u64|bf16|f16|f32|"
                    r"f64|f8e4m3fn|f8e5m2)\[([0-9,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
          "s16": 2, "u16": 2, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
          "f32": 4, "s64": 8, "u64": 8, "f64": 8}


@dataclasses.dataclass
class Array:
    dtype: str
    shape: Tuple[int, ...]

    @property
    def size(self) -> int:
        n = 1
        for d in self.shape:
            n *= d
        return n

    @property
    def nbytes(self) -> int:
        return self.size * _BYTES[self.dtype]


@dataclasses.dataclass
class Call:
    op: str                           # HLO name, e.g. "closed_call.24"
    kind: str                         # the HLO opcode, e.g. "custom-call"
    target: Optional[str]             # custom_call_target, if any
    results: List[Array]
    operands: List[Array]


def _arrays(text: str) -> List[Array]:
    return [Array(m.group(1),
                  tuple(int(x) for x in m.group(2).split(",") if x))
            for m in _ARRAY.finditer(text)]


def parse_hlo(name: str) -> Optional[Call]:
    """``%op = <results> kind(<operands>), attrs`` -> Call; None when the
    text is not an HLO instruction."""
    m = re.match(r"\s*%?(\S+)\s*=\s*(.*?)\s+([\w-]+)\((.*)$", name)
    if not m:
        return None
    op, results, kind, rest = m.group(1), m.group(2), m.group(3), m.group(4)
    depth, cut = 1, len(rest)
    for i, ch in enumerate(rest):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            cut = i
            break
    t = re.search(r'custom_call_target="([^"]+)"', rest[cut:])
    return Call(op, kind, t.group(1) if t else None, _arrays(results),
                _arrays(rest[:cut]))


# opcodes whose event spans the operations of the body it runs
CONTROL = ("while", "conditional", "call")


def leaf_ops(device: Device) -> List[Event]:
    """The core's operations without the control-flow events that enclose
    other operations (a ``while`` spans every operation of its body)."""
    out = []
    for ev in device.ops:
        call = parse_hlo(ev.name[:2000])
        if call is None or call.kind not in CONTROL:
            out.append(ev)
    return out


def pallas_calls(device: Device, lo: float, hi: float
                 ) -> List[Tuple[Call, Event]]:
    """The Pallas kernels (``tpu_custom_call``) run in [lo, hi)."""
    out = []
    for ev in device.ops:
        if lo <= ev.start < hi and "tpu_custom_call" in ev.name:
            call = parse_hlo(ev.name)
            if call is not None:
                out.append((call, ev))
    return out
