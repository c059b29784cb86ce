"""Roofline arithmetic shared by the kernel metrics.

A kernel call's least time is the larger of its FLOPs over the chip's
peak FLOP/s and its bytes over the chip's HBM bandwidth, both counted
from the call's shapes by the kernel's metric file.  The kernel's share
of its roofline is the least time of every call in the traced rounds over
the device time those calls took.
"""
from __future__ import annotations

from typing import Callable, Optional

from benchmarks.chip import trace as T


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


def share(ctx, match: Callable, flops: Callable, nbytes: Callable
          ) -> Optional[float]:
    """100 x sum(least time) / sum(device time) over the calls ``match``
    accepts on the cell's devices; None when no call ran."""
    least = spent = 0.0
    for w in ctx.windows:
        for call, ev in T.pallas_calls(w.device, w.lo, w.hi):
            if match(call):
                least += least_time(flops(call, ctx), nbytes(call, ctx),
                                    ctx.peaks)
                spent += ev.dur * 1e-9
    return 100.0 * least / spent if spent else None


def interface_bytes(call: T.Call) -> int:
    """Bytes of every operand the call reads and every result it writes."""
    return sum(a.nbytes for a in call.operands + call.results)


def prod(xs) -> int:
    n = 1
    for x in xs:
        n *= x
    return n
