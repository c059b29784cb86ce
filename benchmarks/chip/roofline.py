"""Device-time arithmetic shared by the kernel and scope metrics.

A kernel call's least time is the larger of its FLOPs over the chip's
peak FLOP/s and its bytes over the chip's HBM bandwidth, both counted
from the call's shapes by the kernel's metric file.  The kernel's share
of its roofline is the least time of every call in the traced rounds over
the device time those calls took.

A scope's device time (``scope_ms``) is the busy time a round of the
operations that the program ran under a ``jax.named_scope``, read from
each operation's name stack (``trace.scope_of``).
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

from benchmarks.chip import trace as T

# the round program's named scopes (``core/distributed.py``)
ROUND_SCOPES = ("private_loss", "public_logits", "eq2", "optimizer")


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


def scope_ms(ctx, scope: Optional[str],
             among: Sequence[str] = ()) -> Optional[float]:
    """Busy device time a traced round, in ms, of the leaf operations
    whose outermost scope of ``among`` (with ``scope`` added) is
    ``scope``; with ``scope`` None, of those under none of ``among``.
    The union of their intervals a round, averaged over the cell's chips,
    as ``round_device_ms`` takes all operations; None when the trace has
    no window or, for a named scope, no operation of it."""
    names = tuple(among) + ((scope,) if scope and scope not in among
                            else ())
    per_chip, found = [], scope is None
    for w in ctx.windows:
        ops = [ev for ev in T.leaf_ops(w.device)
               if T.scope_of(ev, names) == scope]
        found = found or bool(ops)
        per_chip.append(T.busy(ops, w.lo, w.hi) / w.rounds)
    if not per_chip or not found:
        return None
    return sum(per_chip) / len(per_chip) * 1e-6


def interface_bytes(call: T.Call) -> int:
    """Bytes of every operand the call reads and every result it writes."""
    return sum(a.nbytes for a in call.operands + call.results)


def prod(xs) -> int:
    n = 1
    for x in xs:
        n *= x
    return n
