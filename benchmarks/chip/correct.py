"""The comparison that decides a run's ``correct``.

The program's first ``STEPS`` rounds, driven through the window's own
call, are set beside the plain reference's (``reference.dml``) from the
same seed.  Four numbers are compared, each against its limit in the
cell's file (``workloads/<cell>.json``):

  loss_gap    the widest relative gap of a logged cross-entropy (private
              or public), over the steps and clients;
  kl_gap      the widest relative gap of the logged Eq.-2 term (KLD_avg);
  grad_gap    step 1's gradient as AdamW took it, read back from the
              first moment (g = mu / (1 - b1)): the widest gap between
              the program's and the reference's norm of a leaf, over the
              reference's norm of that leaf or of the client's median
              leaf, whichever is larger;
  change_gap  the same gap for the norm of each leaf's change over the
              ``STEPS`` updates.  Leaves whose reference gradient is under
              ``NOUGHT`` of the client's median leaf are left out: with
              nothing to follow, Adam moves them by round-off alone.

A leaf is one tensor of one layer of one client, in the reference's
layout.  A number that is not finite fails.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

STEPS = 3
NOUGHT = 1e-3
NUMBERS = ("loss_gap", "kl_gap", "grad_gap", "change_gap")


def _relative(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.abs(b)))


def _norm_gap(prog, ref, keep=None) -> float:
    """Widest |prog - ref| / max(ref, median leaf of ref), per client."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    worst = 0.0
    for c in range(ref.shape[0]):
        idx = np.arange(ref.shape[1]) if keep is None else \
            np.flatnonzero(keep[c])
        floor = np.median(ref[c])
        gap = np.abs(prog[c, idx] - ref[c, idx]) / np.maximum(ref[c, idx],
                                                              floor)
        worst = max(worst, float(np.max(gap)))
    return worst


def numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """prog/ref: ``losses`` (STEPS, K, 3), ``grad_norms`` and
    ``change_norms`` (K, n_leaves) in the same leaf order."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    grads = np.asarray(ref["grad_norms"], np.float64)
    keep = grads >= NOUGHT * np.median(grads, axis=1, keepdims=True)
    out = {"loss_gap": _relative(lp[..., :2], lr[..., :2]),
           "kl_gap": _relative(lp[..., 2], lr[..., 2]),
           "grad_gap": _norm_gap(prog["grad_norms"], grads),
           "change_gap": _norm_gap(prog["change_norms"], ref["change_norms"],
                                   keep)}
    return {k: (v if np.isfinite(v) else float("inf")) for k, v in
            out.items()}


def left_out(ref: dict) -> list:
    """Names of the leaves ``change_gap`` leaves out, per client."""
    grads = np.asarray(ref["grad_norms"], np.float64)
    med = np.median(grads, axis=1, keepdims=True)
    return [[ref["leaves"][i] for i in np.flatnonzero(row)]
            for row in grads < NOUGHT * med]


def decide(values: Dict[str, float], limits: Optional[Dict[str, float]]
           ) -> tuple:
    """(correct, checks): every number with a limit at or under it.
    Without limits (a cell not yet calibrated) nothing is correct."""
    if not limits:
        return False, {k: {"value": v, "limit": None}
                       for k, v in values.items()}
    checks = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return bool(ok), checks
