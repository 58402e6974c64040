"""The comparison that decides ``correct``.

The program's first ``CHECK_STEPS`` steps against the plain reference's,
on the same seed:

- ``loss_gap``: the largest relative gap between the program's loss and
  the reference's, over the checked steps;
- ``grad_gap``: the first gradient as the optimizer gets it, read from its
  momentum after the first step that applies one; the gap between the
  program's norm of each leaf and the reference's, over the reference's
  norm of that leaf or of the median leaf, whichever is larger, at the
  worst leaf and worker;
- ``delta_gap``: the same, of the norm of the parameters' change over the
  checked steps, at the worst of the copies the program holds: LayUp's
  write plane and the read plane that the next forward takes (a read
  plane that does not adopt the write plane fails here). Leaves whose
  reference gradient is under a thousandth of the median leaf's move by
  rounding alone and are left out;
- ``clock_gap`` (LayUp): the largest gap between the program's per-worker,
  per-group version clocks and the reference's after the checked steps.

Each has a limit of its own, kept per cell in ``limits/<cell>.json`` with
the readings it was set from. A limit of ``null`` marks a number that
neither the control nor any fault of the cell separates from sound runs:
it is reported and not compared. A number that a limit compares and the
run does not give fails.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "delta_gap", "clock_gap")
# the steps that set-up drives through the window's own call and the
# reference follows
CHECK_STEPS = 3
# a leaf whose reference gradient is under this share of the median leaf's
# is left out of the change
STILL_LEAF = 1e-3


def _by_leaf(norms) -> Dict[str, np.ndarray]:
    """``{leaf: (M,)}`` from either that or a per-worker list of dicts."""
    if isinstance(norms, dict):
        return {k: np.asarray(v, np.float64).reshape(-1)
                for k, v in norms.items()}
    return {k: np.asarray([w[k] for w in norms], np.float64)
            for k in norms[0]}


def _worst(prog, ref, keep=None) -> float:
    prog, ref = _by_leaf(prog), _by_leaf(ref)
    leaves = sorted(ref)
    if set(prog) != set(ref):
        raise ValueError(f"leaves differ: program {sorted(prog)} vs "
                         f"reference {leaves}")
    r = np.stack([ref[k] for k in leaves])     # (leaves, M)
    p = np.stack([prog[k] for k in leaves])
    mask = np.ones_like(r, bool) if keep is None else keep
    med = np.array([np.median(r[mask[:, j], j]) for j in range(r.shape[1])])
    gap = np.abs(p - r) / np.maximum(np.maximum(r, med[None]), 1e-30)
    return float(np.max(np.where(mask, gap, 0.0)))


def gaps(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The numbers compared, from ``{"losses", "m_norms", "d_norms",
    "versions"}`` of the program and of the reference; the program's may
    add ``d_norms_read``, the change of LayUp's read plane. ``versions``
    is ``None`` where the algorithm keeps no clocks."""
    keys = NUMBERS if ref.get("versions") is not None else NUMBERS[:3]
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    if lp.shape != lr.shape or not np.all(np.isfinite(lp)):
        return {k: float("inf") for k in keys}
    g_ref = _by_leaf(ref["m_norms"])
    leaves = sorted(g_ref)
    r = np.stack([g_ref[k] for k in leaves])
    keep = r >= STILL_LEAF * np.median(r, axis=0, keepdims=True)
    out = {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_gap": _worst(prog["m_norms"], ref["m_norms"]),
        "delta_gap": max(_worst(d, ref["d_norms"], keep)
                         for d in (prog["d_norms"], prog.get("d_norms_read"))
                         if d is not None),
    }
    if "clock_gap" in keys:
        vp = np.asarray(prog["versions"], np.float64)
        vr = np.asarray(ref["versions"], np.float64)
        out["clock_gap"] = (float(np.max(np.abs(vp - vr)))
                            if vp.shape == vr.shape else float("inf"))
    return {k: (v if np.isfinite(v) else float("inf")) for k, v in out.items()}


def compared(limits: Dict[str, float]) -> List[str]:
    return [k for k in NUMBERS if limits.get(k) is not None]


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(k in numbers and numbers[k] <= limits[k]
               for k in compared(limits))


def report(numbers: Dict[str, float], limits: Dict[str, float]) -> List[str]:
    """One line per number, with its limit or "not compared"."""
    return [f"{k} {numbers.get(k)!r} "
            + (f"limit {limits[k]!r}" if limits.get(k) is not None
               else "not compared")
            for k in NUMBERS if k in numbers or k in limits]
