"""Kernels the program runs as custom calls, each read by its own roofline.

Each ``<name>.json`` here names one kernel: ``ops``, the prefix of the HLO
name its device ops carry in the trace, and ``part``, the part of the
step's products it computes (a key of ``flops/<family>.py``'s
``step_flops_by_part``). A kernel is a ``custom-call``, which the trace's
classes put under ``other``. Where the window's trace holds a kernel's
ops, ``<name>_roofline`` reads its part's least time (its operations, or
its least bytes, ``step_bytes_by_part``, where those take longer) over
the ops' device time, ``matmul_roofline`` leaves the part out of its
operations, and ``non_matmul_ms_per_step`` leaves the ops out of its time;
where it holds none (the jnp path), the part stays a matmul part. A kernel
is added by its file here and its reader, and edits no other file.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Any, Dict

from chipbench import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))


def table() -> Dict[str, Dict[str, Any]]:
    """Every kernel of this directory, by name."""
    out = {}
    for path in sorted(glob.glob(os.path.join(HERE, "*.json"))):
        with open(path) as f:
            out[os.path.basename(path)[:-len(".json")]] = json.load(f)
    return out


def is_op(op: T.Op, kernel: Dict[str, Any]) -> bool:
    return not op.is_async and op.name.startswith(kernel["ops"])


def seconds(tr: T.Trace, chip: int, kernel: Dict[str, Any]) -> float:
    """Device time of the kernel's ops on one chip inside the window."""
    return T.seconds(tr, chip, lambda o: is_op(o, kernel))


def traced(tr: T.Trace) -> Dict[str, Dict[str, Any]]:
    """The kernels whose ops run inside the window on some chip."""
    return {name: k for name, k in table().items()
            if any(seconds(tr, c, k) > 0 for c in tr.chips())}


def roofline(run, name: str):
    """The kernel's share of its roofline, at the chip where it takes the
    most time: the least time its part needs per step on one chip (the
    larger of its operations at the bf16 peak and its least bytes at the
    HBM peak) over its device time per step. ``None`` where the trace
    holds none of its ops."""
    tr = run.trace
    if tr is None or not tr.ops or not run.steps:
        return None
    kernel = table()[name]
    t = max(seconds(tr, c, kernel) for c in tr.chips())
    if t <= 0:
        return None
    part = kernel["part"]
    need = max(run.flops_by_part[part] / run.peak["bf16_flops_per_s"],
               run.bytes_by_part.get(part, 0.0) / run.peak["hbm_bytes_per_s"])
    return 100.0 * need / run.chips / (t / run.steps)
