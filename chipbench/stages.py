"""Device time by stage of the decoupled step, from the program's own names.

The program names its stages with ``jax.named_scope`` (``fwd<r>``,
``recompute``, ``update``, ``unpack``, ``pack``, ``clock``,
``mix.<buffer>``, and across them ``attention`` and ``head``), and the
trace carries each device op's JAX ``op_name`` path as the ``tf_op`` stat
of its event metadata (``chipbench/xspace.py``). The pipeline engine also
writes host spans ``repro.*`` around each stage call, on the device
trace's clock.

- :func:`stage_of` puts each op in exactly one stage, by precedence:
  ``recompute``, ``mix``, ``update``, ``plane`` (``unpack``, ``pack``,
  ``clock``), ``bwd`` (under ``fwd<r>`` and a ``transpose(``), ``fwd``,
  ``unscoped``. Remat's pullback carries ``transpose(recompute)``: it is
  backward, not recompute;
- :func:`partition` splits the leaf compute-stream time of one chip among
  the stages and the collectives, each instant counted once, so the parts
  sum to the union of the leaf ops;
- :func:`dispatch_idle` is the compute stream's idle time while the host
  is inside a ``repro.*`` span.

Everything is in seconds from the start of the trace, as in
``chipbench/trace.py``, whose ops and spans :func:`load` reproduces.
"""
from __future__ import annotations

import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from chipbench import trace as T
from chipbench import xspace

PROGRAM_PREFIX = "repro."
STAGES = ("recompute", "mix", "update", "plane", "bwd", "fwd", "unscoped")
PLANE = ("unpack", "pack", "clock")
CROSS_CUTS = ("attention", "head")
_FWD = re.compile(r"fwd\d+$")
_WRAPPERS = ("jvp(", "transpose(")


@dataclass
class ScopedOp(T.Op):
    scope: str = ""     # the op's tf_op: its JAX op_name path, or ""


@dataclass
class ScopedTrace(T.Trace):
    # the program's host spans (``repro.*``): (start, end, name)
    program_spans: List[Tuple[float, float, str]] = field(
        default_factory=list)


def components(scope: str) -> List[Tuple[str, bool]]:
    """``(name, transposed)`` of each component of every ``op_name`` path
    in a ``tf_op`` (paths merged by XLA are joined by ``;``; the op type
    follows the last ``:``), each name stripped of its ``jvp(`` and
    ``transpose(`` wrappers."""
    out = []
    for path in scope.rsplit(":", 1)[0].split(";"):
        for c in path.split("/"):
            transposed = c.startswith("transpose(")
            n = 0
            while c.startswith(_WRAPPERS):
                c = c[c.index("(") + 1:]
                n += 1
            if n and c.endswith(")" * n):
                c = c[:len(c) - n]
            out.append((c, transposed))
    return out


def stage_of(scope: str) -> str:
    """The one stage an op belongs to, by :data:`STAGES`' precedence."""
    comps = components(scope)
    names = {c for c, _ in comps}
    if any(c == "recompute" and not t for c, t in comps):
        return "recompute"
    if any(c.startswith("mix.") for c in names):
        return "mix"
    if "update" in names:
        return "update"
    if names & set(PLANE):
        return "plane"
    if any(_FWD.match(c) for c in names):
        return "bwd" if "transpose(" in scope else "fwd"
    return "unscoped"


def in_scope(op: ScopedOp, name: str) -> bool:
    """Whether ``name`` is one of the op's scopes (wrappers stripped)."""
    return any(c == name for c, _ in components(op.scope))


# ---------------------------------------------------------------------------
# reading a trace
# ---------------------------------------------------------------------------


def _ns(line: xspace.Line, off_ps: int) -> int:
    # the profiler's own reader truncates to whole nanoseconds
    return line.timestamp_ns + off_ps // 1000


def load(path: str) -> ScopedTrace:
    """:func:`chipbench.trace.load`'s reduction of one ``.xplane.pb``
    (gzipped or not, or the newest under a directory), with each device
    op's ``tf_op`` and the program's ``repro.*`` host spans."""
    if os.path.isdir(path):
        path = T.find_xplane(path)
    base = T.load(path)
    tr = ScopedTrace(spans=base.spans, window=base.window)
    for plane in xspace.load(path):
        chip = T._device_index(plane.name)
        for line in plane.lines:
            if chip is not None and line.name in (T.SYNC_LINE, T.ASYNC_LINE):
                ops = tr.ops.setdefault(chip, [])
                for mid, off, dur in line.events:
                    text = plane.event_names[mid]
                    name, cat = T.parse_op(text)
                    s = _ns(line, off) * 1e-9
                    ops.append(ScopedOp(
                        s, s + (dur // 1000) * 1e-9, name, cat,
                        is_async=line.name == T.ASYNC_LINE,
                        scope=str(plane.event_stats[mid].get("tf_op", ""))))
            elif chip is None and plane.name.startswith("/host:"):
                for mid, off, dur in line.events:
                    name = plane.event_names[mid]
                    if name.startswith(PROGRAM_PREFIX):
                        s = _ns(line, off) * 1e-9
                        tr.program_spans.append(
                            (s, s + (dur // 1000) * 1e-9, name))
    for ops in tr.ops.values():
        ops.sort(key=lambda o: (o.start, -o.end))
        T._mark_parents(ops)
    tr.program_spans.sort()
    _check_same_ops(tr, base)
    return tr


def _check_same_ops(tr: ScopedTrace, base: T.Trace) -> None:
    """The raw reading has to give the very ops ``trace.load`` gives."""
    key = lambda o: (o.start, o.end, o.name, o.category, o.is_async, o.leaf)
    for chip in set(tr.ops) | set(base.ops):
        a = [key(o) for o in tr.ops.get(chip, [])]
        b = [key(o) for o in base.ops.get(chip, [])]
        if a != b:
            raise ValueError(f"chip {chip}: the raw trace's ops differ from "
                             "the profiler reader's")


# ---------------------------------------------------------------------------
# what the per-stage numbers read
# ---------------------------------------------------------------------------


def _leaf_sync(tr: T.Trace, chip: int) -> List[T.Op]:
    return [o for o in tr.ops[chip] if o.leaf and not o.is_async]


def partition(tr: ScopedTrace, chip: int) -> Dict[str, float]:
    """Seconds of the window's leaf compute-stream time in each stage and
    in the collectives (``"collective"``). Where ops overlap, the earlier
    keeps the shared time, so the parts sum to the union of the leaves."""
    a, b = T.window_of(tr)
    out = dict.fromkeys(STAGES + ("collective",), 0.0)
    cursor = a
    for o in _leaf_sync(tr, chip):   # sorted by start
        s, e = max(o.start, cursor), min(o.end, b)
        if e > s:
            part = ("collective" if o.kind == "collective"
                    else stage_of(o.scope))
            out[part] += e - s
        cursor = max(cursor, o.end)
    return out


def leaf_busy(tr: T.Trace, chip: int) -> float:
    """Seconds of the window in which a leaf compute-stream op runs."""
    return T.length(T.clip(T.union((o.start, o.end)
                                   for o in _leaf_sync(tr, chip)),
                           T.window_of(tr)))


def cross_cut(tr: ScopedTrace, chip: int, name: str) -> float:
    """Compute-stream time of the non-collective leaf ops under the scope
    ``name`` (``attention``, ``head``), whatever their stage."""
    return T.seconds(tr, chip, lambda o: not o.is_async
                     and o.kind != "collective" and in_scope(o, name))


def dispatch_idle(tr: ScopedTrace, chip: int) -> float:
    """Seconds in which the compute stream idles while the host is inside
    one of the program's ``repro.*`` spans."""
    w = T.window_of(tr)
    host = T.union(T.clip([(s, e) for s, e, _ in tr.program_spans], w))
    idle = T.idle_gaps(tr, chip)
    return T.length(idle) - T.length(T.subtract(idle, host))


def top_ops(tr: ScopedTrace, stage: str,
            n: int = 8) -> List[Tuple[str, float]]:
    """The leaf compute-stream ops of one stage with the most device time
    inside the window, summed over the chips: ``"name [category] scope"``
    and seconds."""
    a, b = T.window_of(tr)
    tot: Dict[str, float] = {}
    for chip in tr.chips():
        for o in _leaf_sync(tr, chip):
            d = min(o.end, b) - max(o.start, a)
            if d > 0 and o.kind != "collective" \
                    and stage_of(o.scope) == stage:
                key = f"{o.name} [{o.category}] {o.scope}"
                tot[key] = tot.get(key, 0.0) + d
    return sorted(tot.items(), key=lambda x: -x[1])[:n]


def per_step(tr: ScopedTrace, steps: int) -> Dict[str, Optional[float]]:
    """Each per-stage number in ms per step, at the chip where it is
    largest; ``None`` where the trace has nothing of it to read (a stage
    or cross-cut with no op, such as ``mix`` on one worker, or no program
    span)."""
    if not tr.ops or not steps:
        return {}
    chips = tr.chips()
    parts = {c: partition(tr, c) for c in chips}
    ms = lambda v: 1e3 * v / steps
    some = lambda v: ms(v) if v > 0 else None
    out = {f"{s}_ms_per_step": some(max(parts[c][s] for c in chips))
           for s in STAGES}
    for name in CROSS_CUTS:
        out[f"{name}_ms_per_step"] = some(max(cross_cut(tr, c, name)
                                              for c in chips))
    out["dispatch_idle_ms_per_step"] = (
        ms(max(dispatch_idle(tr, c) for c in chips))
        if tr.program_spans else None)
    out["collective_ms_per_step"] = ms(max(parts[c]["collective"]
                                           for c in chips))
    out["leaf_busy_ms_per_step"] = ms(max(leaf_busy(tr, c) for c in chips))
    out["busy_ms_per_step"] = ms(max(T.busy(tr, c) for c in chips))
    return out
