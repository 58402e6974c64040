"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

- device operations per chip, from the compute stream (``XLA Ops``) and
  the asynchronous one (``Async XLA Ops``), each with the category the
  trace's own text of the op gives it: its opcode, and a fusion's kind.
  Three classes: ``matmul`` (a convolution or dot, or an output fusion,
  which XLA roots at one), ``collective`` and ``other``. An op that
  encloses other traced ops (a while loop) is not a leaf: classes count
  leaves;
- the harness's own host spans (``chipbench.*``, written with
  ``jax.profiler.TraceAnnotation``), on the same clock;
- the busy union of each chip over the traced window, the idle gaps, and
  the host span the host was in during each gap.

Everything is in seconds from the start of the trace.
"""
from __future__ import annotations

import glob
import gzip
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
SYNC_LINE = "XLA Ops"          # the compute stream: busy time
ASYNC_LINE = "Async XLA Ops"   # DMAs and asynchronous collectives
_COLLECTIVE = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast", "send", "recv")
_MATMUL = ("convolution", "dot")
# "%name = <shape> opcode(operands), kind=kLoop, ..."
_OPCODE = re.compile(r"[\]\})] ([a-z][a-z0-9-]*)\(")
_KIND = re.compile(r"kind=(k[A-Za-z]+)")


@dataclass
class Op:
    start: float
    end: float
    name: str            # the op's HLO name, e.g. "fusion.673"
    category: str        # opcode, or "fusion:<kind>" for a fusion
    is_async: bool = False
    leaf: bool = True    # False for a loop or call whose body ops are traced

    @property
    def kind(self) -> str:
        return classify(self.category)


@dataclass
class Trace:
    ops: Dict[int, List[Op]] = field(default_factory=dict)   # chip -> ops
    spans: List[Tuple[float, float, str]] = field(default_factory=list)
    window: Optional[Tuple[float, float]] = None

    def chips(self) -> List[int]:
        return sorted(self.ops)


def parse_op(text: str) -> Tuple[str, str]:
    """``(name, category)`` from the trace's text of one HLO instruction.
    The category is the opcode; a fusion's is ``fusion:<kind>``: XLA roots
    an output fusion (``kOutput``) at a convolution or dot."""
    if " = " not in text:
        return text.lstrip("%"), text
    name, rest = text.split(" = ", 1)
    m = _OPCODE.search(rest)
    opcode = m.group(1) if m else rest.split("(", 1)[0].split()[-1]
    if opcode == "fusion":
        k = _KIND.search(rest)
        opcode = f"fusion:{k.group(1) if k else '?'}"
    return name.strip().lstrip("%"), opcode


def classify(category: str) -> str:
    c = category.lower()
    if any(c.startswith(k) for k in _COLLECTIVE):
        return "collective"
    if c in _MATMUL or c == "fusion:koutput":
        return "matmul"
    return "other"


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def _stats(obj) -> Dict[str, object]:
    try:
        return {k: v for k, v in obj.stats}
    except Exception:
        return {}


def _device_index(plane_name: str) -> Optional[int]:
    # "/device:TPU:3" -> 3; other accelerators' planes are named alike
    if not plane_name.startswith("/device:"):
        return None
    tail = plane_name.rsplit(":", 1)[-1]
    return int(tail) if tail.isdigit() else None


def _mark_parents(ops: List[Op]) -> None:
    """A synchronous op that encloses the next one (a while loop around its
    body's ops) is not a leaf: only leaves are counted by class."""
    sync = [o for o in ops if not o.is_async]
    for a, b in zip(sync, sync[1:]):
        if b.start < a.end and b.end <= a.end + 1e-12:
            a.leaf = False


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` (gzipped or not, or the newest under a
    directory)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        chip = _device_index(plane.name)
        for line in plane.lines:
            if chip is not None and line.name in (SYNC_LINE, ASYNC_LINE):
                ops = tr.ops.setdefault(chip, [])
                for ev in line.events:
                    name, cat = parse_op(ev.name)
                    s = ev.start_ns * 1e-9
                    ops.append(Op(s, s + ev.duration_ns * 1e-9, name, cat,
                                  is_async=line.name == ASYNC_LINE))
            elif chip is None and plane.name.startswith("/host:"):
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns * 1e-9
                        tr.spans.append((s, s + ev.duration_ns * 1e-9,
                                         ev.name))
    for s, e, n in tr.spans:
        if n == WINDOW_SPAN:
            tr.window = (s, e)
    for ops in tr.ops.values():
        ops.sort(key=lambda o: (o.start, -o.end))
        _mark_parents(ops)
    tr.spans.sort()
    return tr


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, window) -> List[Tuple[float, float]]:
    a, b = window
    return [(max(s, a), min(e, b)) for s, e in intervals if e > a and s < b]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(intervals, minus) -> List[Tuple[float, float]]:
    """``intervals`` less the union ``minus`` (both unions, sorted)."""
    out, j = [], 0
    minus = list(minus)
    for s, e in intervals:
        cur = s
        while j < len(minus) and minus[j][1] <= cur:
            j += 1
        k = j
        while k < len(minus) and minus[k][0] < e:
            ms, me = minus[k]
            if ms > cur:
                out.append((cur, ms))
            cur = max(cur, me)
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# ---------------------------------------------------------------------------
# what the metrics read
# ---------------------------------------------------------------------------


def window_of(tr: Trace) -> Tuple[float, float]:
    if tr.window is not None:
        return tr.window
    starts = [o.start for ops in tr.ops.values() for o in ops]
    ends = [o.end for ops in tr.ops.values() for o in ops]
    return (min(starts), max(ends))


def _sync(tr: Trace, chip: int) -> List[Op]:
    return [o for o in tr.ops[chip] if not o.is_async]


def busy(tr: Trace, chip: int) -> float:
    """Seconds of the window in which a compute-stream op runs."""
    w = window_of(tr)
    return length(clip(union((o.start, o.end) for o in _sync(tr, chip)), w))


def is_permute(op: Op) -> bool:
    return op.category.startswith("collective-permute")


def seconds(tr: Trace, chip: int, pick) -> float:
    """Device time of the leaf ops ``pick`` selects, inside the window:
    the union of their spans, so that an asynchronous op and the wait for
    it (a collective's start on one line, its done on the other) count
    once."""
    return length(union(clip([(o.start, o.end) for o in tr.ops[chip]
                              if o.leaf and pick(o)], window_of(tr))))


def kind_seconds(tr: Trace, chip: int, kind: str) -> float:
    """Compute-stream time of the leaf ops of one class."""
    return seconds(tr, chip, lambda o: not o.is_async and o.kind == kind)


def exposed(tr: Trace, chip: int, pick) -> float:
    """Time of the ops ``pick`` selects during which no other op runs on
    the chip."""
    w = window_of(tr)
    mine = union(clip([(o.start, o.end) for o in tr.ops[chip]
                       if o.leaf and pick(o)], w))
    rest = union(clip([(o.start, o.end) for o in _sync(tr, chip)
                       if o.leaf and not pick(o)], w))
    return length(subtract(mine, rest))


def idle_gaps(tr: Trace, chip: int) -> List[Tuple[float, float]]:
    w = window_of(tr)
    b = union(clip([(o.start, o.end) for o in _sync(tr, chip)], w))
    return subtract([w], b)


def host_span_at(tr: Trace, gap: Tuple[float, float]) -> str:
    """The innermost harness span (not the window itself) that overlaps
    the gap most; ``"none"`` when the host was in none."""
    best, best_len = "none", 0.0
    for s, e, n in tr.spans:
        if n == WINDOW_SPAN:
            continue
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best_len:
            best, best_len = n, ov
    return best


def top_ops(tr: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """Leaf compute-stream ops by device time inside the window, averaged
    over the chips."""
    w = window_of(tr)
    tot: Dict[str, float] = {}
    for chip in tr.chips():
        for o in _sync(tr, chip):
            if not o.leaf:
                continue
            d = min(o.end, w[1]) - max(o.start, w[0])
            if d > 0:
                key = f"{o.name} [{o.category}]"
                tot[key] = tot.get(key, 0.0) + d
    k = max(len(tr.ops), 1)
    return sorted(((name, t / k) for name, t in tot.items()),
                  key=lambda x: -x[1])[:n]


def top_gaps(tr: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """The longest idle gaps over all chips, each named by the host span
    the host was in."""
    out = []
    for chip in tr.chips():
        for g in idle_gaps(tr, chip):
            out.append((f"chip{chip} {host_span_at(tr, g)}", g[1] - g[0]))
    return sorted(out, key=lambda x: -x[1])[:n]
