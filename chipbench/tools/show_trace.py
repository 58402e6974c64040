"""Print what a profiler trace holds: planes, lines, event counts, the
stats on the first events of each line, and device time by category.

    python3 chipbench/tools/show_trace.py <file.xplane.pb | directory>
"""
from __future__ import annotations

import collections
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT


def main(argv=None) -> int:
    from jax.profiler import ProfileData
    from chipbench import trace as T
    path = (sys.argv[1:] if argv is None else argv)[0]
    if os.path.isdir(path):
        path = T.find_xplane(path)
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name!r} stats={T._stats(plane)!r}"[:300])
        for line in plane.lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r}: {len(evs)} events")
            for ev in evs[:3]:
                print(f"    {ev.name!r} start={ev.start_ns} dur="
                      f"{ev.duration_ns} stats={T._stats(ev)!r}"[:400])
    tr = T.load(path)
    print("window", tr.window, "spans", len(tr.spans))
    for chip in tr.chips():
        cats = collections.Counter()
        for o in tr.ops[chip]:
            cats[(o.kind, o.category)] += o.end - o.start
        print(f"chip {chip}: {len(tr.ops[chip])} ops, busy "
              f"{T.busy(tr, chip):.6f} s")
        for (kind, cat), t in cats.most_common(25):
            print(f"   {kind:10s} {cat[:60]:60s} {t:.6f}")
    print("top ops", T.top_ops(tr))
    print("top gaps", T.top_gaps(tr))
    return 0


if __name__ == "__main__":
    sys.exit(main())
