"""Device time of the router (the ``router`` scope: the scores over every
expert, the biased top-k, the gates, the balance loss, and the sort and
grouping of the assignments to the held experts), across forward, backward
and recompute. In ms per step, at the chip where it is largest, read from
the window's trace by the program's scopes (``chipbench/stages.py``).
``None`` where the trace holds no op under the scope."""
from chipbench import stages


def read(run):
    tr = run.trace
    if tr is None or not tr.ops or not run.steps:
        return None
    t = max(stages.cross_cut(tr, c, "router") for c in tr.chips())
    return 1e3 * t / run.steps if t > 0 else None
