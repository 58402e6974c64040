"""Device time per step of the collective-permute ops that carry the
gossip ring hop, at the chip where it is largest, in ms. Nothing to read
where the trace holds no such op."""
from chipbench import trace as T


def read(run):
    tr = run.trace
    if tr is None or not tr.ops or not run.steps:
        return None
    t = max(T.seconds(tr, c, T.is_permute) for c in tr.chips())
    return 1e3 * t / run.steps if t > 0 else None
