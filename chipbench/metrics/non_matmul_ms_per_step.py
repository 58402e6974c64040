"""Device time per step of every op that is neither a matrix product nor
a collective (update lane, plane unpack, norms, softmax, loss), at the
chip where it is largest, in ms."""
from chipbench import trace as T


def read(run):
    tr = run.trace
    if tr is None or not tr.ops or not run.steps:
        return None
    return 1e3 * max(T.kind_seconds(tr, c, "other")
                     for c in tr.chips()) / run.steps
