"""The part of the gossip's collective-permute time during which no other
op runs on the chip, per step, at the chip where it is largest, in ms."""
from chipbench import trace as T


def read(run):
    tr = run.trace
    if tr is None or not tr.ops or not run.steps:
        return None
    if max(T.seconds(tr, c, T.is_permute) for c in tr.chips()) <= 0:
        return None
    return 1e3 * max(T.exposed(tr, c, T.is_permute)
                     for c in tr.chips()) / run.steps
