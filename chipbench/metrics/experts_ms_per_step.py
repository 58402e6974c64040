"""Device time of the routed experts (the ``experts`` scope: the permute of
the T·k assignments into expert order and the weighted combine; and the
held experts' grouped products, XLA's ragged dot, whose custom calls
``ragged-dot-*`` carry none of the program's scopes and are found by
name), across forward, backward and recompute. In ms per step, at the chip
where it is largest, read from the window's trace (``chipbench/stages.py``).
``None`` where the trace holds none of these ops."""
from chipbench import stages
from chipbench import trace as T

PRODUCTS = "ragged-dot"


def _is_experts(op) -> bool:
    return not op.is_async and op.kind != "collective" and (
        op.name.startswith(PRODUCTS) or stages.in_scope(op, "experts"))


def read(run):
    tr = run.trace
    if tr is None or not tr.ops or not run.steps:
        return None
    t = max(T.seconds(tr, c, _is_experts) for c in tr.chips())
    return 1e3 * t / run.steps if t > 0 else None
