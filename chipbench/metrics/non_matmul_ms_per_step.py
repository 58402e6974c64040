"""Device time per step of every op that is neither a matrix product, a
collective nor a kernel of ``chipbench/kernels`` (update lane, plane
unpack, norms, softmax, loss), at the chip where it is largest, in ms."""
from chipbench import kernels
from chipbench import trace as T


def read(run):
    tr = run.trace
    if tr is None or not tr.ops or not run.steps:
        return None
    ks = kernels.traced(tr).values()
    return 1e3 * max(
        T.seconds(tr, c, lambda o: not o.is_async and o.kind == "other"
                  and not any(kernels.is_op(o, k) for k in ks))
        for c in tr.chips()) / run.steps
