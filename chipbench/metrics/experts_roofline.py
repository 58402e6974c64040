"""The routed experts' kernel (XLA's ragged dot, which the TPU's compiler
turns into tiled custom calls ``ragged-dot-*``: the forward products, the
rows' and the weights' gradients, and their group bookkeeping) against
its roofline, at the chip where it takes the most time: the least time
the held experts' products of a step need on one chip (the larger of their
operations at the bf16 peak and their least bytes at the HBM peak,
``flops/<family>.py``'s ``experts`` part, at the balanced expectation of
the rows routed here) over the kernel's device time per step. ``None``
where the trace holds none of its ops (a family without routed experts).

The kernel is read here rather than through a file of ``chipbench/kernels``,
whose table takes only parts that the dense family's operations name."""
from chipbench import kernels

KERNEL = {"ops": "ragged-dot", "part": "experts"}


def read(run):
    tr = run.trace
    part = KERNEL["part"]
    if tr is None or not tr.ops or not run.steps \
            or part not in run.flops_by_part:
        return None
    t = max(kernels.seconds(tr, c, KERNEL) for c in tr.chips())
    if t <= 0:
        return None
    need = max(run.flops_by_part[part] / run.peak["bf16_flops_per_s"],
               run.bytes_by_part.get(part, 0.0)
               / run.peak["hbm_bytes_per_s"])
    return 100.0 * need / run.chips / (t / run.steps)
