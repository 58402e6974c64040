"""Matrix products' share of their roofline: the matmul operations a step
requires on one chip over the device time of the ops whose trace
category is a convolution or dot, per step, times the bf16 peak. The
products here are bound by operations, not bytes, so the least time is
operations over peak. The chip with the most matmul time is reported.
Remat's recomputed products and the masked half of the attention scores
run but are not required, so they lower the share. A part that a kernel
of ``chipbench/kernels`` computes, where the trace holds that kernel's
ops, is left out: the kernel is not a dot, and its own roofline reads it."""
from chipbench import kernels
from chipbench import trace as T


def read(run):
    tr = run.trace
    if tr is None or not tr.ops or not run.steps:
        return None
    t = max(T.kind_seconds(tr, c, "matmul") for c in tr.chips())
    if t <= 0:
        return None
    flops = run.flops_per_step - sum(run.flops_by_part[k["part"]]
                                     for k in kernels.traced(tr).values())
    need = flops / run.chips / run.peak["bf16_flops_per_s"]
    return 100.0 * need / (t / run.steps)
