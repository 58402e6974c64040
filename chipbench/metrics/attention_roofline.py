"""The attention core's kernel (``chipbench/kernels/attention.json``,
the splash ``splash_mha_*`` custom calls) against its roofline: the least
time the causal attention products of a step need on one chip, over the
kernel's device time per step. ``None`` where the trace holds none of its
ops (the jnp path, whose products ``matmul_roofline`` reads)."""
from chipbench import kernels


def read(run):
    return kernels.roofline(run, "attention")
