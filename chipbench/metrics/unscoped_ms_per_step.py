"""Device time of the leaf ops in no stage: XLA's own copies, broadcasts and
allocations, and ops hoisted out of every scope. In ms per step, at the chip
where it is largest, as ``chipbench/stages.py`` reads the window's trace by
the program's scopes."""


def read(run):
    return run.stages.get("unscoped_ms_per_step")
