"""Device time of remat's recomputed forward: the ops under ``recompute``, not
transposed. In ms per step, at the chip where it is largest, as
``chipbench/stages.py`` reads the window's trace by the program's scopes."""


def read(run):
    return run.stages.get("recompute_ms_per_step")
