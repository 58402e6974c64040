"""Device time of the forward stage: the ops under a ``fwd<r>`` scope and not
under ``transpose(``, nor in a stage that takes precedence. In ms per step,
at the chip where it is largest, as ``chipbench/stages.py`` reads the
window's trace by the program's scopes."""


def read(run):
    return run.stages.get("fwd_ms_per_step")
