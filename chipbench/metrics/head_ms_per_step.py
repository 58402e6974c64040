"""Device time of the head (the ``head`` scope: final norm, unembed, cross-
entropy), across forward and backward. In ms per step, at the chip where it
is largest, as ``chipbench/stages.py`` reads the window's trace by the
program's scopes."""


def read(run):
    return run.stages.get("head_ms_per_step")
