"""Device time of the backward stage: the ops under a ``fwd<r>`` scope and a
``transpose(``, remat's pullback (``transpose(recompute)``) among them. In
ms per step, at the chip where it is largest, as ``chipbench/stages.py``
reads the window's trace by the program's scopes."""


def read(run):
    return run.stages.get("bwd_ms_per_step")
