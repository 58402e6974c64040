"""Device time of the plane's tree view, the gradients' pack, the swap, the
clocks and the step's metrics (``unpack``, ``pack``, ``clock``). In ms per
step, at the chip where it is largest, as ``chipbench/stages.py`` reads the
window's trace by the program's scopes. Nothing to read where no op carries
those scopes: DDP has no plane."""


def read(run):
    return run.stages.get("plane_ms_per_step")
