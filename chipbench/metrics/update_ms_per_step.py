"""Device time of the update stage (``update``): FIFO pop and push, non-finite
guard, optimizer and apply. In ms per step, at the chip where it is largest,
as ``chipbench/stages.py`` reads the window's trace by the program's scopes."""


def read(run):
    return run.stages.get("update_ms_per_step")
