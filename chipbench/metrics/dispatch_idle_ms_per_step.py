"""Compute-stream idle time while the host is inside one of the program's
``repro.*`` spans: the pipeline engine's stage calls, backpressure waits and
fence polls. In ms per step, at the chip where it is largest, as
``chipbench/stages.py`` reads the window's trace by the program's scopes.
Nothing to read where the program writes no such span: a step that compiles
whole dispatches once."""


def read(run):
    return run.stages.get("dispatch_idle_ms_per_step")
