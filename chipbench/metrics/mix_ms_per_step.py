"""Device time of the gossip mix's non-collective ops (``mix.<buffer>``,
``mix.weight``). In ms per step, at the chip where it is largest, as
``chipbench/stages.py`` reads the window's trace by the program's scopes.
Nothing to read where the step does not mix: one worker."""


def read(run):
    return run.stages.get("mix_ms_per_step")
