"""Device time of the attention core (the ``attention`` scope: the splash
kernel's custom calls, or the jnp flash), across forward, backward and
recompute. In ms per step, at the chip where it is largest, as
``chipbench/stages.py`` reads the window's trace by the program's scopes."""


def read(run):
    return run.stages.get("attention_ms_per_step")
