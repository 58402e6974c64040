"""The compiled step's argument and temporary bytes on one chip, from
``compiled.memory_analysis()``, in GB (1e9 bytes)."""


def read(run):
    if run.compiled_bytes is None:
        return None
    return run.compiled_bytes / 1e9
