"""Share of the traced window in which no operation runs on the device,
at the chip that idles most."""
from chipbench import trace as T


def read(run):
    tr = run.trace
    if tr is None or not tr.ops:
        return None
    a, b = T.window_of(tr)
    return max(100.0 * (1.0 - T.busy(tr, c) / (b - a)) for c in tr.chips())
