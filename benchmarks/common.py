"""Shared benchmark helpers: CSV emission, sim-clock based TTC/TTA."""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

RESULTS: List[str] = []


def emit(name: str, us_per_call: float, derived: str = ""):
    line = f"{name},{us_per_call:.1f},{derived}"
    RESULTS.append(line)
    print(line, flush=True)


def section(title: str):
    print(f"\n# === {title} ===", flush=True)


def dump_json(tag: str, prefix=None, out_dir: Optional[str] = None) -> str:
    """Write the emitted CSV lines as ``BENCH_<tag>.json`` — the artifact
    the nightly CI job uploads so the perf trajectory is tracked per run.

    ``prefix`` (a string or tuple of strings) restricts the dump to those
    metric-name prefixes (modules share the RESULTS buffer when driven by
    benchmarks.run)."""
    import json
    import os
    out_dir = out_dir or os.path.join(os.path.dirname(__file__), "results")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_{tag}.json")
    rows = {}
    for line in RESULTS:
        name, us, derived = line.split(",", 2)
        if prefix and not name.startswith(prefix):
            continue
        rows[name] = {"us_per_call": float(us), "derived": derived}
    with open(path, "w") as f:
        json.dump(rows, f, indent=1, sort_keys=True)
    print(f"# wrote {path} ({len(rows)} entries)", flush=True)
    return path


def ensure_host_devices(n: int) -> None:
    """Make sure jax will fake >= ``n`` host CPU devices. Must run BEFORE
    jax initializes (the prod-backend benchmarks call it from their
    __main__ guards). Appends the XLA flag if absent; if the environment
    already pins a SMALLER count, raises the count to ``n`` (and says so)
    rather than letting the backend fail with a device-count error. The
    flag applies to the CPU platform only: a CPU run selects it with
    ``JAX_PLATFORMS=cpu``; the platform is never chosen here."""
    import os
    import re
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        flags = (flags + f" --xla_force_host_platform_device_count={n}")
    elif int(m.group(1)) < n:
        print(f"# raising xla_force_host_platform_device_count "
              f"{m.group(1)} -> {n} (needed for M={n} workers)", flush=True)
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                       f"--xla_force_host_platform_device_count={n}", flags)
    os.environ["XLA_FLAGS"] = flags.strip()


def time_to_target(values: np.ndarray, per_step_time: float, target: float,
                   mode: str = "below") -> Optional[float]:
    """First wall-clock time at which the metric crosses the target."""
    ok = values < target if mode == "below" else values > target
    idx = np.argmax(ok)
    if not ok.any():
        return None
    return float((idx + 1) * per_step_time)
