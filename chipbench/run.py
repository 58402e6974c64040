#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json`` at the root of the repo) names a
configuration (``chipbench/configs/<config>.json``) and a training job
(``chipbench/traffic/<traffic>.json``). The run builds the system under
test's step, makes its weights on the device from the seed, drives the
step through its first checked steps, then trains for ``--seconds``,
dispatching ahead and blocking only to keep ``IN_FLIGHT`` steps queued.
After the window it reads the chips' peak memory, frees the program's
state, and runs the plain reference over the checked steps: ``correct``
is that comparison (``chipbench/check.py``).

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window is traced and the metrics are the per-layer
ones, each read by ``chipbench/metrics/<name>.py`` from the window's trace
with the program's scopes (``chipbench/stages.py``).

The last line of stdout is one JSON object. Without a TPU, or with fewer
chips than the cell asks for, the run prints no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import collections
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# the repo root, not this directory, leads the path: chipbench.trace must
# not shadow the standard library's trace module
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

from chipbench import cells, check  # noqa: E402
from chipbench.traffic import generator  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
# steps dispatched ahead of the one the window waits for
IN_FLIGHT = 2


def log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def device_gate(chips: int) -> dict:
    """The device the run is reported on. Refuses (exit 2, nothing on
    stdout) unless JAX's first device is a TPU and at least ``chips`` of
    them are present."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"no TPU (jax found {devs[0].platform!r}); refusing to run")
        raise SystemExit(2)
    if len(devs) < chips:
        log(f"the cell needs {chips} chips, jax found {len(devs)}")
        raise SystemExit(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def peak_for(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if kind not in peaks:
        log(f"no peaks for device_kind {kind!r} in peaks.json")
        raise SystemExit(2)
    return peaks[kind]


def enable_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout;
    the program's own cache helper reads the same variable."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def load_reader(name: str):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def checked_steps(prog, job, V: int, seed: int, n: int) -> dict:
    """The program's first ``n`` steps through the window's own call and
    feed: each loss, the optimizer's momentum norms after the first step
    that applies a gradient, the norms of the parameters' change, and the
    version clocks."""
    first = cells.first_applied_step(job)
    readings = {"losses": []}
    for t in range(n):
        b = prog.put_batch(generator.batch(job, V, seed, t))
        readings["losses"].append(float(prog.step(b, t)))
        if t == first:
            readings["m_norms"] = prog.opt_norms()
    readings.update(prog.param_change_norms())
    readings["versions"] = prog.versions()
    return readings


def reference(ref, cdict, job, seed: int, n: int, devices, **kw) -> dict:
    """The plain reference over the same ``n`` steps from the same seed."""
    import jax
    key = jax.random.PRNGKey(cells.seed_key(seed))
    V = int(cdict["vocab_size"])
    batches = [generator.batch(job, V, seed, s) for s in range(n)]
    return ref.train(cdict, job, key, batches, devices, **kw)


def run(args, *, base: str = HERE, bench_path=None, gate=device_gate,
        build_step=cells.build_step, program=cells.Program,
        cache: bool = True, keep_trace=None) -> dict:
    bench = cells.load_benchmark(bench_path)
    cell = cells.find_cell(bench, args.workload)
    job = cells.load_traffic(cell["traffic"], base)
    cdict = cells.config_dict(cell["config"], base)
    limits = cells.load_limits(cell["name"], base)
    ref = cells.family_module("reference", cdict)
    ref.check_job(job)
    chips = int(cell["chips"])
    if int(job["workers"]) != chips or math.prod(job["mesh"]) != chips:
        raise SystemExit(f"chipbench: job {cell['traffic']} has "
                         f"{job['workers']} workers on mesh {job['mesh']}, "
                         f"the cell {chips} chips")
    device = gate(chips)
    peaks = peak_for(device["kind"])
    if cache:
        enable_cache()
    import jax

    flops = cells.family_module("flops", cdict)
    cfg = cells.load_config(cell["config"], base)
    devices = jax.devices()[:chips]
    mesh = jax.make_mesh(tuple(job["mesh"]), ("data", "model"),
                         devices=devices)
    V = int(cdict["vocab_size"])

    # -- set-up: compile, weights from the seed, the checked steps --------
    prog = program(cfg, job, mesh, build_step(cfg, job, mesh), args.seed)
    n_check = check.CHECK_STEPS
    readings = checked_steps(prog, job, V, args.seed, n_check)
    prog.wait()
    # what set-up left is collected now and frozen out of later
    # collections, so that no full collection of it falls in the window
    t_gc = time.perf_counter()
    gc.collect()
    gc.freeze()
    t_gc = time.perf_counter() - t_gc
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f} s (collection {t_gc:.3f} s, "
        f"{gc.get_freeze_count()} objects frozen), checked losses "
        f"{readings['losses']}")

    # -- the window -------------------------------------------------------
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") \
        if args.trace else None
    from jax.profiler import TraceAnnotation
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # no Python call events: spans only
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    queue = collections.deque()
    losses = []
    t = n_check
    longest = 0.0   # the longest time between two completed steps
    with TraceAnnotation("chipbench.window"):
        t0, last = time.perf_counter(), None
        while True:
            with TraceAnnotation("chipbench.batch"):
                b = prog.put_batch(generator.batch(job, V, args.seed, t))
            with TraceAnnotation("chipbench.dispatch"):
                queue.append(prog.step(b, t))
            t += 1
            if len(queue) > IN_FLIGHT:
                with TraceAnnotation("chipbench.wait"):
                    losses.append(float(queue.popleft()))
                now = time.perf_counter()
                if last is not None:
                    longest = max(longest, now - last)
                last = now
            if time.perf_counter() - t0 >= args.seconds:
                break
        with TraceAnnotation("chipbench.drain"):
            while queue:
                losses.append(float(queue.popleft()))
            prog.wait()
        window_s = time.perf_counter() - t0
    if trace_dir:
        jax.profiler.stop_trace()
    steps = t - n_check
    failed = sum(1 for x in losses if not math.isfinite(x))

    # -- memory, then free the program before the reference ---------------
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    compiled_bytes = prog.compiled_bytes()
    flops_step = flops.step_flops(cdict, job)

    metrics = {}
    breakdown = None
    dev = dict(device, memory_peak_bytes=int(peak))
    if args.trace:
        from chipbench import stages
        from chipbench import trace as T
        xplane = T.find_xplane(trace_dir)
        tr = stages.load(xplane)
        if keep_trace:
            shutil.copy(xplane, keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        a, z = T.window_of(tr)
        dev["busy_s"] = (sum(T.busy(tr, c) for c in tr.chips())
                         / max(len(tr.chips()), 1))
        dev["window_s"] = z - a
        # what the per-layer readers read
        rd = types.SimpleNamespace(
            trace=tr, stages=stages.per_step(tr, steps), steps=steps,
            window_s=window_s, chips=chips, peak=peaks,
            flops_per_step=flops_step,
            flops_by_part=flops.step_flops_by_part(cdict, job),
            bytes_by_part=flops.step_bytes_by_part(cdict, job),
            compiled_bytes=compiled_bytes, job=job, config=cdict)
        for m in bench["per_layer"]:
            if cell["name"] not in m.get("workloads", [cell["name"]]):
                continue
            v = load_reader(m["name"])(rd)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": T.top_ops(tr), "idle_gaps": T.top_gaps(tr)}
    else:
        e2e = {
            "trained_tokens_per_s_per_chip":
                flops.trained_tokens_per_step(job) * steps / window_s / chips,
            "peak_hbm_gb": max(peak, compiled_bytes or 0) / 1e9,
            "setup_s": setup_s,
        }
        for m in bench["end_to_end"]:
            if cell["name"] in m.get("workloads", [cell["name"]]):
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    log(f"window {window_s:.3f} s, {steps} steps, longest step interval "
        f"{longest:.3f} s, peak_bytes_in_use {peak}, "
        f"compiled argument+temp bytes {compiled_bytes}")
    prog.free()
    del prog
    gc.unfreeze()
    gc.collect()

    # -- the reference over the checked steps -----------------------------
    want = reference(ref, cdict, job, args.seed, n_check, devices)
    numbers = check.gaps(readings, want)
    correct = check.verdict(numbers, limits) and failed == 0
    lines = check.report(numbers, limits)
    log(f"reference losses {want['losses']}")
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    out = {"correct": bool(correct), "attempted": steps, "failed": failed,
           "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checked"] = {k: {"value": numbers[k], "limit": limits[k]}
                      for k in check.compared(limits)}
    return out


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    out = run(parse(argv))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
