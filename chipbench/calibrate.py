#!/usr/bin/env python3
"""Readings that the limits of a cell's comparison are set from.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1 2 3 ... \
        [--out chipbench_runs/calibrate_<cell>.json]

For each seed, in one process that compiles the step once: the program's
checked steps against the plain reference (the sound readings, whose
largest sets the lower end of each limit); the reference in the next
precision below the configuration's in the program's place (the control,
which has to fail); and the faults a training cell can have: half of
each worker's rows left out and, with more than one worker, the gossip
exchange left out, planted in the reference put in the program's place;
and, for LayUp, a read plane that does not adopt the write plane
(``StaleReadProgram``), planted in the program. A state left unchanged
reads 1 by construction and needs no run. ``--parts`` reads a subset.

The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

from chipbench import cells, check, run as R  # noqa: E402


class StaleReadProgram(cells.Program):
    """LayUp's read plane left a step behind: after each step it keeps the
    plane that step read, instead of adopting the updated write plane."""

    def step(self, batch, t):
        import jax
        import jax.numpy as jnp
        keep = jax.tree.map(jnp.copy, self.state["read"])
        loss = super().step(batch, t)
        self.state["read"] = keep
        return loss


PROGRAMS = {"program": cells.Program, "stale_read": StaleReadProgram}
IN_REFERENCE = {"half_batch": dict(half_batch=True),
                "no_exchange": dict(no_exchange=True)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out")
    ap.add_argument("--parts", nargs="+",
                    choices=sorted(PROGRAMS) + sorted(IN_REFERENCE)
                    + ["control"],
                    help="what to read (default: all the cell can have)")
    args = ap.parse_args(argv)

    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, args.workload)
    job = cells.load_traffic(cell["traffic"])
    cdict = cells.config_dict(cell["config"])
    chips = int(cell["chips"])
    R.device_gate(chips)
    R.enable_cache()
    import jax
    ref = cells.family_module("reference", cdict)
    cfg = cells.load_config(cell["config"])
    devices = jax.devices()[:chips]
    mesh = jax.make_mesh(tuple(job["mesh"]), ("data", "model"),
                         devices=devices)
    V = int(cdict["vocab_size"])
    n = check.CHECK_STEPS
    layup = job["step"]["algo"] == "layup"
    parts = args.parts or (["program", "control", "half_batch"]
                           + (["no_exchange"] if chips > 1 else [])
                           + (["stale_read"] if layup else []))
    built = cells.build_step(cfg, job, mesh)
    engine = hasattr(built, "init_state")
    if not engine:   # compiled once, for every seed's program
        built = built.lower().compile()

    rows = []
    for seed in args.seeds:
        got = {}
        for part in [p for p in parts if p in PROGRAMS]:
            prog = PROGRAMS[part](cfg, job, mesh, cells.build_step(
                cfg, job, mesh) if engine else built, seed)
            got[part] = R.checked_steps(prog, job, V, seed, n)
            prog.free()
            del prog
            gc.collect()
        want = R.reference(ref, cdict, job, seed, n, devices)
        row = {"seed": seed}
        row.update({part: check.gaps(g, want) for part, g in got.items()})
        if "control" in parts:
            ctl = R.reference(ref, cdict, job, seed, n, devices,
                              p=ref.CONTROLS[cdict["dtype"]])
            row["control"] = check.gaps(ctl, want)
        for part in [p for p in parts if p in IN_REFERENCE]:
            bad = R.reference(ref, cdict, job, seed, n, devices,
                              **IN_REFERENCE[part])
            row[part] = check.gaps(bad, want)
        print(json.dumps(row), flush=True)
        rows.append(row)
    summary = {}
    for part in [p for p in rows[0] if p != "seed"]:
        pick = max if part == "program" else min
        for k in rows[0][part]:
            summary.setdefault(k, {})[f"{part}_{pick.__name__}"] = pick(
                r[part][k] for r in rows)
    print(json.dumps({"workload": args.workload, "summary": summary}),
          flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rows": rows,
                       "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
