"""Aggregate the dry-run roofline JSONs into the §Roofline table.

Reads benchmarks/results/dryrun/*.json (produced by
``python -m repro.launch.dryrun``) and prints a markdown table plus CSV
lines: per (arch × shape × mesh): the three roofline terms, the dominant
bottleneck, MODEL_FLOPS/HLO ratio and the memory estimate.
"""
from __future__ import annotations

import glob
import json
import os

from benchmarks.common import emit, section

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results", "dryrun")

SHAPE_ORDER = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]
ARCH_ORDER = [
    "jamba-v0.1-52b", "qwen2-vl-2b", "mamba2-780m", "mixtral-8x7b",
    "granite-8b", "qwen3-moe-30b-a3b", "yi-34b", "stablelm-1.6b",
    "moonlight-16b-a3b", "whisper-large-v3",
]


def load():
    out = {}
    for path in glob.glob(os.path.join(RESULTS_DIR, "*.json")):
        with open(path) as f:
            d = json.load(f)
        stem = os.path.basename(path)[:-5]
        base = f"{d['arch']}_{d['shape']}_{d['mesh']}_{d['algo']}"
        variant = stem[len(base):].lstrip("_") or "base"
        out[(d["arch"], d["shape"], d["mesh"], d["algo"], variant)] = d
    return out


def fmt_ms(s):
    return f"{s * 1e3:.2f}"


def tuned_schedule():
    """Surface the autotuner's pick next to the analytic table: when the
    nightly ``BENCH_autotune_record.json`` artifact exists, print the
    measured best schedule and how far it sits from the hand-picked
    default (see benchmarks/autotune.py and DESIGN.md §16)."""
    path = os.path.join(os.path.dirname(__file__), "results",
                        "BENCH_autotune_record.json")
    if not os.path.exists(path):
        return None
    from repro.launch.tuner import load_tuning
    rec = load_tuning(path)
    if rec is None:
        return None
    section("Tuned schedule (from autotune artifact)")
    print(f"# key={rec.key}")
    print(f"# best={rec.best['label']} score={rec.score:.4f} "
          f"over {len(rec.table)} candidates")
    emit("roofline.tuned_schedule", rec.table[0]["step_time_s"] * 1e6,
         f"label={rec.best['label']};score={rec.score:.4f};"
         f"candidates={len(rec.table)}")
    return rec


def main(quick=False):
    section("Roofline table (from dry-run artifacts)")
    tuned_schedule()
    data = load()
    if not data:
        print("# no dry-run results yet — run: "
              "PYTHONPATH=src python -m repro.launch.dryrun --all")
        return {}
    print("| arch | shape | mesh | algo | t_comp ms | t_mem ms | t_coll ms |"
          " dominant | useful | HBM GB | notes |")
    print("|---|---|---|---|---|---|---|---|---|---|---|")
    rows = sorted(data.items(), key=lambda kv: (
        ARCH_ORDER.index(kv[0][0]) if kv[0][0] in ARCH_ORDER else 99,
        SHAPE_ORDER.index(kv[0][1]) if kv[0][1] in SHAPE_ORDER else 99,
        kv[0][2]))
    for (arch, shape, mesh, algo, variant), d in rows:
        hbm = d["memory"].get("peak_hbm_corrected", 0) / 1e9
        label = algo if variant == "base" else f"{algo}+{variant}"
        print(f"| {arch} | {shape} | {mesh} | {label} | "
              f"{fmt_ms(d['t_compute'])} | {fmt_ms(d['t_memory'])} | "
              f"{fmt_ms(d['t_collective'])} | {d['dominant']} | "
              f"{d['useful_ratio']:.2f} | {hbm:.1f} | {d['notes']} |")
        emit(f"roofline.{arch}.{shape}.{mesh}.{algo}.{variant}",
             d["t_compute"] * 1e6,
             f"dom={d['dominant']};useful={d['useful_ratio']:.2f}")
    return data


if __name__ == "__main__":
    main()
