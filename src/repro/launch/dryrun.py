import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape) on the production
mesh, print memory/cost analysis, and extract roofline terms.

The two lines above MUST stay first: jax locks the device count on first
init, and only the dry-run should see 512 host devices (smoke tests and
benches see 1).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--algo layup]
  PYTHONPATH=src python -m repro.launch.dryrun --all --shapes train_4k,prefill_32k

Results are cached as JSON under benchmarks/results/dryrun/ for
benchmarks/roofline.py to aggregate.
"""
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import INPUT_SHAPES, get_config, list_configs
from repro.launch import analysis as AN
from repro.launch.mesh import make_production_mesh, num_workers
from repro.launch.train import make_step
from repro.models import build_model

ASSIGNED = [
    "jamba-v0.1-52b", "qwen2-vl-2b", "mamba2-780m", "mixtral-8x7b",
    "granite-8b", "qwen3-moe-30b-a3b", "yi-34b", "stablelm-1.6b",
    "moonlight-16b-a3b", "whisper-large-v3",
]

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "../../../benchmarks/results/dryrun")


def effective_config(cfg, shape):
    """long_500k requires sub-quadratic attention: SSM is native; archs with
    a sliding window are native; everything else gets the SWA variant
    (window 4096) — recorded in the report notes (DESIGN.md §5)."""
    notes = ""
    if shape.name == "long_500k" and cfg.family != "ssm":
        if cfg.sliding_window == 0:
            cfg = cfg.with_(sliding_window=4096)
            notes = "SWA-variant(4096) for long_500k"
    return cfg, notes


def _compile_step(cfg, mesh, shape, algo, shifts, overrides, preset=None,
                  accum_steps=1, act_pspec=None, moe_groups=1,
                  constrain_grads=False, fb_ratio=1, update_delay=0):
    import repro.models.transformer as T
    import repro.models.moe as MOE
    from jax.sharding import NamedSharding, PartitionSpec as P
    model = build_model(cfg)
    if act_pspec is not None:
        if shape.kind == "train":  # traced inside shard_map: raw spec
            T.ACTIVATION_PSPEC = P(*act_pspec)
        else:  # pjit serve paths need an explicit NamedSharding
            T.ACTIVATION_PSPEC = NamedSharding(mesh, P(*act_pspec))
    if moe_groups > 1:
        eaxis = "expert" if "expert" in mesh.axis_names else "model"
        MOE.GROUPS = moe_groups
        if shape.kind == "train":  # traced inside shard_map: raw specs
            MOE.GROUP_PSPEC = P(eaxis, None, None)
            MOE.EXPERT_PSPEC = P(eaxis, None, None)
        else:  # pjit serve paths need explicit NamedShardings
            MOE.GROUP_PSPEC = NamedSharding(mesh, P(eaxis, None, None))
            MOE.EXPERT_PSPEC = NamedSharding(mesh, P(eaxis, None, None))
    try:
        step = make_step(model, mesh, shape, algo=algo, shifts=shifts,
                         overrides=overrides, preset=preset,
                         accum_steps=accum_steps,
                         constrain_grads=constrain_grads,
                         fb_ratio=fb_ratio, update_delay=update_delay)
        return step.lower().compile()
    finally:
        T.ACTIVATION_PSPEC = None
        MOE.GROUPS = 1
        MOE.GROUP_PSPEC = MOE.EXPERT_PSPEC = None


def run_one(arch: str, shape_name: str, *, algo: str = "layup",
            multi_pod: bool = False, shifts=(1,), overrides=None,
            save: bool = True, verbose: bool = True, tag_suffix: str = "",
            layout: str = "2d", preset=None, accum_steps: int = 1,
            act_pspec=None, moe_groups: int = 1, constrain_grads=False,
            fb_ratio: int = 1, update_delay: int = 0):
    shape = INPUT_SHAPES[shape_name]
    cfg0 = get_config(arch)
    cfg, notes = effective_config(cfg0, shape)
    mesh = make_production_mesh(multi_pod=multi_pod, layout=layout)
    mesh_desc = "x".join(str(mesh.shape[a]) for a in mesh.axis_names)
    if layout != "2d":
        notes = (notes + "; " if notes else "") + f"mesh layout={layout}"
    if preset:
        notes += f"; preset={preset}"
    if accum_steps > 1:
        notes += f"; accum={accum_steps}"
    if moe_groups > 1:
        notes += f"; moe_groups={moe_groups}"
    if fb_ratio > 1 or update_delay > 0:
        notes += f"; decoupled R={fb_ratio} D={update_delay}"

    # --- lower + compile: the dry-run proof ---------------------------------
    t0 = time.time()
    compiled = _compile_step(cfg, mesh, shape, algo, shifts, overrides,
                             preset, accum_steps, act_pspec, moe_groups,
                             constrain_grads, fb_ratio, update_delay)
    t_full = time.time() - t0

    from repro.models.transformer import _superblock_period
    n_super = ((cfg.num_layers - cfg.first_k_dense_replace)
               // _superblock_period(cfg))
    from repro.launch.mesh import num_workers as _nw
    n_workers = _nw(mesh)
    n_model = mesh.size // n_workers

    report = AN.analyze(
        compiled, cfg, shape, arch=arch,
        algo=(algo if shape.kind == "train" else shape.kind),
        mesh_desc=mesh_desc, n_model=n_model, n_workers=n_workers,
        n_devices=mesh.size, loop_trip=n_super, notes=notes)
    d = report.to_dict()
    d["compile_s"] = round(t_full, 1)

    if verbose:
        print(f"[{arch} × {shape_name} × {mesh_desc} × {d['algo']}] "
              f"compile {t_full:.0f}s  {notes}")
        print(compiled.memory_analysis())
        print(f"  corrected flops/dev={report.flops_per_device:.3e} "
              f"bytes/dev={report.bytes_per_device:.3e} "
              f"coll_wire={report.collective_wire_bytes:.3e}")
        print(f"  t_comp={report.t_compute*1e3:.2f}ms "
              f"t_mem={report.t_memory*1e3:.2f}ms "
              f"t_coll={report.t_collective*1e3:.2f}ms "
              f"dominant={report.dominant} useful={report.useful_ratio:.2f} "
              f"hbm={report.memory.get('peak_hbm_corrected', 0)/1e9:.1f}GB")

    if save:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        tag = f"{arch}_{shape_name}_{mesh_desc}_{d['algo']}" + tag_suffix
        with open(os.path.join(RESULTS_DIR, tag + ".json"), "w") as f:
            json.dump(d, f, indent=1)
    return d


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ASSIGNED + list_configs(), default=None)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES), default=None)
    ap.add_argument("--shapes", default=None,
                    help="comma-separated subset for --all")
    ap.add_argument("--algo", default="layup", choices=["layup", "ddp"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--archs", default=None)
    ap.add_argument("--shifts", default="1",
                    help="comma-separated gossip ring shifts (lax.switch set)")
    ap.add_argument("--layout", default="2d", choices=["2d", "ep"])
    ap.add_argument("--preset", default=None,
                    choices=[None, "megatron", "ep", "fsdp"])
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--fb-ratio", type=int, default=1,
                    help="decoupled lane: forward passes per backward")
    ap.add_argument("--update-delay", type=int, default=0,
                    help="decoupled lane: gradient FIFO depth D")
    ap.add_argument("--moe-groups", type=int, default=1)
    ap.add_argument("--constrain-grads", action="store_true")
    ap.add_argument("--act-pspec", default=None,
                    help="comma-separated activation PartitionSpec, "
                         "e.g. 'model,None,None' (FSDP batch sharding)")
    ap.add_argument("--tag", default="")
    ap.add_argument("--override", default=None,
                    help="comma-separated rule overrides, e.g. "
                         "'vocab=model,heads=None'")
    args = ap.parse_args()
    overrides = None
    if args.override:
        overrides = {}
        for kv in args.override.split(","):
            k, v = kv.split("=")
            if v == "None":
                overrides[k] = None
            elif "+" in v:
                overrides[k] = tuple(v.split("+"))
            else:
                overrides[k] = v
    act_pspec = None
    if args.act_pspec:
        act_pspec = tuple(None if a == "None" else a
                          for a in args.act_pspec.split(","))

    shifts = tuple(int(s) for s in args.shifts.split(","))
    failures = []
    if args.all:
        archs = args.archs.split(",") if args.archs else ASSIGNED
        shapes = (args.shapes.split(",") if args.shapes
                  else list(INPUT_SHAPES))
        for arch in archs:
            for shape in shapes:
                try:
                    run_one(arch, shape, algo=args.algo,
                            multi_pod=args.multi_pod, shifts=shifts,
                            layout=args.layout, preset=args.preset,
                            accum_steps=args.accum, act_pspec=act_pspec,
                            tag_suffix=args.tag, overrides=overrides,
                            moe_groups=args.moe_groups,
                            constrain_grads=args.constrain_grads,
                            fb_ratio=args.fb_ratio,
                            update_delay=args.update_delay)
                except Exception as e:
                    traceback.print_exc()
                    failures.append((arch, shape, repr(e)[:200]))
        if failures:
            print("FAILURES:")
            for f in failures:
                print(" ", f)
            sys.exit(1)
        print("ALL DRY-RUNS PASSED")
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        run_one(args.arch, args.shape, algo=args.algo,
                multi_pod=args.multi_pod, shifts=shifts,
                layout=args.layout, preset=args.preset,
                accum_steps=args.accum, act_pspec=act_pspec,
                tag_suffix=args.tag, overrides=overrides,
                moe_groups=args.moe_groups,
                constrain_grads=args.constrain_grads,
                fb_ratio=args.fb_ratio, update_delay=args.update_delay)


if __name__ == "__main__":
    main()
