"""Compile a cell's step for a described TPU v5e, without a chip, and print
its memory analysis at each depth given.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 chipbench/tools/fit_depth.py \
        --config stablelm-1.6b --traffic layup-r2d1.m1.b2s4096 --layers 6 8 10

A depth fits one chip when the argument and temporary bytes, plus the
batch and the runtime's reservation, stay under the chip's limit.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--layers", type=int, nargs="+", required=True)
    ap.add_argument("--topology", default="v5e:2x2")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    from chipbench import cells

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    for n in args.layers:
        cfg = cells.load_config(args.config, num_layers=n)
        job = cells.load_traffic(args.traffic)
        M = job["workers"]
        mesh = Mesh(np.array(topo.devices[:M]).reshape(M, 1),
                    ("data", "model"))
        step = cells.build_step(cfg, job, mesh)
        t0 = time.perf_counter()
        compiled = step.lower().compile()
        ma = compiled.memory_analysis()
        print(f"{args.config} layers={n}: arguments="
              f"{ma.argument_size_in_bytes} temps={ma.temp_size_in_bytes} "
              f"outputs={ma.output_size_in_bytes} aliased="
              f"{ma.alias_size_in_bytes} sum(arg+temp)="
              f"{ma.argument_size_in_bytes + ma.temp_size_in_bytes} "
              f"compile {time.perf_counter() - t0:.1f} s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
