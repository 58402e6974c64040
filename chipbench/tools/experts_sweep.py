"""Device time of one expert layer's routed products, by kernel and tiling.

    python3 chipbench/tools/experts_sweep.py [--out <file.json>]

On one TPU chip, at Moonlight-16B-A3B's published widths (d_model 2048,
experts of width 1408, 64 routed experts, top-6, 2 shared) with this
chip's 8 held experts and one 8192-token forward slice in bf16, times the
forward and the forward + backward (``jax.grad`` of a weighted sum) of the
whole MoE layer (``repro.models.moe.moe_apply``: router, sort, permute,
products, combine) with the routed products as the program's, XLA's ragged
dot, and in their place the megablox grouped matmul (the Pallas kernel
that ships with JAX) at several tilings, and, for scale, the held experts
computed densely over every token. Then one product alone with 1/8 and
with all of its rows in held groups: whether its time follows the rows
routed to the held experts. Prints one JSON object per
line, milliseconds per call (median of 5 repeats of 10 calls, each repeat
ended by ``block_until_ready``). Refuses to run without a TPU (exit 2).
"""
from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

TOKENS = 8192
# (rows, contraction, output) tiles of the grouped matmul
TILINGS = ((512, 1024, 1024), (512, 512, 512), (256, 1024, 1024),
           (1024, 512, 1024), (128, 128, 128))


def _time(fn, *args, calls=10, repeats=5) -> float:
    import jax
    jax.block_until_ready(fn(*args))
    runs = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        runs.append((time.perf_counter() - t0) / calls)
    return 1e3 * statistics.median(runs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        print("experts_sweep: no TPU; refusing to run", file=sys.stderr)
        return 2
    from repro.configs import get_config
    from repro.models import layers as L
    from repro.models import moe as M

    cfg = get_config("moonlight-16b-a3b").with_(ep_size=8)
    key = jax.random.PRNGKey(0)
    p = L.init_params(key, M.moe_specs(cfg), cfg.dtype)
    x = jax.random.normal(jax.random.fold_in(key, 1),
                          (1, TOKENS, cfg.d_model)).astype(cfg.dtype)
    w = jax.random.normal(jax.random.fold_in(key, 2), x.shape)

    def loss(p, x):
        y, aux = M.moe_apply(p, x, cfg)
        return jnp.sum(y.astype(jnp.float32) * w) + aux["balance"]

    def dense(p, x):
        return jnp.sum(M.moe_apply_dense(p, x, cfg).astype(jnp.float32) * w)

    fwd = jax.jit(lambda p, x: M.moe_apply(p, x, cfg)[0])
    both = jax.jit(jax.grad(loss, (0, 1)))
    _, aux = jax.jit(lambda p, x: M.moe_apply(p, x, cfg))(p, x)
    rows = float(aux["held_rows"])
    # the held experts' products per call, forward (2 per multiply-add)
    flops = 3 * 2 * cfg.d_model * cfg.expert_d_ff() * rows
    out = []
    cases = [("ragged", None)] + [("gmm", t) for t in TILINGS]
    ragged = M.grouped_matmul

    def gmm(tiling):
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

        def products(x, w, sizes):
            t = (math.gcd(x.shape[0], tiling[0]), min(tiling[1], x.shape[1]),
                 min(tiling[2], w.shape[-1]))
            return megablox.gmm(x, w, sizes, x.dtype, t)
        return products

    for kernel, tiling in cases:
        M.grouped_matmul = ragged if kernel == "ragged" else gmm(tiling)
        jax.clear_caches()
        row = {"kernel": kernel, "tiling": tiling, "held_rows": rows,
               "held_products_gflop_fwd": flops / 1e9}
        try:
            row.update(fwd_ms=_time(fwd, p, x), fwd_bwd_ms=_time(both, p, x))
        except Exception as e:   # a tiling the kernel refuses
            row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        print(json.dumps(row), flush=True)
        out.append(row)
    # does a product's time follow the rows routed to the held experts?
    # one forward product over the T·k assignments with 1/8 of them held
    # (the layer's balanced share), then with all of them held
    m, H = TOKENS * cfg.experts_per_token, cfg.experts_held
    xs = jax.random.normal(key, (m, cfg.d_model)).astype(cfg.dtype)
    for kernel in ("ragged", "gmm"):
        prod = jax.jit(ragged if kernel == "ragged" else gmm(TILINGS[0]))
        for held in (m // 8, m):
            sizes = jnp.array([held // H] * H + [m - held], jnp.int32)
            row = {"kernel": kernel, "product_rows_held": held,
                   "rows": m, "ms": _time(prod, xs, p["wi_gate"], sizes)}
            print(json.dumps(row), flush=True)
            out.append(row)
    row = {"kernel": "dense over the held experts",
           "fwd_bwd_ms": _time(jax.jit(jax.grad(dense, (0, 1))), p, x)}
    print(json.dumps(row), flush=True)
    out.append(row)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
