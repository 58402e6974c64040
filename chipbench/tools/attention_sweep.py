"""Device time of the model's causal attention core, by path and block size.

    python3 chipbench/tools/attention_sweep.py [--out <file.json>]

On one TPU chip, at each cell's attention shape (B, H, S, D) and dtype,
times the forward and the forward + backward (``jax.grad`` of a weighted
sum) of the chunked jnp core (``repro.models.layers.flash_attention_jnp``,
``block_k`` 1024, as the model calls it) and of the splash kernel
(``repro.kernels.splash``) over a grid of block sizes: (query, key) blocks of
the forward with the backward's at 512, then the backward's with the
forward's at 512. Prints one JSON object per line, milliseconds per call
(median of 5 repeats of 10 calls, each repeat ended by
``block_until_ready``). Refuses to run without a TPU (exit 2).
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

# (name, (B, H, S, D), dtype) of each cell's attention core
SHAPES = (("gpt2-medium", (8, 16, 1024, 64), "float32"),
          ("stablelm-1.6b", (2, 32, 4096, 64), "bfloat16"))
SIZES = (256, 512, 1024)


def _time(fn, *args, calls=10, repeats=5) -> float:
    import jax
    jax.block_until_ready(fn(*args))
    runs = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        runs.append((time.perf_counter() - t0) / calls * 1e3)
    return statistics.median(runs)


def _fwd_and_grad(core, q, k, v, w):
    import jax
    import jax.numpy as jnp

    def loss(q, k, v):
        return jnp.sum(core(q, k, v).astype(jnp.float32) * w)

    return (_time(jax.jit(core), q, k, v),
            _time(jax.jit(jax.grad(loss, (0, 1, 2))), q, k, v))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", help="where to write the JSON lines too")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    if jax.default_backend() != "tpu":
        print("attention_sweep: no TPU", file=sys.stderr)
        return 2
    from repro.kernels import splash
    from repro.models.layers import flash_attention_jnp
    lines = []

    def emit(**row):
        row["device_kind"] = jax.devices()[0].device_kind
        lines.append(json.dumps(row))
        print(lines[-1], flush=True)

    for name, (B, H, S, D), dt in SHAPES:
        key = jax.random.PRNGKey(0)
        q, k, v, w = (jax.random.normal(jax.random.fold_in(key, i),
                                        (B, H, S, D), jnp.float32)
                      for i in range(4))
        for dtype in sorted({dt, "bfloat16"}):
            qd, kd, vd = (x.astype(dtype) for x in (q, k, v))
            pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

            def jnp_core(q, k, v):
                t = lambda x: x.transpose(0, 2, 1, 3)
                return t(flash_attention_jnp(t(q), t(k), t(v),
                                             q_positions=pos,
                                             k_positions=pos, block_k=1024))

            fwd, grad = _fwd_and_grad(jnp_core, qd, kd, vd, w)
            emit(cell=name, shape=[B, H, S, D], dtype=dtype, path="jnp",
                 fwd_ms=fwd, fwd_bwd_ms=grad)
            grid = [(a, b, 512, 512) for a, b in itertools.product(SIZES, SIZES)]
            grid += [(512, 512, a, b) for a, b in itertools.product(SIZES, SIZES)
                     if (a, b) != (512, 512)]
            if dtype != dt:
                grid = [g for g in grid if len(set(g)) == 1]
            for blocks in grid:
                core = (lambda q, k, v, blocks=blocks: splash.causal_attention(
                    q * D ** -0.5, k, v, blocks=blocks))
                try:
                    fwd, grad = _fwd_and_grad(core, qd, kd, vd, w)
                except Exception as e:  # a block the compiler refuses
                    emit(cell=name, dtype=dtype, path="kernel",
                         blocks=list(blocks), error=str(e)[:200])
                    continue
                emit(cell=name, shape=[B, H, S, D], dtype=dtype, path="kernel",
                     blocks=list(blocks), fwd_ms=fwd, fwd_bwd_ms=grad)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write("\n".join(lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
