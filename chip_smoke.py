#!/usr/bin/env python3
"""Bring-up smoke run of the decoupled LayUp lane on TPU chips.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip path only

One chip: the Pallas kernels compiled for the chip against
``repro.kernels.ref`` at GPT-2 Medium plane and head sizes, then the
registry's ``gpt2-medium`` (24 layers, full width, seeded random weights,
synthetic tokens) through ``make_step``: the monolithic decoupled step
(R=2, D=1), the pipeline engine, the stream engine and DDP.

Four chips: M=4 LayUp at full width on a (4, 1) mesh, one gossip ring
hop checked against ``gossip_mix_ref`` on host-held planes, and the
reduced config's M=4 prod lane against the sim trainer.

The times and bytes printed are bring-up readings, not benchmark
numbers. The last line of stdout is ``{"ok": true, "device": {...}}``
only when every check passed; a failed check exits non-zero without it.
There is no CPU path: the script refuses to run without a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

ARCH = "gpt2-medium"
SEQ, BATCH = 1024, 8          # per worker
R, D = 2, 1                   # fb_ratio, update_delay
LR = 0.01
STEPS = 5
# max-norm error relative to max|ref|, per kernel output dtype: f32 allows
# rounding-order differences (FMA), bf16 one or two ulps of the output
KERNEL_RTOL = {"float32": 1e-5, "bfloat16": 1e-2}
# step-0 loss (default matmul precision) vs the f32 loss_fn at "highest"
REF_LOSS_RTOL = 1e-3
# pipeline / stream engine losses vs the monolithic step, every step
ENGINE_LOSS_RTOL = 1e-3
# Pallas interpret mode: never on the chip (a CPU rehearsal of the phases
# at reduced sizes sets it)
INTERPRET = False


class SmokeFailure(Exception):
    """A check failed."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    log(f"  pass: {what}")


def device_gate(chips: int = 1) -> dict:
    """The device the run is reported on. Refuses (SystemExit, non-zero,
    nothing on stdout) unless JAX's first device is a TPU and at least
    ``chips`` of them are present."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {devs[0].platform!r}); "
              f"refusing to run", file=sys.stderr)
        raise SystemExit(2)
    if len(devs) < chips:
        print(f"chip_smoke: needs {chips} chips, jax found {len(devs)}",
              file=sys.stderr)
        raise SystemExit(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _memory(tag: str) -> None:
    import jax
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        log(f"  [bring-up] {tag} device {d.id}: peak_bytes_in_use="
            f"{st.get('peak_bytes_in_use')} bytes_in_use="
            f"{st.get('bytes_in_use')} bytes_limit={st.get('bytes_limit')}")


def _compiled_memory(tag: str, compiled) -> None:
    ma = compiled.memory_analysis()
    log(f"  [bring-up] {tag} memory_analysis: arguments="
        f"{ma.argument_size_in_bytes} outputs={ma.output_size_in_bytes} "
        f"aliased={ma.alias_size_in_bytes} temps={ma.temp_size_in_bytes}")


def _rel_err(got, want) -> float:
    """max|got - want| / max(max|want|, tiny), computed on the device."""
    import jax.numpy as jnp
    g = jnp.asarray(got, jnp.float32)
    w = jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(g - w)) / jnp.maximum(jnp.max(jnp.abs(w)),
                                                        1e-30))


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def plane_sizes(cfg) -> dict:
    """Gossip-plane buffer sizes of ``cfg``: its embedding and final-norm
    groups and one layer's share of the stacked block group."""
    from repro.core.layerview import FlatPartition
    from repro.models import build_model
    sizes = FlatPartition(build_model(cfg).abstract_params()).group_sizes
    return {"final_norm": sizes["final_norm"],
            "block_layer": sizes["blocks"] // cfg.num_layers,
            "embed": sizes["embed"]}


def phase_kernels(cfg, seed: int, *, seq: int = SEQ,
                  batch: int = BATCH) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    interpret = INTERPRET
    key = jax.random.PRNGKey(seed)
    a, b = jnp.float32(0.3), jnp.float32(0.7)
    for name, n in plane_sizes(cfg).items():
        for dtype in (jnp.float32, jnp.bfloat16):
            tol = KERNEL_RTOL[jnp.dtype(dtype).name]
            tag = f"{name} n={n} {jnp.dtype(dtype).name}"
            kx, kr, ku, key = jax.random.split(key, 4)
            x, r, u = (jax.random.normal(k, (n,), jnp.float32).astype(dtype)
                       for k in (kx, kr, ku))
            err = _rel_err(ops.gossip_mix(x, r, u, a, b, interpret=interpret),
                           jax.jit(ref.gossip_mix_ref)(x, r, u, a, b))
            check(err <= tol, f"gossip_mix {tag}: rel err {err:.3g} <= {tol}")
            err = _rel_err(
                ops.gossip_mix(x, r, None, a, b, interpret=interpret),
                jax.jit(ref.gossip_mix_ref)(x, r, jnp.zeros_like(x), a, b))
            check(err <= tol,
                  f"gossip_mix (pure) {tag}: rel err {err:.3g} <= {tol}")

            q, s, res = ops.quantize_plane(x, r, interpret=interpret)
            q_r, s_r, _ = jax.jit(ref.quantize_plane_ref)(x, r)
            err = _rel_err(s, s_r)
            check(err <= 1e-6, f"quantize_plane scales {tag}: rel err "
                               f"{err:.3g} <= 1e-6")
            dq = jnp.abs(q.astype(jnp.int32) - q_r.astype(jnp.int32))
            off = int(jnp.sum(dq > 0))
            check(int(jnp.max(dq)) <= 1 and off <= max(1, n // 10_000),
                  f"quantize_plane q {tag}: {off} codes off by one "
                  f"(<= {max(1, n // 10_000)}), none by more")
            # error feedback: dequant(q, s) + residual == x + r
            want = jax.jit(ref.dequant_mix_ref)(
                jnp.zeros_like(x, jnp.float32), q, s, res.astype(jnp.float32),
                0.0, 1.0)
            ef_tol = 1e-6 if dtype == jnp.float32 else 1e-4
            err = _rel_err(want, x.astype(jnp.float32) + r.astype(jnp.float32))
            check(err <= ef_tol, f"quantize_plane error feedback {tag}: "
                                 f"rel err {err:.3g} <= {ef_tol}")
            for upd, variant in ((u, ""), (None, " (pure)")):
                err = _rel_err(
                    ops.dequant_mix(x, q, s, upd, a, b, interpret=interpret),
                    jax.jit(ref.dequant_mix_ref)(x, q, s, upd, a, b))
                check(err <= tol, f"dequant_mix{variant} {tag}: rel err "
                                  f"{err:.3g} <= {tol}")

    B, H, S, hd = batch, cfg.num_heads, seq, cfg.head_dim
    kq, kk, kv = jax.random.split(key, 3)
    q, k, v = (jax.random.normal(kk_, (B, H, S, hd), jnp.float32)
               .astype(jnp.bfloat16) for kk_ in (kq, kk, kv))
    got = ops.flash_attention(q, k, v, causal=True, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.attention_ref)(q, k, v)
    tol = KERNEL_RTOL["bfloat16"]
    err = _rel_err(got, want)
    check(err <= tol, f"flash_attention B{B} H{H} S{S} d{hd} bf16: rel err "
                      f"{err:.3g} <= {tol}")


# ---------------------------------------------------------------------------
# one chip: gpt2-medium through make_step
# ---------------------------------------------------------------------------


def _run_steps(label, step_fn, state, batches, wait, *, compiles=False):
    """One step per batch, each timed to completion (``compiles``: the
    first call also compiles). Returns the final state and the losses."""
    import math
    losses = []
    for t, batch in enumerate(batches):
        t0 = time.perf_counter()
        state, loss = step_fn(state, batch, t)
        wait(state)
        loss = float(loss)
        dt = time.perf_counter() - t0
        losses.append(loss)
        log(f"  [bring-up] {label} step {t}: {dt:.4f} s"
            f"{' (compile included)' if compiles and t == 0 else ''}"
            f" loss={loss!r}")
        check(math.isfinite(loss), f"{label} step {t} loss is finite")
    return state, losses


def _engine_losses(label, pipe, params_stacked, batches):
    """Drive an overlap engine (``make_step(overlap=True)``) over the
    batches and release it; returns the per-step losses."""
    import jax
    log(f"  {pipe.describe}")
    engine = pipe.engine

    def run(state, b, t):
        state, m = pipe.fn(state, b, t, 0)
        return state, m["loss"]

    def wait(state):
        if hasattr(engine, "materialize"):  # stream engine: futures
            state = engine.materialize(state)
        jax.block_until_ready(state)

    state = pipe.init_state(params_stacked)
    del params_stacked
    _, losses = _run_steps(label, run, state, batches, wait, compiles=True)
    if hasattr(engine, "close"):
        engine.close()
    else:
        engine.reset()
    return losses


def phase_main(cfg, seed: int, *, seq: int = SEQ, batch: int = BATCH,
               steps: int = STEPS) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs import ShapeConfig
    from repro.data.synthetic import lm_batch_for
    from repro.launch.train import make_decoupled_state, make_step
    from repro.models import build_model
    from repro.models.layers import attention_path_counts
    from repro.optim import constant, momentum

    model = build_model(cfg)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1])
    shape = ShapeConfig("chip_smoke", seq, batch, "train")
    opt = momentum(0.9, state_dtype=cfg.dtype)
    sched = constant(LR)
    batches = [lm_batch_for(cfg, batch, seq, seed=seed + t)
               for t in range(steps)]
    params = model.init(jax.random.PRNGKey(seed))
    n_params = sum(p.size for p in jax.tree.leaves(params))
    log(f"  {cfg.name}: {n_params} parameters, {cfg.num_layers} layers, "
        f"d_model={cfg.d_model}, batch {batch} x seq {seq}")

    with jax.default_matmul_precision("highest"):
        ref_loss = float(jax.jit(model.loss_fn)(params, batches[0])[0])
    log(f"  reference loss (f32 loss_fn, highest precision): {ref_loss!r}")
    host_params = jax.device_get(params)
    del params

    def stacked():
        return jax.tree.map(lambda p: jnp.asarray(p)[None], host_params)

    common = dict(algo="layup", optimizer=opt, schedule=sched, fb_ratio=R,
                  update_delay=D)

    # -- monolithic decoupled step ---------------------------------------
    before = attention_path_counts()
    step = make_step(model, mesh, shape, **common)
    log(f"  {step.describe}")
    t0 = time.perf_counter()
    compiled = step.lower().compile()
    log(f"  [bring-up] monolithic compile: {time.perf_counter() - t0:.2f} s")
    _compiled_memory("monolithic", compiled)
    paths = {k: n - before[k] for k, n in attention_path_counts().items()}
    log(f"  attention calls traced for the step, by path: {paths}")
    want, other = (("kernel", "jnp") if jax.default_backend() == "tpu"
                   else ("jnp", "kernel"))
    check(paths[want] > 0 and paths[other] == 0,
          f"the model's attention takes the {want} path on every call")

    def mono(state, b, t):
        state, m = compiled(state, b, jnp.int32(t), jnp.int32(0))
        return state, m["loss"]

    state = make_decoupled_state(stacked(), opt, update_delay=D)
    state, mono_losses = _run_steps("monolithic", mono, state, batches,
                                    jax.block_until_ready)
    del state, compiled
    _memory("after monolithic")
    err = abs(mono_losses[0] - ref_loss) / abs(ref_loss)
    check(err <= REF_LOSS_RTOL, f"step-0 loss {mono_losses[0]!r} vs "
          f"reference {ref_loss!r}: rel {err:.3g} <= {REF_LOSS_RTOL}")

    # -- pipeline engine, stream engine ----------------------------------
    for label, kw in (("pipeline", dict(overlap=True)),
                      ("streams=3", dict(overlap=True, streams=3))):
        losses = _engine_losses(label, make_step(model, mesh, shape, **common,
                                                 **kw), stacked(), batches)
        _memory(f"after {label}")
        worst = max(abs(a - b) / abs(b) for a, b in zip(losses, mono_losses))
        check(worst <= ENGINE_LOSS_RTOL, f"{label} losses match the "
              f"monolithic step: worst rel {worst:.3g} <= {ENGINE_LOSS_RTOL}")

    # -- DDP ---------------------------------------------------------------
    ddp = make_step(model, mesh, shape, algo="ddp", optimizer=opt,
                    schedule=sched)
    t0 = time.perf_counter()
    compiled = ddp.lower().compile()
    log(f"  [bring-up] ddp compile: {time.perf_counter() - t0:.2f} s")
    _compiled_memory("ddp", compiled)

    def ddp_step(carry, b, t):
        p, o, loss = compiled(carry[0], carry[1], b, jnp.int32(t))
        return (p, o), loss

    p = jax.tree.map(jnp.asarray, host_params)
    carry = (p, opt.init(p))
    del p
    carry, ddp_losses = _run_steps("ddp", ddp_step, carry, batches,
                                   jax.block_until_ready)
    del carry, compiled
    _memory("after ddp")
    err = abs(ddp_losses[0] - ref_loss) / abs(ref_loss)
    check(err <= REF_LOSS_RTOL, f"ddp step-0 loss {ddp_losses[0]!r} vs "
          f"reference: rel {err:.3g} <= {REF_LOSS_RTOL}")


# ---------------------------------------------------------------------------
# four chips: M=4 ring
# ---------------------------------------------------------------------------


def _fingerprint(plane):
    """Per-worker (sum, sum of squares) of every plane buffer: (M, 2G)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fp(plane):
        cols = []
        for v in plane.values():
            v = v.reshape(v.shape[0], -1).astype(jnp.float32)
            cols += [jnp.sum(v, axis=1), jnp.sum(v * v, axis=1)]
        return jnp.stack(cols, axis=1)

    return jax.device_get(fp(plane))


def _replicate_workers(one, shardings, M: int):
    """The M-worker decoupled state from a one-worker state ``one``: each
    worker-stacked leaf becomes ``one``'s copy on every chip, and the
    push-sum weights start at 1/M."""
    import jax
    import jax.numpy as jnp

    def place(x, sh):
        if tuple(sh.spec)[:1] != ("data",):
            return jax.device_put(x, sh)
        shards = [jax.device_put(x, d) for d in sh.mesh.devices.flat]
        return jax.make_array_from_single_device_arrays(
            (M,) + x.shape[1:], sh, shards)

    state = jax.tree.map(place, one, shardings)
    state["w"] = jax.device_put(jnp.full((M,), 1.0 / M, jnp.float32),
                                shardings["w"])
    return state


def phase_m4_step(cfg, seed: int, *, seq: int = SEQ, batch: int = BATCH,
                  steps: int = STEPS, M: int = 4) -> None:
    import itertools
    import math

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import ShapeConfig
    from repro.data.synthetic import lm_batch_for
    from repro.launch.train import make_decoupled_state, make_step
    from repro.models import build_model
    from repro.optim import constant, momentum

    model = build_model(cfg)
    mesh = jax.make_mesh((M, 1), ("data", "model"),
                         devices=jax.devices()[:M])
    shape = ShapeConfig("chip_smoke_m4", seq, batch * M, "train")
    opt = momentum(0.9, state_dtype=cfg.dtype)
    shifts = (1, 2)
    step = make_step(model, mesh, shape, algo="layup", optimizer=opt,
                     schedule=constant(LR), fb_ratio=R, update_delay=D,
                     shifts=shifts)
    log(f"  {step.describe}")
    t0 = time.perf_counter()
    compiled = step.lower().compile()
    log(f"  [bring-up] M={M} compile: {time.perf_counter() - t0:.2f} s")
    _compiled_memory(f"M={M}", compiled)

    # one worker's state, built on the first chip as in the one-chip run,
    # copied to every chip (jitting the M-worker pack for this layout
    # takes minutes to compile)
    params = model.init(jax.random.PRNGKey(seed))
    one = make_decoupled_state(jax.tree.map(lambda p: p[None], params), opt,
                               update_delay=D)
    del params
    state = _replicate_workers(one, compiled.input_shardings[0][0], M)
    del one
    batches = [lm_batch_for(cfg, batch * M, seq, seed=seed + t)
               for t in range(steps)]
    for t, b in enumerate(batches):
        # shift 1 after the first local update (t=1): every worker then
        # mixes with a distinct source, so all four planes must differ
        shift_idx = (t + 1) % len(shifts)
        t0 = time.perf_counter()
        state, m = compiled(state, b, jnp.int32(t), jnp.int32(shift_idx))
        jax.block_until_ready((state, m))
        dt = time.perf_counter() - t0
        loss, wsum = float(m["loss"]), float(m["weight_sum"])
        log(f"  [bring-up] M={M} step {t}: {dt:.4f} s loss={loss!r} "
            f"sum_w={wsum!r} shift_idx={shift_idx}")
        check(math.isfinite(loss), f"M={M} step {t} loss is finite")
        check(abs(wsum - 1.0) <= 1e-6, f"M={M} step {t}: sum of push-sum "
                                       f"weights stays 1")
        if t == D:
            fp = _fingerprint(state["read"])
            same = [(i, j) for i, j in itertools.combinations(range(M), 2)
                    if np.array_equal(fp[i], fp[j])]
            check(not same, f"the {M} workers' planes differ after their "
                            f"first local update (equal pairs: {same})")
    _memory(f"after M={M} step")


def phase_ring_hop(cfg, seed: int, *, M: int = 4) -> None:
    """One gossip ring hop on the chips, both lanes, against
    ``gossip_mix_ref`` applied to host-held planes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.layerview import FlatPartition
    from repro.kernels.ref import gossip_mix_ref
    from repro.launch.train import gossip_plane_lane, shard_map
    from repro.models import build_model

    mesh = jax.make_mesh((M, 1), ("data", "model"),
                         devices=jax.devices()[:M])
    part = FlatPartition(build_model(cfg).abstract_params())
    shifts = (1, 2)
    wsh = NamedSharding(mesh, P("data"))
    planes = jax.jit(
        lambda key: {name: jax.random.normal(jax.random.fold_in(key, i),
                                             (M, n), jnp.float32)
                     for i, (name, n) in enumerate(part.group_sizes.items())},
        out_shardings=wsh)(jax.random.PRNGKey(seed))
    w = jax.device_put(jnp.asarray([0.1, 0.2, 0.3, 0.4][:M], jnp.float32),
                       wsh)
    host = jax.device_get(planes)
    host_w = np.asarray(w)
    for use_pallas in (False, True):
        mix = gossip_plane_lane(part, M, "data", shifts,
                                use_pallas=use_pallas, interpret=INTERPRET)

        def body(plane, w, shift_idx):
            mixed, w2 = mix({k: v[0] for k, v in plane.items()}, w[0],
                            shift_idx)
            return {k: v[None] for k, v in mixed.items()}, w2[None]

        hop = jax.jit(shard_map(body, mesh=mesh,
                                in_specs=(P("data"), P("data"), P()),
                                out_specs=(P("data"), P("data")),
                                axis_names={"data"}))
        for shift_idx, s in enumerate(shifts):
            mixed, new_w = hop(planes, w, jnp.int32(shift_idx))
            mixed, new_w = jax.device_get((mixed, new_w))
            worst = w_err = 0.0
            for j in range(M):
                src = (j - s) % M  # worker i sends to i + s
                keep, recv = host_w[j] * 0.5, host_w[src] * 0.5
                denom = keep + recv
                w_err = max(w_err, abs(float(new_w[j]) - denom))
                for name, x in host.items():
                    want = np.asarray(gossip_mix_ref(
                        x[j], x[src], np.zeros_like(x[j]),
                        np.float32(keep / denom), np.float32(recv / denom)))
                    worst = max(worst, float(np.max(np.abs(mixed[name][j]
                                                           - want))))
            check(w_err <= 1e-7, f"ring hop shift {s} (pallas={use_pallas}):"
                                 f" every worker's weight is w[j]/2 + "
                                 f"w[j-{s}]/2, max err {w_err:.3g}")
            tol = 1e-5
            check(worst <= tol,
                  f"ring hop shift {s} (pallas={use_pallas}): every "
                  f"worker mixed its own plane with worker j-{s}'s, max "
                  f"abs err {worst:.3g} <= {tol}")
    check(abs(float(np.sum(host_w)) - 1.0) <= 1e-6,
          "ring hop weights sum to 1")


def phase_sim_parity(cfg, seed: int, *, M: int = 4, seq: int = 64,
                     batch: int = 4, steps: int = STEPS) -> None:
    """The reduced config's M=4 prod lane against the sim trainer:
    identical staleness accounting every step, the first step's loss
    (before any gossip) equal, push-sum mass conserved."""
    import jax
    import numpy as np
    from repro.core import make_backend
    from repro.data.synthetic import lm_batch_for
    from repro.models import build_model
    from repro.optim import constant, momentum

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    kw = dict(M=M, loss_fn=model.loss_fn, optimizer=momentum(0.9),
              schedule=constant(0.05), fb_ratio=R, update_delay=D)
    prod = make_backend("prod", "layup", **kw)
    sim = make_backend("sim", "layup-hypercube", **kw)
    ps = prod.init(jax.random.PRNGKey(seed), params)
    ss = sim.init(jax.random.PRNGKey(seed), params)
    rng = jax.random.PRNGKey(seed + 1)
    for t in range(steps):
        b = jax.tree.map(
            lambda x: x.reshape((M, batch) + x.shape[1:]),
            lm_batch_for(cfg, M * batch, seq, seed=seed + t))
        rng, r = jax.random.split(rng)
        ps, pm = prod.step(ps, b, r)
        ss, sm = sim.step(ss, b, r)
        lp, ls = float(pm["loss"]), float(sm["loss"])
        log(f"  t={t} prod loss={lp!r} sim loss={ls!r} "
            f"staleness prod={np.asarray(pm['layer_staleness']).tolist()} "
            f"sim={np.asarray(sm['layer_staleness']).tolist()}")
        if t == 0:
            err = abs(lp - ls) / abs(ls)
            check(err <= ENGINE_LOSS_RTOL,
                  f"step-0 loss prod vs sim: rel {err:.3g} <= "
                  f"{ENGINE_LOSS_RTOL}")
        check(np.array_equal(np.asarray(pm["layer_staleness"]),
                             np.asarray(sm["layer_staleness"])),
              f"step {t}: per-layer staleness prod == sim")
        check(float(pm["update_staleness"]) == float(sm["update_staleness"]),
              f"step {t}: update staleness prod == sim")
        check(abs(float(pm["weight_sum"]) - 1.0) <= 1e-6,
              f"step {t}: prod sum of push-sum weights stays 1")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the four-chip path only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    device = device_gate(args.chips)
    sys.path.insert(0, SRC)
    from repro.launch.cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {device}")
    from repro.configs import get_config, reduced
    cfg = get_config(ARCH)

    if args.chips == 4:
        phases = [("M=4 LayUp step, full width", phase_m4_step, cfg),
                  ("gossip ring hop", phase_ring_hop, reduced(cfg)),
                  ("M=4 prod vs sim, reduced config", phase_sim_parity,
                   reduced(cfg))]
    else:
        phases = [("Pallas kernels (compiled)", phase_kernels, cfg),
                  (f"{ARCH} through make_step", phase_main, cfg)]
    for title, fn, c in phases:
        log(f"== {title}")
        t0 = time.perf_counter()
        try:
            fn(c, args.seed)
        except SmokeFailure as e:
            print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
            return 1
        log(f"  [bring-up] phase wall time: {time.perf_counter() - t0:.2f} s")
    log(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
