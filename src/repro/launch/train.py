"""Production-backend step builders (pjit / shard_map on the real mesh).

Three distribution strategies, mirroring the paper's comparison:

* **DDP** (baseline): parameters replicated over the ('pod','data') axes,
  tensor-parallel over 'model'. Plain ``jax.jit``: GSPMD inserts the gradient
  all-reduce (2·P·(M−1)/M wire bytes — the synchronization the paper removes).

* **LayUp** (the paper): every data-parallel replica owns a distinct copy of
  the parameters (stacked leading worker axis, sharded over ('pod','data')).
  ``shard_map`` is *manual* over the worker axes and **auto (GSPMD) over
  'model'**, so tensor parallelism composes transparently ("orthogonal to
  model/tensor/pipeline parallelism", paper §1). Gossip is a
  ``collective_permute`` ring shift over the worker axes — the TPU-native
  realization of random-peer gossip (each hop is an ICI-neighbour hop; the
  shift is drawn per step from a static power-of-two set via ``lax.switch``,
  i.e. hypercube gossip — see DESIGN.md §2). Push-sum weights ride along as
  a per-worker scalar. Collectives are issued **per layer group by
  construction**: the parameter tree is partitioned through the same
  ``LayerPartition`` the sim backend's v2 hooks use (DESIGN.md §1), and each
  group's subtree ships as one logical gossip message — the HLO counterpart
  of the paper's layer-wise updates.

* **Decoupled LayUp** (the paper's PD-ASGD execution, production form):
  the per-worker step is assembled from three composable lanes —
  ``forward_lane`` (loss + grads on the *read* parameter buffer, with an
  R:1 forward:backward ratio), ``backward_update_lane`` (a D-deep gradient
  FIFO feeding the optimizer, mutating the *write* buffer), and
  ``gossip_lane`` (the per-layer-group push-sum ring mix). Parameters are
  **double-buffered**: the forward lane consumes the read copy while the
  update lane mutates the write copy; at the end of the step each layer
  group's read copy adopts the mixed write copy ("buffer swap") and its
  version clock is stamped with the group's generation time ``t + phi_g``
  (``send_fractions``). Forward passes at step ``t`` therefore use layer
  groups whose content reflects gradients through step ``t − 1 − D`` — the
  production analogue of the sim trainer's ``fb_ratio``/``update_delay``
  (DESIGN.md §3/§9). DDP and lockstep LayUp are assembled from the same
  lane pieces (R=1, D=0, with/without the gossip lane). By default the
  decoupled state carries the parameters as a **persistent flat plane**
  (one contiguous buffer per layer group, packed once at init through
  :class:`~repro.core.layerview.FlatPartition`): gossip ships the plane
  directly in the params' dtype — no per-step repack, no f32 wire bloat —
  and ``use_pallas`` fuses mix+apply into the ``gossip_mix`` kernel
  (DESIGN.md §11).

Serving: ``make_prefill_step`` / ``make_decode_step`` build the inference
paths (params replicated over data axes, TP over 'model'; decode donates the
KV cache).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map as _shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from jax.flatten_util import ravel_pytree

from repro.configs.base import ModelConfig, ShapeConfig, input_specs
from repro.core.layerview import (
    FlatPartition, LayerPartition, send_fractions, stamp_groups,
    version_metrics,
)
from repro.kernels.gossip_mix import gossip_mix as _gossip_mix_kernel
from repro.kernels.ops import per_shard
from repro.kernels.quantize import dequant_mix as _dequant_mix_kernel
from repro.kernels.quantize import quantize_plane as _quantize_plane_kernel
from repro.kernels.ref import dequant_mix_ref, quantize_plane_ref
from repro.launch import sharding as SH
from repro.launch.mesh import data_axes, num_workers
from repro.models.model import Model
from repro.optim.optimizers import Optimizer, apply_updates


@dataclass
class ProdStep:
    """A lowered-able step: ``fn`` jitted with shardings, plus abstract args.

    ``chaos`` (set by ``make_step(faults=)``) is the
    :class:`repro.chaos.ChaosController` driving the step's fault plan —
    callers apply ``chaos.before_step`` at each host step boundary."""
    fn: Any
    abstract_args: Tuple[Any, ...]
    describe: str = ""
    chaos: Any = None

    def lower(self):
        return self.fn.lower(*self.abstract_args)


def shard_map(f, *, mesh, in_specs, out_specs, axis_names):
    """``jax.shard_map`` manual over ``axis_names`` (the worker axes) and
    over every axis of size 1, which GSPMD has nothing to split over, and
    GSPMD-auto over the rest ('model' when it is split), replication checks
    off. A compiled Pallas kernel in the body then needs no shard_map of its
    own on a (M, 1) mesh (``repro.kernels.ops.per_shard``): nested one per
    attention call, they cost the stablelm-1.6b step 0.8 GB more compiled
    HBM (compiled for a v5e)."""
    names = set(axis_names) | {a for a, n in mesh.shape.items() if n == 1}
    return _shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=False, axis_names=names)


def _abstract_batch(cfg: ModelConfig, shape: ShapeConfig, dtype=None):
    return input_specs(cfg, shape, dtype)


# ---------------------------------------------------------------------------
# composable lanes: forward / backward-update / gossip
#
# DDP, lockstep LayUp and decoupled LayUp are assembled from these three
# factories; each returns a pure per-worker function traced inside the
# step (shard_map body for the LayUp paths, plain jit for DDP).
# ---------------------------------------------------------------------------


def _batch_dim(leaf) -> int:
    """Per-leaf batch dimension: M-RoPE positions are (3, B, S) → dim 1,
    everything else leads with the batch dim."""
    if len(leaf.shape) == 3 and leaf.shape[0] == 3 and leaf.dtype == jnp.int32:
        return 1
    return 0


def _worker_batch_pspec(ax):
    """Per-leaf shard_map batch specs: the worker axes land on the leaf's
    batch dim (see :func:`_batch_dim`)."""
    def batch_pspec(s):
        if _batch_dim(s) == 1:
            return P(None, ax)
        return P(ax)
    return batch_pspec


def _split_fwd_slices(batch, R: int):
    """Split a per-worker batch into R equal forward slices along the batch
    dim (slice 0 feeds the backward lane — cf. api._split_fwd_lane)."""
    def slc(x, r):
        d = _batch_dim(x)
        n = x.shape[d]
        if n % R:
            raise ValueError(
                f"fb_ratio={R} needs per-worker batch divisible by {R}; "
                f"got leaf shape {x.shape}")
        return jax.lax.slice_in_dim(x, (n // R) * r, (n // R) * (r + 1),
                                    axis=d)

    return [jax.tree.map(lambda x: slc(x, r), batch) for r in range(R)]


def _apply_grad_specs(grads, grad_specs):
    """Pin gradients to the parameter sharding (reduce-scatter instead of
    all-reduce+slice, §Perf iteration A3). Shared by the monolithic forward
    lane and the per-slice pipeline stages so both compile identical HLO."""
    if grad_specs is None:
        return grads
    return jax.tree.map(
        lambda g, s: jax.lax.with_sharding_constraint(g, s),
        grads, grad_specs)


def forward_lane(loss_fn: Callable, *, fb_ratio: int = 1,
                 accum_steps: int = 1, grad_specs=None) -> Callable:
    """Forward(+backward-AD) compute on the read buffer.

    Returns ``fwd(params, batch) -> (loss, grads)``. With ``fb_ratio=R > 1``
    the worker batch is split into R slices of which only slice 0 receives a
    backward — the paper's decoupled forward threads, serving data at R× the
    update rate; the reported loss averages all R slices. ``accum_steps``
    microbatches the backward (activation footprint scales with the
    microbatch); it does not compose with R > 1. ``grad_specs`` pins the
    gradients to the parameter sharding so GSPMD reduce-scatters instead of
    all-reduce+slice (§Perf iteration A3)."""
    R = int(fb_ratio)
    if R < 1:
        raise ValueError("fb_ratio must be >= 1")
    if R > 1 and accum_steps > 1:
        raise ValueError("fb_ratio > 1 does not compose with accum_steps")

    def fwd(params, batch):
        if R > 1:
            slices = _split_fwd_slices(batch, R)
            with jax.named_scope("fwd0"):
                (bwd_loss, _), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, slices[0])
            fwd_losses = []
            for r in range(1, R):
                with jax.named_scope(f"fwd{r}"):
                    fwd_losses.append(loss_fn(params, slices[r])[0])
            loss = (bwd_loss + sum(fwd_losses)) / R
        else:
            with jax.named_scope("fwd0"):
                loss, grads = whole(params, batch)
        grads = _apply_grad_specs(grads, grad_specs)
        return loss, grads

    def whole(params, batch):
        """Loss and gradients of the whole batch, microbatched when
        ``accum_steps > 1``."""
        if accum_steps > 1:
            def micro(b):
                return jax.value_and_grad(loss_fn, has_aux=True)(params, b)

            mb = jax.tree.map(
                lambda x: x.reshape((accum_steps, x.shape[0] // accum_steps)
                                    + x.shape[1:]), batch)

            def acc_body(carry, b):
                (l, _), g = micro(b)
                return jax.tree.map(lambda a, x: a + x, carry,
                                    {"l": l, "g": g}), ()

            zero = {"l": jnp.zeros((), jnp.float32),
                    "g": jax.tree.map(
                        lambda p: jnp.zeros(p.shape, jnp.float32), params)}
            tot, _ = jax.lax.scan(acc_body, zero, mb)
            loss = tot["l"] / accum_steps
            grads = jax.tree.map(
                lambda g, p: (g / accum_steps).astype(p.dtype),
                tot["g"], params)
            return loss, grads
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        return loss, grads

    return fwd


def forward_slice_lane(loss_fn: Callable, *, fb_ratio: int = 1,
                       slice_idx: int = 0, grad_specs=None) -> Callable:
    """ONE forward slice of the decoupled forward lane, as a standalone
    stage — the unit the pipeline engine (repro.launch.pipeline) compiles
    into its own jitted executable.

    Slice 0 is the backward slice: returns ``(loss, grads)``. Slices
    ``1..R-1`` are forward-only: returns ``(loss, None)``. Slicing uses the
    same :func:`_split_fwd_slices` as the monolithic :func:`forward_lane`,
    so the per-slice math (and therefore the combined loss) is identical —
    the engine's parity with the monolithic step rests on it."""
    R, r = int(fb_ratio), int(slice_idx)
    if not 0 <= r < R:
        raise ValueError(f"slice_idx={r} out of range for fb_ratio={R}")

    def fwd(params, batch):
        s = _split_fwd_slices(batch, R)[r] if R > 1 else batch
        with jax.named_scope(f"fwd{r}"):
            if r == 0:
                (loss, _), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, s)
                return loss, _apply_grad_specs(grads, grad_specs)
            return loss_fn(params, s)[0], None

    return fwd


def backward_update_lane(optimizer: Optimizer, schedule: Callable, *,
                         update_delay: int = 0, apply: bool = True,
                         compensate: float = 0.0) -> Callable:
    """Delayed update application on the write buffer.

    Returns ``upd(params, opt_state, grads, fifo, step_idx) ->
    (params, opt_state, fifo, update_staleness, nonfinite_skips)``.
    ``nonfinite_skips`` counts the layer groups whose delayed gradient
    arrived NaN/Inf this step: those groups' updates are skipped (params
    untouched, optimizer state fed zeros — DESIGN.md §15) instead of
    poisoning the plane. With ``update_delay=D > 0``
    gradients flow through a D-deep FIFO (``{"g": (D, ...) tree in the
    params' dtypes, "stamp": (D,) f32}``): the gradient applied at step
    ``t`` was generated
    at step ``t − D`` (warm-up: the FIFO holds zeros and stamp −1 for the
    first D steps, so early updates are no-ops). Mirrors the sim trainer's
    backward lane exactly (api.make_sim_trainer). ``active`` (scalar 0/1,
    per worker) masks the *application* of the update — the straggler
    emulation of the sim backend (the optimizer state still advances,
    matching api.make_sim_trainer's masked_apply semantics).

    ``apply=False`` returns the (masked) update DELTAS in place of the
    new params — the contract of the fused gossip lane
    (:func:`gossip_fused_lane`), which folds the apply into the mix's
    single memory pass. Params are still consumed read-only (weight
    decay, delayed-gradient dtype).

    ``compensate=λ > 0`` turns on Zheng-style delay compensation
    (DESIGN.md §14): the delayed gradient is corrected by the diagonal
    Hessian approximation ``g' = g + λ·g⊙g⊙(θ_now − θ_stale)`` before the
    optimizer sees it, with ``θ_now − θ_stale`` estimated from the
    version clocks as ``s·(θ_now − θ_prev)`` — ``s`` the measured update
    staleness and ``θ_prev`` ONE carried plane buffer (the previous
    step's pre-update params), not a D-deep tree copy. The lane then
    takes a ``theta`` kwarg and appends ``theta_new`` (this step's
    pre-update params) after ``nonfinite_skips``. At D == 0 the
    stamp-driven staleness is 0 and the correction self-gates to a
    no-op."""
    D = int(update_delay)
    if D < 0:
        raise ValueError("update_delay must be >= 0")
    lam = float(compensate)
    if lam < 0:
        raise ValueError("compensate (λ) must be >= 0")

    def upd(params, opt_state, grads, fifo, step_idx, active=None,
            theta=None):
        with jax.named_scope("update"):
            return update(params, opt_state, grads, fifo, step_idx, active,
                          theta)

    def update(params, opt_state, grads, fifo, step_idx, active, theta):
        step_f = step_idx.astype(jnp.float32)
        if D > 0:
            g_apply = jax.tree.map(lambda b: b[0], fifo["g"])
            applied_stamp = fifo["stamp"][0]
            fifo = {
                "g": jax.tree.map(
                    lambda b, g: jnp.concatenate(
                        [b[1:], g[None].astype(b.dtype)], axis=0),
                    fifo["g"], grads),
                "stamp": jnp.concatenate([fifo["stamp"][1:], step_f[None]]),
            }
            grads = jax.tree.map(lambda g, p: g.astype(p.dtype),
                                 g_apply, params)
            update_staleness = jnp.where(applied_stamp >= 0.0,
                                         step_f - applied_stamp, 0.0)
        else:
            update_staleness = jnp.zeros((), jnp.float32)
        # nonfinite guard (DESIGN.md §15): a NaN/Inf gradient for a layer
        # group is skipped, not applied — sanitized to zero BEFORE the
        # optimizer (where(ok, g, 0), never g·0: Inf·0 is NaN — so the
        # optimizer state stays finite) and its update masked below so the
        # group's params are untouched. For finite gradients both steps
        # are bitwise identity (select-true, u·1.0). In flat mode leaves
        # ARE layer groups, so `skips` counts skipped (worker, group)
        # pairs.
        ok = jax.tree.map(lambda g: jnp.isfinite(g).all(), grads)
        skips = sum(1.0 - o.astype(jnp.float32)
                    for o in jax.tree.leaves(ok))
        skips = jnp.asarray(skips, jnp.float32)
        grads = jax.tree.map(lambda g, o: jnp.where(o, g, jnp.zeros_like(g)),
                             grads, ok)
        if lam > 0.0:
            drift = update_staleness  # θ_now − θ_stale ≈ s·(θ_now − θ_prev)

            def comp(g, p, tp):
                gf = g.astype(jnp.float32)
                delta = drift * (p.astype(jnp.float32)
                                 - tp.astype(jnp.float32))
                return (gf + lam * gf * gf * delta).astype(g.dtype)

            grads = jax.tree.map(comp, grads, params, theta)
        lr = schedule(step_idx)
        updates, opt_state = optimizer.update(grads, opt_state, params, lr)
        # mask the skipped groups' updates too: a sanitized-to-zero grad
        # can still move params through momentum — skip means UNCHANGED
        updates = jax.tree.map(lambda u, o: u * o.astype(u.dtype),
                               updates, ok)
        if active is not None:
            updates = jax.tree.map(lambda u: u * active.astype(u.dtype),
                                   updates)
        out = updates if not apply else apply_updates(params, updates)
        if lam > 0.0:
            return out, opt_state, fifo, update_staleness, skips, params
        return out, opt_state, fifo, update_staleness, skips

    return upd


def fifo_init(params_single, update_delay: int, M: int = 0):
    """Abstract/zero FIFO state: gradients in the params' dtypes plus f32
    generation stamps. Matching the parameter dtype (instead of a fixed
    f32) keeps the D param-sized FIFO slots at the parameter memory
    footprint — for bf16 params the FIFO is half the size, and the
    gradients it carries are quantized exactly like the updates the
    optimizer would apply anyway.

    With ``M > 0`` the gradient buffers are worker-stacked (M, D, ...) —
    the layout the decoupled step state carries."""
    D = int(update_delay)

    def zeros(p):
        shape = ((M, D) if M else (D,)) + tuple(p.shape)
        return jnp.zeros(shape, p.dtype)

    return {"g": jax.tree.map(zeros, params_single),
            "stamp": jnp.full((D,), -1.0, jnp.float32)}


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """Pallas interpret mode: on by default off-TPU (this container), so
    the same lanes run on CPU CI and real hardware."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _mix_scope(name: str):
    """The named scope of one plane buffer's part of the gossip mix,
    ``mix.<buffer>``, and ``mix.weight`` for the push-sum weights. A
    profiler trace's ``tf_op`` puts ``:`` before the op type, so the ``:``
    of a mixed-dtype group's buffer name becomes ``.``."""
    return jax.named_scope("mix." + name.replace(":", "."))


def _ring_exchange(plane, w, shift_idx, M: int, ax, shifts: Sequence[int],
                   alive=None):
    """One push-sum ring hop on the flat plane: ship every group buffer
    (in its own dtype — the wire cost is exactly ``plane_nbytes`` per
    peer) plus the halved push-sum weight.

    Returns ``(recv, w_keep, rw, use)``: the received buffers, the local
    share of the push-sum weight after the hop, the received weight
    share, and a 0/1 gate (``None`` without membership) that is 1 only
    when BOTH this worker and the hop's source are alive — callers must
    fall back to their own buffer when it is 0.

    ``alive`` (a per-worker 0/1 f32 scalar, DESIGN.md §15) gates the
    exchange for fault-tolerant membership: mass is sent only when both
    endpoints are alive (``w_sent = w/2 · a_self · a_tgt`` — a dead
    target would absorb it, leaking Σw out of the live set; a dead
    sender must not inject its stale plane), so Σw over the live peers
    is conserved exactly every round (``w_keep + w_sent`` re-adds the
    identical f32 terms). With every peer alive the gating multiplies by
    1.0 throughout — bitwise identical to the ungated hop."""
    def branch(s):
        perm = [(i, (i + s) % M) for i in range(M)]
        inv = [(i, (i - s) % M) for i in range(M)]

        def run(args):
            plane, w = args
            with _mix_scope("weight"):
                if alive is None:
                    w_sent = w * 0.5
                    w_keep = w * 0.5
                else:
                    a_tgt = jax.lax.ppermute(alive, ax, inv)
                    w_sent = w * 0.5 * (alive * a_tgt)
                    w_keep = w - w_sent
            recv = {}
            for name, v in plane.items():
                with _mix_scope(name):
                    recv[name] = jax.lax.ppermute(v, ax, perm)
            with _mix_scope("weight"):
                rw = jax.lax.ppermute(w_sent, ax, perm)
                # the received w_sent already carries the sender's gating;
                # `use` re-derives it receiver-side (a_src · a_self) as the
                # fall-back-to-own-buffer predicate
                use = (None if alive is None
                       else jax.lax.ppermute(alive, ax, perm) * alive)
            return recv, w_keep, rw, use

        return run

    return jax.lax.switch(shift_idx, [branch(s) for s in shifts],
                          (plane, w))


def gossip_plane_lane(part: FlatPartition, M: int, ax,
                      shifts: Sequence[int], *, use_pallas: bool = False,
                      interpret: Optional[bool] = None,
                      wire: str = "param"):
    """Push-sum ring gossip directly on the persistent flat plane: no
    per-step ravel, no unravel, and the wire dtype IS the plane dtype
    (bf16 params ship half the bytes of the old blanket-f32 wire; the
    push-sum weight accounting stays f32). Returns
    ``mix(plane, w, shift_idx) -> (plane, w)``; the identity when M == 1.

    ``use_pallas`` routes the per-group mix through the fused
    ``gossip_mix`` kernel (pure-mix variant — the update was already
    applied by the backward lane); the default jnp path computes
    ``(w/2·mine + w'/2·recv) / (w/2 + w'/2)`` in f32, bitwise-identical
    per element to the legacy ravel_pytree lane.

    ``wire="int8"`` quantizes the OUTGOING plane (error-feedback
    residual carried in a second per-group plane buffer, DESIGN.md §14)
    and ships ``{q, scales}`` per group instead of the param-dtype
    buffer — ~0.52× the bf16 wire. The local mix operand stays exact;
    only the received side is dequantized. Signature becomes
    ``mix(plane, resid, w, shift_idx) -> (plane, resid, w)`` (identity
    at M == 1 — nothing crosses the wire, nothing is quantized)."""
    interpret = _resolve_interpret(interpret)
    if wire == "int8":
        if M == 1:
            return lambda plane, resid, w, shift_idx, alive=None: (
                plane, resid, w)
        if use_pallas:
            qfn = per_shard(lambda x, r: _quantize_plane_kernel(
                x, r, interpret=interpret))
            dqfn = per_shard(lambda x, q, s, a, b: _dequant_mix_kernel(
                x, q, s, None, a, b, interpret=interpret))
        else:
            qfn = quantize_plane_ref
            dqfn = lambda x, q, s, a, b: dequant_mix_ref(x, q, s, None, a, b)

        def mix_q(plane, resid, w, shift_idx, alive=None):
            payload, new_resid = {}, {}
            for name, mine in plane.items():
                with _mix_scope(name):
                    q, s, r2 = qfn(mine, resid[name])
                payload[f"q:{name}"] = q
                payload[f"s:{name}"] = s
                new_resid[name] = r2
            recv, w_keep, rw, use = _ring_exchange(payload, w, shift_idx,
                                                   M, ax, shifts, alive)
            with _mix_scope("weight"):
                new_w = w_keep + rw
                # membership: a dead peer's weight is 0 on both sides of
                # the hop — guard the 0/0 (its buffers are never read
                # again)
                denom = new_w if use is None else jnp.where(new_w > 0.0,
                                                            new_w, 1.0)
                alpha, beta = w_keep / denom, rw / denom
            mixed = {}
            for name, mine in plane.items():
                with _mix_scope(name):
                    mx = dqfn(mine, recv[f"q:{name}"], recv[f"s:{name}"],
                              alpha, beta)
                    mixed[name] = mx if use is None else jnp.where(
                        use > 0.0, mx, mine)
            return mixed, new_resid, new_w

        return mix_q
    if wire != "param":
        raise ValueError(f"unknown wire dtype {wire!r}")
    if M == 1:
        return lambda plane, w, shift_idx, alive=None: (plane, w)
    pure_mix = per_shard(lambda x, r, a, b: _gossip_mix_kernel(
        x, r, None, a, b, interpret=interpret))

    def mix(plane, w, shift_idx, alive=None):
        recv, w_keep, rw, use = _ring_exchange(plane, w, shift_idx, M, ax,
                                               shifts, alive)
        with _mix_scope("weight"):
            new_w = w_keep + rw
            denom = new_w if use is None else jnp.where(new_w > 0.0, new_w,
                                                        1.0)
        mixed = {}
        for name, mine in plane.items():
            with _mix_scope(name):
                if use_pallas:
                    mx = pure_mix(mine, recv[name], w_keep / denom,
                                  rw / denom)
                else:
                    mf = (w_keep * mine.astype(jnp.float32)
                          + rw * recv[name].astype(jnp.float32)) / denom
                    mx = mf.astype(mine.dtype)
                mixed[name] = mx if use is None else jnp.where(
                    use > 0.0, mx, mine)
        return mixed, new_w

    return mix


def gossip_fused_lane(part: FlatPartition, M: int, ax,
                      shifts: Sequence[int], *, use_pallas: bool = True,
                      interpret: Optional[bool] = None,
                      wire: str = "param"):
    """The paper's Alg. 1 ordering, fused: ship the PRE-update plane, then
    one pass per group computes ``mixed = α·x + β·recv + upd`` (3 reads +
    1 write — the memory-bound op the ``gossip_mix`` Pallas kernel was
    written for; separate apply-then-mix costs 4 reads + 2 writes).
    Returns ``mix_apply(plane, updates, w, shift_idx) -> (plane, w)``.

    Note the semantic difference from the default lane: a worker's own
    update reaches its peers one ring hop later (it is not mixed into the
    outgoing message). Both orderings are valid push-sum ASGD; the fused
    lane is the kernel's contract and is selected by ``use_pallas`` on
    the decoupled paths. At M == 1 it degenerates to a fused
    ``x + upd`` apply (α=1, β=0), still through the kernel.

    ``wire="int8"`` quantizes the outgoing pre-update plane (EF residual
    carried forward, DESIGN.md §14) and fuses receive-side dequantize
    into the same single mix pass (``dequant_mix`` kernel). Signature
    becomes ``mix_apply(plane, resid, updates, w, shift_idx) ->
    (plane, resid, w)``; at M == 1 the residual passes through
    untouched."""
    interpret = _resolve_interpret(interpret)
    if use_pallas:
        op = per_shard(lambda x, r, u, a, b: _gossip_mix_kernel(
            x, r, u, a, b, interpret=interpret))
    else:
        from repro.kernels.ref import gossip_mix_ref as op
    if wire == "int8":
        if use_pallas:
            qfn = per_shard(lambda x, r: _quantize_plane_kernel(
                x, r, interpret=interpret))
            dqfn = per_shard(lambda x, q, s, u, a, b: _dequant_mix_kernel(
                x, q, s, u, a, b, interpret=interpret))
        else:
            qfn = quantize_plane_ref
            dqfn = dequant_mix_ref

        def mix_apply_q(plane, resid, updates, w, shift_idx, alive=None):
            if M == 1:
                return apply_only(plane, updates), resid, w
            payload, new_resid = {}, {}
            for name, mine in plane.items():
                with _mix_scope(name):
                    q, s, r2 = qfn(mine, resid[name])
                payload[f"q:{name}"] = q
                payload[f"s:{name}"] = s
                new_resid[name] = r2
            recv, w_keep, rw, use = _ring_exchange(payload, w, shift_idx,
                                                   M, ax, shifts, alive)
            with _mix_scope("weight"):
                new_w = w_keep + rw
                denom = new_w if use is None else jnp.where(new_w > 0.0,
                                                            new_w, 1.0)
                alpha, beta = w_keep / denom, rw / denom
            mixed = {}
            for name, x in plane.items():
                with _mix_scope(name):
                    mx = dqfn(x, recv[f"q:{name}"], recv[f"s:{name}"],
                              updates[name], alpha, beta)
                    if use is not None:
                        # degraded hop: still apply the local update (α=1,
                        # β=0), just don't mix in the dead source's payload
                        own = op(x, x, updates[name], jnp.float32(1.0),
                                 jnp.float32(0.0))
                        mx = jnp.where(use > 0.0, mx, own)
                    mixed[name] = mx
            return mixed, new_resid, new_w

        return mix_apply_q
    if wire != "param":
        raise ValueError(f"unknown wire dtype {wire!r}")

    def apply_only(plane, updates):
        """``x + upd`` per buffer through the mix op (α=1, β=0): the
        fused lane with nothing received."""
        mixed = {}
        for name, x in plane.items():
            with _mix_scope(name):
                mixed[name] = op(x, x, updates[name], jnp.float32(1.0),
                                 jnp.float32(0.0))
        return mixed

    def mix_apply(plane, updates, w, shift_idx, alive=None):
        if M == 1:
            return apply_only(plane, updates), w
        recv, w_keep, rw, use = _ring_exchange(plane, w, shift_idx, M, ax,
                                               shifts, alive)
        with _mix_scope("weight"):
            new_w = w_keep + rw
            denom = new_w if use is None else jnp.where(new_w > 0.0, new_w,
                                                        1.0)
            alpha, beta = w_keep / denom, rw / denom
        mixed = {}
        for name, x in plane.items():
            with _mix_scope(name):
                mx = op(x, recv[name], updates[name], alpha, beta)
                if use is not None:
                    own = op(x, x, updates[name], jnp.float32(1.0),
                             jnp.float32(0.0))
                    mx = jnp.where(use > 0.0, mx, own)
                mixed[name] = mx
        return mixed, new_w

    return mix_apply


def gossip_lane(part: FlatPartition, M: int, ax, shifts: Sequence[int], *,
                use_pallas: bool = False,
                interpret: Optional[bool] = None):
    """Tree-level gossip for the lockstep LayUp step (whose state stays a
    parameter pytree): pack each layer group through the shared
    :class:`FlatPartition` layout, mix on the flat buffers, unpack. One
    collective per layer group, in the params' dtype — the decoupled
    lanes skip the per-call pack entirely by keeping the plane persistent
    (``gossip_plane_lane``). Returns ``mix(tree, w, shift_idx) ->
    (tree, w)``; the identity when M == 1."""
    if M == 1:
        return lambda tree, w, shift_idx, alive=None: (tree, w)
    plane_mix = gossip_plane_lane(part, M, ax, shifts,
                                  use_pallas=use_pallas,
                                  interpret=interpret)

    def mix(tree, w, shift_idx, alive=None):
        with jax.named_scope("pack"):
            plane = part.pack(tree)
        plane, w = plane_mix(plane, w, shift_idx, alive=alive)
        with jax.named_scope("unpack"):
            return part.unpack(plane), w

    return mix


def gossip_lane_legacy(part: LayerPartition, M: int, ax,
                       shifts: Sequence[int]):
    """The pre-flat-plane gossip lane: re-packs every layer group with
    ``ravel_pytree`` on EVERY step and ships a blanket-f32 wire. Kept as
    the baseline side of ``benchmarks/gossip_path.py`` and behind the
    decoupled builders' ``flat=False`` escape hatch (which also retains
    per-leaf model-axis sharding of the parameters — the flat plane
    replicates them over 'model', see DESIGN.md §11). Returns
    ``mix(tree, w, shift_idx) -> (tree, w)``; the identity when M == 1."""
    if M == 1:
        return lambda tree, w, shift_idx, alive=None: (tree, w)

    def mix(tree, w, shift_idx, alive=None):
        if alive is not None:
            raise ValueError("membership needs the flat plane (flat=True)")
        groups = part.split(tree)
        packed, unravel = {}, {}
        for name, sub in groups.items():
            packed[name], unravel[name] = ravel_pytree(
                jax.tree.map(lambda v: v.astype(jnp.float32), sub))

        recv, w_keep, rw, _ = _ring_exchange(packed, w, shift_idx, M, ax,
                                             shifts)
        new_w = w_keep + rw
        mixed_groups = {}
        for name, mine in packed.items():
            mixed = (w_keep * mine + rw * recv[name]) / new_w
            mixed_groups[name] = jax.tree.map(
                lambda x, ref: x.astype(ref.dtype),
                unravel[name](mixed), groups[name])
        return part.join(mixed_groups), new_w

    return mix


# ---------------------------------------------------------------------------
# DDP train step (baseline)
# ---------------------------------------------------------------------------


def make_ddp_train_step(model: Model, mesh, optimizer: Optimizer,
                        schedule: Callable, shape: ShapeConfig,
                        overrides: Optional[Dict[str, Any]] = None,
                        preset: Optional[str] = None) -> ProdStep:
    cfg = model.cfg
    fwd = forward_lane(model.loss_fn)
    upd = backward_update_lane(optimizer, schedule)

    def step(params, opt_state, batch, step_idx):
        loss, grads = fwd(params, batch)
        params, opt_state, _, _, _ = upd(params, opt_state, grads, (),
                                         step_idx)
        return params, opt_state, loss

    p_sh = SH.param_shardings(model, mesh, overrides=overrides,
                              preset=preset)
    abstract_params = model.abstract_params()
    abstract_opt = jax.eval_shape(optimizer.init, abstract_params)
    opt_sh = _opt_shardings(optimizer, abstract_params, p_sh, mesh)
    batch_abs = _abstract_batch(cfg, shape)
    b_sh = SH.batch_shardings(batch_abs, mesh, overrides=overrides,
                              preset=preset)
    scalar = NamedSharding(mesh, P())
    fn = jax.jit(step,
                 in_shardings=(p_sh, opt_sh, b_sh, scalar),
                 out_shardings=(p_sh, opt_sh, scalar),
                 donate_argnums=(0, 1))
    abstract = (abstract_params, abstract_opt, batch_abs,
                jax.ShapeDtypeStruct((), jnp.int32))
    return ProdStep(fn, abstract, "ddp train")


def _param_path_index(abstract_params, per_param):
    """{param tree-path → (shape, per-param value)} for suffix matching."""
    flat_p, _ = jax.tree_util.tree_flatten_with_path(abstract_params)
    vals = jax.tree.leaves(per_param)
    return {jax.tree_util.keystr(path): (leaf.shape, v)
            for (path, leaf), v in zip(flat_p, vals)}


def _match_param(path, leaf, index):
    """Optimizer states nest the param tree under wrapper keys ("mu"/"nu"
    slots, etc.): match the longest tree-path *suffix* that names a param of
    the same shape. Keying by path (not leaf.shape) keeps two identically
    shaped params with different shardings from colliding (last-wins)."""
    for i in range(len(path)):
        hit = index.get(jax.tree_util.keystr(path[i:]))
        if hit is not None and hit[0] == leaf.shape:
            return hit[1]
    return None


def _opt_shardings(optimizer, abstract_params, p_sh, mesh):
    """Optimizer-state shardings: leaves whose tree path mirrors a param
    path (module-prefix-stripped) get that param's sharding; the rest are
    replicated."""
    abstract_opt = jax.eval_shape(optimizer.init, abstract_params)
    index = _param_path_index(abstract_params, p_sh)
    flat_o, treedef = jax.tree_util.tree_flatten_with_path(abstract_opt)
    out = []
    for path, leaf in flat_o:
        sh = _match_param(path, leaf, index)
        if sh is None:
            sh = NamedSharding(mesh, P(*([None] * len(leaf.shape))))
        out.append(sh)
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# LayUp train step (the paper, production form)
# ---------------------------------------------------------------------------


def make_layup_train_step(model: Model, mesh, optimizer: Optimizer,
                          schedule: Callable, shape: ShapeConfig,
                          shifts: Sequence[int] = (1, 2, 4, 8),
                          overrides: Optional[Dict[str, Any]] = None,
                          preset: Optional[str] = None,
                          accum_steps: int = 1,
                          constrain_grads: bool = False,
                          use_pallas: bool = False) -> ProdStep:
    cfg = model.cfg
    worker_axes = data_axes(mesh)
    # per-leaf model-axis specs (worker prefix stripped) — used to pin the
    # gradients to the parameter sharding so GSPMD reduce-scatters instead
    # of all-reduce+slice (§Perf iteration A3)
    rules_g = SH.rules_for(mesh, overrides, preset)
    from repro.models.layers import is_spec
    grad_specs = jax.tree.map(
        lambda sp: SH.spec_for_axes(tuple(sp.axes), rules_g, mesh,
                                    tuple(sp.shape)),
        model.specs, is_leaf=is_spec)
    ax = worker_axes if len(worker_axes) > 1 else worker_axes[0]
    M = num_workers(mesh)
    shifts = tuple(s % M for s in shifts if s % M != 0) or (1,)

    # layer-group partition shared with the sim backend's v2 hooks: gossip
    # messages are layer groups, not loose leaves (DESIGN.md §1/§2). The
    # FlatPartition layout makes each group ONE wire buffer in the params'
    # dtype (DESIGN.md §11).
    part = FlatPartition(model.abstract_params())
    fwd = forward_lane(model.loss_fn, accum_steps=accum_steps,
                       grad_specs=grad_specs if constrain_grads else None)
    upd = backward_update_lane(optimizer, schedule)
    mix = gossip_lane(part, M, ax, shifts, use_pallas=use_pallas)

    def worker_fn(params_st, opt_st, w_st, batch, step_idx, shift_idx):
        params = jax.tree.map(lambda x: x[0], params_st)
        opt_state = jax.tree.map(
            lambda x: x[0] if x.ndim >= 1 else x, opt_st)
        w = w_st[0]
        loss, grads = fwd(params, batch)
        params, opt_state, _, _, _ = upd(params, opt_state, grads, (),
                                         step_idx)
        params, w = mix(params, w, shift_idx)
        loss = jax.lax.pmean(loss, worker_axes)
        restack = lambda t: jax.tree.map(lambda x: x[None], t)
        return (restack(params), restack(opt_state), w[None], loss)

    pw = P(worker_axes if len(worker_axes) > 1 else worker_axes[0])
    abstract_params = model.abstract_params()
    stacked_params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((M,) + s.shape, s.dtype),
        abstract_params)
    abstract_opt_single = jax.eval_shape(optimizer.init, abstract_params)
    stacked_opt = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((M,) + s.shape, s.dtype),
        abstract_opt_single)
    opt_specs = jax.tree.map(lambda _: pw, abstract_opt_single)

    batch_specs_sm = jax.tree.map(_worker_batch_pspec(ax),
                                  _abstract_batch(cfg, shape))
    fn_sm = shard_map(
        worker_fn, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: pw, abstract_params), opt_specs,
                  pw, batch_specs_sm, P(), P()),
        out_specs=(jax.tree.map(lambda _: pw, abstract_params), opt_specs,
                   pw, P()),
        axis_names=set(worker_axes))

    # model-axis sharding flows in through jit in_shardings (auto axis)
    p_sh = SH.param_shardings(model, mesh, stacked_workers=M,
                              overrides=overrides, preset=preset)
    opt_sh = _opt_shardings_stacked(abstract_opt_single, abstract_params,
                                    p_sh, mesh, M)
    batch_abs = _abstract_batch(cfg, shape)
    b_sh = SH.batch_shardings(batch_abs, mesh, overrides=overrides,
                              preset=preset)
    w_sh = NamedSharding(mesh, pw)
    scalar = NamedSharding(mesh, P())

    fn = jax.jit(fn_sm,
                 in_shardings=(p_sh, opt_sh, w_sh, b_sh, scalar, scalar),
                 out_shardings=(p_sh, opt_sh, w_sh, scalar),
                 donate_argnums=(0, 1, 2))
    abstract = (stacked_params, stacked_opt,
                jax.ShapeDtypeStruct((M,), jnp.float32), batch_abs,
                jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32))
    return ProdStep(fn, abstract, f"layup train (M={M}, shifts={shifts})")


def _opt_shardings_stacked(abstract_opt_single, abstract_params, p_sh, mesh, M):
    index = _param_path_index(abstract_params,
                              [s.spec for s in jax.tree.leaves(p_sh)])
    worker_part = jax.tree.leaves(p_sh)[0].spec[0]  # ('pod','data') part
    flat_o, treedef = jax.tree_util.tree_flatten_with_path(
        abstract_opt_single)
    out = []
    for path, leaf in flat_o:
        spec = _match_param(path, leaf, index)
        if spec is None:
            spec = P(worker_part, *([None] * len(leaf.shape)))
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Decoupled LayUp train step (PD-ASGD execution, production form)
# ---------------------------------------------------------------------------


def _decoupled_worker_fn(part: LayerPartition, fwd: Callable, upd: Callable,
                         mix: Callable, M: int, worker_axes, D: int,
                         squeeze_batch: bool = False,
                         active_fn: Optional[Callable] = None,
                         flat: bool = False,
                         fused_mix: Optional[Callable] = None,
                         wire: str = "param",
                         compensate: float = 0.0,
                         membership: bool = False):
    """Per-worker decoupled step body (traced inside shard_map).

    Arguments arrive worker-stacked with a leading axis of 1 (the shard):
    ``(read, write, opt, w, versions[, fifo_g, fifo_stamp][, resid]
    [, theta][, alive], batch, step_idx, shift_idx)`` — the fifo args are
    present iff ``D > 0``, the error-feedback residual plane iff
    ``wire="int8"``, the stale-θ reference plane iff ``compensate > 0``
    (DESIGN.md §14), and the per-worker 0/1 ``alive`` membership mask iff
    ``membership`` (DESIGN.md §15: a dead peer's updates are masked, its
    version clocks freeze, the gossip hop is alive-gated, and the loss is
    averaged over the live peers only). The three lanes compose: forward
    on the READ buffer, delayed
    update on the WRITE buffer, gossip on the updated write copy, then
    the per-layer-group buffer swap (read adopts each mixed group; its
    clock is stamped ``t + phi_g``).

    ``flat=True`` (the default route, DESIGN.md §11): read/write/opt/fifo
    are flat planes (``part`` is a :class:`FlatPartition`); the forward
    consumes the unpacked slice/reshape view of the read plane, gradients
    are packed ONCE right after AD, and everything downstream — FIFO,
    optimizer, gossip — runs on the plane. ``fused_mix`` (the
    ``use_pallas`` route) replaces apply-then-mix with the fused Alg. 1
    single pass; ``upd`` must then have been built with ``apply=False``."""
    phi = jnp.asarray(send_fractions(part.num_groups))
    unstack = lambda t: jax.tree.map(lambda x: x[0], t)
    unstack_opt = lambda t: jax.tree.map(
        lambda x: x[0] if x.ndim >= 1 else x, t)
    restack = lambda t: jax.tree.map(lambda x: x[None], t)
    int8 = wire == "int8"
    comp = float(compensate) > 0.0

    def worker_fn(*args):
        (read_st, write_st, opt_st, w_st, versions) = args[:5]
        i = 5
        if D > 0:
            fifo = {"g": unstack(args[5]), "stamp": args[6]}
            i = 7
        else:
            fifo = ()
        resid = None
        if int8:
            resid = unstack(args[i])
            i += 1
        theta = None
        if comp:
            theta = unstack(args[i])
            i += 1
        alive_st, a = None, None
        if membership:
            alive_st = args[i]
            a = alive_st[0]
            i += 1
        batch, step_idx, shift_idx = args[i:]
        read = unstack(read_st)
        write = unstack(write_st)
        opt_state = unstack_opt(opt_st)
        w = w_st[0]
        if squeeze_batch:  # sim-layout batches carry a leading worker axis
            batch = unstack(batch)

        # forward lane: consumes the read buffer (content = updates through
        # step t − 1 − D; never sees the write buffer mid-mutation). In
        # flat mode the read plane is unpacked into the tree view here
        # (static slices — XLA fuses them into the forward) and the
        # gradients are packed once, right out of AD.
        with jax.named_scope("unpack"):
            view = part.unpack(read) if flat else read
        loss, grads = fwd(view, batch)
        if flat:
            with jax.named_scope("pack"):
                grads = part.pack(grads)
        active = active_fn(step_idx) if active_fn is not None else None
        if fused_mix is not None:
            # fused route: the backward lane yields the update DELTAS and
            # the gossip lane folds apply+mix into one pass per group
            upd_out = upd(write, opt_state, grads, fifo, step_idx,
                          active=active, theta=theta) if comp else \
                upd(write, opt_state, grads, fifo, step_idx, active=active)
            updates, opt_state, fifo, upd_stale, skips = upd_out[:5]
            if comp:
                theta = upd_out[5]
            if membership:
                # a dead peer applies no updates (its replica is frozen
                # until donor re-sync). A SELECT, not `u·a`: an arithmetic
                # gate changes XLA's FMA contraction and breaks the
                # empty-plan bit-exactness; where(1.0, u, 0) is the
                # identity bit-for-bit
                updates = jax.tree.map(
                    lambda u: jnp.where(a > 0.0, u, jnp.zeros_like(u)),
                    updates)
            if int8:
                write, resid, w = fused_mix(write, resid, updates, w,
                                            shift_idx, alive=a)
            else:
                write, w = fused_mix(write, updates, w, shift_idx, alive=a)
        else:
            # backward/update lane: delayed gradient lands on the write
            # buffer, then the per-layer-group push-sum ring mix
            write_prev = write
            upd_out = upd(write, opt_state, grads, fifo, step_idx,
                          active=active, theta=theta) if comp else \
                upd(write, opt_state, grads, fifo, step_idx, active=active)
            write, opt_state, fifo, upd_stale, skips = upd_out[:5]
            if comp:
                theta = upd_out[5]
            if membership:
                # dead peer: params frozen until donor re-sync — a select
                # (bit-transparent when alive), never an arithmetic mask
                write = jax.tree.map(
                    lambda n, o: jnp.where(a > 0.0, n, o),
                    write, write_prev)
            if int8:
                write, resid, w = mix(write, resid, w, shift_idx, alive=a)
            else:
                write, w = mix(write, w, shift_idx, alive=a)
        # buffer swap: the read copy adopts the mixed write copy and each
        # group clock is stamped with its generation time t + phi_g. In the
        # real async system this is a per-group pointer flip as each
        # delayed gradient lands mid-backward; in the jitted step the swap
        # is the state carry (read == write at every step boundary — all
        # numeric staleness lives in the gradient FIFO, which is what keeps
        # R=1/D=0 exactly equal to the sim trainer). On the ring every
        # worker receives every step; with M == 1 nothing is received.
        with jax.named_scope("clock"):
            read = write
            if M > 1:
                stamped = stamp_groups(versions,
                                       step_idx.astype(jnp.float32) + phi)
                # a dead peer's clocks freeze at its last live generation —
                # the serving health gate keys off this (DESIGN.md §15)
                versions = stamped if not membership else jnp.where(
                    a > 0.0, stamped, versions)
            if membership:
                # loss over the live peers only (a dead peer's forward output
                # is meaningless); with every peer alive this is bitwise
                # pmean: psum(loss·1.0)/psum(1.0) == psum(loss)/M
                loss = (jax.lax.psum(loss * a, worker_axes)
                        / jax.lax.psum(a, worker_axes))
            else:
                loss = jax.lax.pmean(loss, worker_axes)
            # skips differ per worker (one peer's NaN is everyone's metric):
            # psum so the P() out spec is sound
            skips = jax.lax.psum(skips, worker_axes)
        outs = [restack(read), restack(write), restack(opt_state), w[None],
                versions]
        if D > 0:
            outs += [restack(fifo["g"]), fifo["stamp"]]
        if int8:
            outs += [restack(resid)]
        if comp:
            outs += [restack(theta)]
        if membership:
            outs += [alive_st]
        return tuple(outs) + (loss, upd_stale, skips)

    return worker_fn


def make_decoupled_state(params_stacked, optimizer, *, update_delay: int = 0,
                         part: Optional[LayerPartition] = None,
                         flat: bool = True, wire: str = "param",
                         compensate: float = 0.0,
                         membership: bool = False):
    """Initial step state for the decoupled lane.

    ``read`` and ``write`` start as identical copies. Both are fresh
    buffers (the step donates its state, so it must not alias the caller's
    ``params_stacked``, and read/write must not alias each other); the
    gradient FIFO holds zeros with stamp −1 (warm-up no-ops).

    With ``flat=True`` (the default — must match the step builder's flag)
    this is THE pack: params are packed into the persistent per-group
    plane here, once, and never repacked again — the step carries, mixes
    and donates the plane itself; the optimizer state and the gradient
    FIFO are allocated directly in plane layout (DESIGN.md §11).

    ``wire="int8"`` adds the zero-initialized error-feedback residual
    plane (``state["resid"]``, plane dtype); ``compensate > 0`` adds the
    stale-θ reference plane (``state["theta"]``, a copy of the initial
    params — the θ_prev of step 0); ``membership`` adds the per-worker
    0/1 ``alive`` mask (all ones — the chaos controller mutates it at
    fault events, DESIGN.md §15). All are flat-plane machinery and
    require ``flat=True``."""
    M = jax.tree_util.tree_leaves(params_stacked)[0].shape[0]
    single = jax.tree.map(lambda x: x[0], params_stacked)
    D = int(update_delay)
    if (wire == "int8" or float(compensate) > 0.0 or membership) \
            and not flat:
        raise ValueError("wire='int8' / compensate / membership need the "
                         "flat plane (flat=True)")
    if flat:
        if part is None:
            part = FlatPartition(single)
        elif not isinstance(part, FlatPartition):
            raise ValueError("flat=True needs a FlatPartition")
        # one pack, two copies: read and write must not alias each other
        # (the step donates both) nor the caller's buffers — jnp.copy
        # also guards the single-leaf-group case where pack's reshape can
        # be the identity
        plane = part.pack(params_stacked)
        read = jax.tree.map(jnp.copy, plane)
        state = {
            "read": read,
            "write": jax.tree.map(jnp.copy, plane),
            "opt": jax.vmap(optimizer.init)(read),
            "w": jnp.full((M,), 1.0 / M, jnp.float32),
            "versions": part.init_versions(M),
        }
        if D > 0:
            state["fifo"] = fifo_init(part.pack(single), D, M)
        if wire == "int8":
            state["resid"] = jax.tree.map(jnp.zeros_like, plane)
        if float(compensate) > 0.0:
            state["theta"] = jax.tree.map(jnp.copy, plane)
        if membership:
            state["alive"] = jnp.ones((M,), jnp.float32)
        return state
    part = part or LayerPartition(single)
    state = {
        "read": jax.tree.map(jnp.copy, params_stacked),
        "write": jax.tree.map(jnp.copy, params_stacked),
        "opt": jax.vmap(optimizer.init)(params_stacked),
        "w": jnp.full((M,), 1.0 / M, jnp.float32),
        "versions": part.init_versions(M),
    }
    if D > 0:
        state["fifo"] = fifo_init(single, D, M)
    return state


def _decoupled_metrics(w, versions, loss, upd_stale, step_idx, skips=None,
                       alive=None):
    with jax.named_scope("clock"):
        out = {"loss": loss, "update_staleness": upd_stale,
               "weight_sum": jnp.sum(w)}
        if skips is not None:
            out["nonfinite_skips"] = skips
        if alive is not None:
            out["peers_live"] = jnp.sum(alive)
        out.update(version_metrics(versions, step_idx))
        return out


def _check_wire(wire: str, compensate: float, flat: bool,
                membership: bool = False) -> None:
    """Shared validation for the quantized-wire / delay-compensation /
    membership knobs (all flat-plane machinery — DESIGN.md §14/§15)."""
    if wire not in ("param", "int8"):
        raise ValueError(f"unknown wire dtype {wire!r} "
                         "(expected 'param' or 'int8')")
    if float(compensate) < 0.0:
        raise ValueError("compensate (λ) must be >= 0")
    if (wire == "int8" or float(compensate) > 0.0 or membership) \
            and not flat:
        raise ValueError("wire='int8' / compensate > 0 / faults need the "
                         "flat plane (flat=True)")


def _decoupled_state_specs(D: int, pw, wire: str = "param",
                           compensate: float = 0.0,
                           membership: bool = False):
    """shard_map specs for the flattened decoupled state
    (read, write, opt, w, versions[, fifo_g, fifo_stamp][, resid]
    [, theta][, alive])."""
    extra = (int(wire == "int8") + int(float(compensate) > 0.0)
             + int(membership))
    return [pw] * 5 + ([pw, P()] if D > 0 else []) + [pw] * extra


def _decoupled_step_caller(fn_sm, D: int, wire: str = "param",
                           compensate: float = 0.0,
                           membership: bool = False):
    """Adapt the flat shard_map'd worker fn to the dict state + metrics
    step signature shared by both decoupled entry points."""
    int8 = wire == "int8"
    comp = float(compensate) > 0.0

    def step(state, batch, step_idx, shift_idx):
        args = [state["read"], state["write"], state["opt"], state["w"],
                state["versions"]]
        if D > 0:
            args += [state["fifo"]["g"], state["fifo"]["stamp"]]
        if int8:
            args += [state["resid"]]
        if comp:
            args += [state["theta"]]
        if membership:
            args += [state["alive"]]
        outs = fn_sm(*args, batch, step_idx, shift_idx)
        read, write, opt, w, versions = outs[:5]
        loss, upd_stale, skips = outs[-3:]
        new_state = {"read": read, "write": write, "opt": opt, "w": w,
                     "versions": versions}
        i = 5
        if D > 0:
            new_state["fifo"] = {"g": outs[5], "stamp": outs[6]}
            i = 7
        if int8:
            new_state["resid"] = outs[i]
            i += 1
        if comp:
            new_state["theta"] = outs[i]
            i += 1
        alive = None
        if membership:
            new_state["alive"] = alive = outs[i]
            i += 1
        return new_state, _decoupled_metrics(w, versions, loss, upd_stale,
                                             step_idx, skips=skips,
                                             alive=alive)

    return step


def make_layup_decoupled_train_step(model: Model, mesh, optimizer: Optimizer,
                                    schedule: Callable, shape: ShapeConfig,
                                    shifts: Sequence[int] = (1, 2, 4, 8),
                                    overrides: Optional[Dict[str, Any]] = None,
                                    preset: Optional[str] = None,
                                    fb_ratio: int = 2,
                                    update_delay: int = 1,
                                    constrain_grads: bool = False,
                                    flat: bool = True,
                                    use_pallas: bool = False,
                                    wire: str = "param",
                                    compensate: float = 0.0,
                                    membership: bool = False) -> ProdStep:
    """The paper's decoupled execution on the real mesh.

    Step signature: ``fn(state, batch, step_idx, shift_idx) -> (state,
    metrics)`` where ``state`` is the dict built by
    :func:`make_decoupled_state` (double-buffered params + opt state +
    push-sum weights + per-group version clocks + D-deep gradient FIFO) and
    ``metrics`` carries loss / update_staleness / layer_staleness /
    staleness_mean / weight_sum — the same accounting the sim trainer
    reports, so sim-vs-prod parity is assertable key by key.

    ``flat=True`` (default): the state's parameter buffers are the
    persistent per-group flat plane (packed once in
    :func:`make_decoupled_state`) — gossip ships the plane directly in
    the params' dtype, no per-step ravel/unravel, and the plane is
    replicated over the 'model' axis (per-leaf tensor-parallel param
    sharding needs ``flat=False`` — DESIGN.md §11). ``use_pallas`` routes
    mix+apply through the fused ``gossip_mix`` kernel
    (:func:`gossip_fused_lane`; Alg. 1 ordering).

    ``wire="int8"`` quantizes the gossip wire with an error-feedback
    residual plane carried in the state; ``compensate=λ > 0`` turns on
    the staleness-aware delay compensation in the backward lane
    (DESIGN.md §14); ``membership`` compiles the fault-tolerant
    alive-gated lane (per-worker ``alive`` mask in the state, live-set
    push-sum renormalization, frozen dead-peer clocks — DESIGN.md §15).
    All require ``flat=True``."""
    cfg = model.cfg
    worker_axes = data_axes(mesh)
    ax = worker_axes if len(worker_axes) > 1 else worker_axes[0]
    M = num_workers(mesh)
    R, D = int(fb_ratio), int(update_delay)
    if shape.global_batch % (M * max(R, 1)):
        raise ValueError(
            f"global_batch={shape.global_batch} must divide by "
            f"M*R={M}*{R} for the decoupled forward lane")
    shifts = tuple(s % M for s in shifts if s % M != 0) or (1,)

    grad_specs = None
    if constrain_grads:
        rules_g = SH.rules_for(mesh, overrides, preset)
        from repro.models.layers import is_spec
        grad_specs = jax.tree.map(
            lambda sp: SH.spec_for_axes(tuple(sp.axes), rules_g, mesh,
                                        tuple(sp.shape)),
            model.specs, is_leaf=is_spec)

    if use_pallas and not flat:
        raise ValueError("use_pallas requires the flat plane (flat=True)")
    _check_wire(wire, compensate, flat, membership)
    part = FlatPartition(model.abstract_params())
    fwd = forward_lane(model.loss_fn, fb_ratio=R, grad_specs=grad_specs)
    upd = backward_update_lane(optimizer, schedule, update_delay=D,
                               apply=not use_pallas, compensate=compensate)
    if use_pallas:
        mix, fused = None, gossip_fused_lane(part, M, ax, shifts, wire=wire)
    elif flat:
        mix, fused = gossip_plane_lane(part, M, ax, shifts, wire=wire), None
    else:
        mix, fused = gossip_lane_legacy(part, M, ax, shifts), None
    worker_fn = _decoupled_worker_fn(part, fwd, upd, mix, M, worker_axes, D,
                                     flat=flat, fused_mix=fused, wire=wire,
                                     compensate=compensate,
                                     membership=membership)

    pw = P(ax)
    abstract_params = model.abstract_params()
    stack = lambda s: jax.ShapeDtypeStruct((M,) + tuple(s.shape), s.dtype)
    abstract_opt_base = part.abstract_plane() if flat else abstract_params
    if flat:
        stacked_params = part.abstract_plane((M,))
        fifo_g_abs = part.abstract_plane((M, D))
    else:
        stacked_params = jax.tree.map(stack, abstract_params)
        fifo_g_abs = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((M, D) + tuple(s.shape), s.dtype),
            abstract_params)
    abstract_opt_single = jax.eval_shape(optimizer.init, abstract_opt_base)
    stacked_opt = jax.tree.map(stack, abstract_opt_single)
    abstract_state = {
        "read": stacked_params,
        "write": stacked_params,
        "opt": stacked_opt,
        "w": jax.ShapeDtypeStruct((M,), jnp.float32),
        "versions": jax.ShapeDtypeStruct((M, part.num_groups), jnp.float32),
    }
    if D > 0:
        abstract_state["fifo"] = {
            "g": fifo_g_abs,
            "stamp": jax.ShapeDtypeStruct((D,), jnp.float32),
        }
    if wire == "int8":
        abstract_state["resid"] = stacked_params
    if float(compensate) > 0.0:
        abstract_state["theta"] = stacked_params
    if membership:
        abstract_state["alive"] = jax.ShapeDtypeStruct((M,), jnp.float32)

    batch_specs_sm = jax.tree.map(_worker_batch_pspec(ax),
                                  _abstract_batch(cfg, shape))
    state_specs = _decoupled_state_specs(D, pw, wire, compensate,
                                         membership)
    fn_sm = shard_map(
        worker_fn, mesh=mesh,
        in_specs=tuple(state_specs + [batch_specs_sm, P(), P()]),
        out_specs=tuple(state_specs + [P(), P(), P()]),
        axis_names=set(worker_axes))
    step = _decoupled_step_caller(fn_sm, D, wire, compensate, membership)

    w_sh = NamedSharding(mesh, pw)
    scalar = NamedSharding(mesh, P())
    if flat:
        # the flat plane carries only the worker axis: buffers are
        # replicated over 'model' (per-leaf TP sharding needs flat=False)
        worker_only = lambda tree: jax.tree.map(
            lambda _: w_sh, tree)
        p_sh = worker_only(stacked_params)
        opt_sh = worker_only(stacked_opt)
        fifo_g_sh = worker_only(fifo_g_abs) if D > 0 else None
    else:
        # model-axis sharding flows in through jit in_shardings (auto axis)
        p_sh = SH.param_shardings(model, mesh, stacked_workers=M,
                                  overrides=overrides, preset=preset)
        opt_sh = _opt_shardings_stacked(abstract_opt_single, abstract_params,
                                        p_sh, mesh, M)
        if D > 0:
            # FIFO leaves insert the depth axis after the worker axis
            fifo_g_sh = jax.tree.map(
                lambda s: NamedSharding(
                    mesh, P(s.spec[0], None, *tuple(s.spec)[1:])), p_sh)
    state_sh = {"read": p_sh, "write": p_sh, "opt": opt_sh, "w": w_sh,
                "versions": w_sh}
    if D > 0:
        state_sh["fifo"] = {"g": fifo_g_sh, "stamp": scalar}
    if wire == "int8":
        state_sh["resid"] = p_sh
    if float(compensate) > 0.0:
        state_sh["theta"] = p_sh
    if membership:
        state_sh["alive"] = w_sh
    metrics_sh = {"loss": scalar, "update_staleness": scalar,
                  "layer_staleness": scalar, "staleness_mean": scalar,
                  "weight_sum": scalar, "nonfinite_skips": scalar}
    if membership:
        metrics_sh["peers_live"] = scalar
    batch_abs = _abstract_batch(cfg, shape)
    b_sh = SH.batch_shardings(batch_abs, mesh, overrides=overrides,
                              preset=preset)
    fn = jax.jit(step,
                 in_shardings=(state_sh, b_sh, scalar, scalar),
                 out_shardings=(state_sh, metrics_sh),
                 donate_argnums=(0,))
    abstract = (abstract_state, batch_abs,
                jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32))
    return ProdStep(fn, abstract,
                    f"layup decoupled train (M={M}, R={R}, D={D}, "
                    f"shifts={shifts}, flat={flat}"
                    f"{', pallas' if use_pallas else ''}"
                    f"{', wire=int8' if wire == 'int8' else ''}"
                    f"{f', comp={compensate}' if compensate else ''}"
                    f"{', membership' if membership else ''})")


def straggler_active_fn(mesh, straggler_delays) -> Optional[Callable]:
    """Per-worker 0/1 activity mask from a straggler-delay vector:
    ``straggler_delays[i] = d`` makes worker ``i`` active every ``d + 1``
    steps. Traced inside the shard_map body (uses ``axis_index``); shared
    by the monolithic decoupled step and the pipeline engine's update
    stage. Returns ``None`` when no delays are given."""
    if straggler_delays is None:
        return None
    worker_axes = data_axes(mesh)
    delays_c = jnp.asarray(np.asarray(straggler_delays), jnp.int32)
    sizes = [mesh.shape[a] for a in worker_axes]

    def active_fn(step_idx):
        idx = jnp.zeros((), jnp.int32)
        for a, n in zip(worker_axes, sizes):
            idx = idx * n + jax.lax.axis_index(a)
        return (jnp.mod(step_idx, delays_c[idx] + 1) == 0).astype(
            jnp.float32)

    return active_fn


def make_decoupled_backend_trainer(loss_fn: Callable, optimizer: Optimizer,
                                   schedule: Callable, mesh, *,
                                   shifts: Sequence[int] = (1, 2, 4, 8),
                                   fb_ratio: int = 1, update_delay: int = 0,
                                   straggler_delays=None,
                                   measure_drift: bool = False,
                                   flat: bool = True,
                                   use_pallas: bool = False,
                                   publisher=None,
                                   wire: str = "param",
                                   compensate: float = 0.0,
                                   membership: bool = False):
    """Decoupled LayUp over a generic pytree + loss_fn (no Model/ShapeConfig)
    — the engine behind the ``"prod"`` TrainerBackend (core/backend.py).

    Batches use the sim layout: every leaf carries a leading ``(M,)`` worker
    axis, so the same data pipeline drives the sim and prod backends.
    ``straggler_delays[i] = d`` makes worker ``i`` apply its local update
    only every ``d + 1`` steps (it still gossips and receives, paper §5.4)
    — the numeric analogue of the sim backend's straggler mask.
    ``measure_drift`` adds the ``disagreement`` metric, computed inside the
    jitted step like the sim trainer does.

    ``publisher`` (a :class:`repro.serving.PlanePublisher`) receives the
    read plane + version clocks + drift once per gossip round (= per
    step), the training side of the train-and-serve path (DESIGN.md §12).
    This step is jitted with ``donate_argnums=(0,)`` — the state the
    publisher sees IS donated on the next call — so the publish is marked
    ``stable=False`` and the publisher stabilizes the plane with async
    device copies (still no checkpoint round-trip; the pipeline engine's
    publish path is the zero-copy one). Requires ``flat=True``.

    Returns ``(init_fn, step_fn, shifts, box)``: ``init_fn(rng,
    params_single) -> state``, ``step_fn(state, batch, step_idx,
    shift_idx) -> (state, metrics)``, the effective (mod-M-filtered)
    gossip shift set the caller draws ``shift_idx`` from, and the build
    box (``box["part"]`` holds the FlatPartition once ``init_fn`` has
    seen the params — the unpack key for exporting the flat state)."""
    worker_axes = data_axes(mesh)
    ax = worker_axes if len(worker_axes) > 1 else worker_axes[0]
    M = num_workers(mesh)
    R, D = int(fb_ratio), int(update_delay)
    shifts = tuple(s % M for s in shifts if s % M != 0) or (1,)
    active_fn = straggler_active_fn(mesh, straggler_delays)
    part_box = {}

    if use_pallas and not flat:
        raise ValueError("use_pallas requires the flat plane (flat=True)")
    if publisher is not None and not flat:
        raise ValueError("publisher needs the flat plane (flat=True): the "
                         "legacy tree state has no per-group plane to "
                         "publish")
    _check_wire(wire, compensate, flat, membership)

    def build(params_single):
        part = FlatPartition(params_single)
        fwd = forward_lane(loss_fn, fb_ratio=R)
        upd = backward_update_lane(optimizer, schedule, update_delay=D,
                                   apply=not use_pallas,
                                   compensate=compensate)
        if use_pallas:
            mix, fused = None, gossip_fused_lane(part, M, ax, shifts,
                                                 wire=wire)
        elif flat:
            mix, fused = gossip_plane_lane(part, M, ax, shifts,
                                           wire=wire), None
        else:
            mix, fused = gossip_lane_legacy(part, M, ax, shifts), None
        worker_fn = _decoupled_worker_fn(part, fwd, upd, mix, M, worker_axes,
                                         D, squeeze_batch=True,
                                         active_fn=active_fn, flat=flat,
                                         fused_mix=fused, wire=wire,
                                         compensate=compensate,
                                         membership=membership)
        pw = P(ax)
        state_specs = _decoupled_state_specs(D, pw, wire, compensate,
                                             membership)
        fn_sm = shard_map(worker_fn, mesh=mesh,
                          in_specs=tuple(state_specs + [pw, P(), P()]),
                          out_specs=tuple(state_specs + [P(), P(), P()]),
                          axis_names=set(worker_axes))
        base_step = _decoupled_step_caller(fn_sm, D, wire, compensate,
                                           membership)

        def step(state, batch, step_idx, shift_idx):
            new_state, metrics = base_step(state, batch, step_idx, shift_idx)
            if measure_drift:
                from repro.core.api import disagreement
                metrics["disagreement"] = disagreement(new_state["read"],
                                                       new_state["w"])
            return new_state, metrics

        return jax.jit(step, donate_argnums=(0,)), part

    def init_fn(rng, params_single):
        del rng
        stacked = jax.tree.map(
            lambda p: jnp.broadcast_to(p[None], (M,) + p.shape),
            params_single)
        if "step" not in part_box:
            part_box["step"], part_box["part"] = build(params_single)
        return make_decoupled_state(stacked, optimizer, update_delay=D,
                                    part=part_box["part"], flat=flat,
                                    wire=wire, compensate=compensate,
                                    membership=membership)

    def step_fn(state, batch, step_idx, shift_idx):
        if "step" not in part_box:
            raise RuntimeError("call init_fn before step_fn")
        new_state, metrics = part_box["step"](
            state, batch, jnp.asarray(step_idx, jnp.int32),
            jnp.asarray(shift_idx, jnp.int32))
        if publisher is not None:
            # stable=False: this jitted step donates its input state, so
            # the read plane the publisher pins here is consumed on the
            # NEXT step_fn call — the publisher copies it (async, on
            # device) before handing it to serving consumers
            publisher.publish(new_state["read"], new_state["versions"],
                              new_state["w"], int(step_idx),
                              drift=metrics.get("disagreement"),
                              stable=False)
        return new_state, metrics

    return init_fn, step_fn, shifts, part_box


def make_prefill_step(model: Model, mesh, shape: ShapeConfig,
                      overrides: Optional[Dict[str, Any]] = None,
                      preset: Optional[str] = None) -> ProdStep:
    cfg = model.cfg

    def step(params, batch):
        cache, logits = model.prefill_fn(params, batch)
        return cache, logits

    p_sh = SH.param_shardings(model, mesh, overrides=overrides,
                              preset=preset)
    batch_abs = _abstract_batch(cfg, shape)
    b_sh = SH.batch_shardings(batch_abs, mesh, overrides=overrides,
                              preset=preset)
    fn = jax.jit(step, in_shardings=(p_sh, b_sh))
    return ProdStep(fn, (model.abstract_params(), batch_abs), "prefill")


def make_decode_step(model: Model, mesh, shape: ShapeConfig,
                     overrides: Optional[Dict[str, Any]] = None,
                     preset: Optional[str] = None) -> ProdStep:
    cfg = model.cfg
    B = shape.global_batch

    def step(params, cache, token, position):
        logits, new_cache = model.decode_fn(params, cache, token, position)
        return logits, new_cache

    p_sh = SH.param_shardings(model, mesh, overrides=overrides,
                              preset=preset)
    cache_abs = model.cache_specs(B, shape.seq_len)
    c_sh = SH.cache_shardings(cache_abs, mesh, cfg, overrides=overrides,
                              preset=preset)
    rules = SH.rules_for(mesh, overrides, preset)
    db = rules["batch"]
    if db is not None and B % SH._axis_size(mesh, db) != 0:
        db = None  # e.g. long_500k batch=1: replicate over the data axes
    tok_sh = NamedSharding(mesh, P(db, None))
    pos_sh = NamedSharding(mesh, P(db))
    fn = jax.jit(step, in_shardings=(p_sh, c_sh, tok_sh, pos_sh),
                 donate_argnums=(1,))
    abstract = (model.abstract_params(), cache_abs,
                jax.ShapeDtypeStruct((B, 1), jnp.int32),
                jax.ShapeDtypeStruct((B,), jnp.int32))
    return ProdStep(fn, abstract, "decode")


# ---------------------------------------------------------------------------
# dispatcher
# ---------------------------------------------------------------------------


def make_step(model: Model, mesh, shape: ShapeConfig, *, algo: str = "layup",
              optimizer: Optional[Optimizer] = None,
              schedule: Optional[Callable] = None,
              overrides: Optional[Dict[str, Any]] = None,
              shifts: Sequence[int] = (1, 2, 4, 8),
              preset: Optional[str] = None,
              accum_steps: int = 1,
              constrain_grads: bool = False,
              fb_ratio: int = 1,
              update_delay: int = 0,
              overlap: bool = False,
              flat: bool = True,
              use_pallas: bool = False,
              streams: int = 1,
              wire: str = "param",
              compensate: float = 0.0,
              faults=None,
              max_inflight_steps: Optional[int] = None,
              tuning=None) -> ProdStep:
    """``overlap=True`` selects the stage-graph pipeline engine
    (repro.launch.pipeline): the decoupled lane compiled into separately
    jitted fwd-slice / bwd+update / gossip stages dispatched asynchronously
    from the host, instead of one monolithic jitted step. Numerics are
    identical (the monolithic path stays as the oracle — DESIGN.md §10);
    only the dispatch schedule and the per-stage timestamps differ.

    ``streams`` (with ``overlap=True``): > 1 runs those stages on
    per-stage execution streams with the gossip stage split per layer
    group behind one-sided signals (repro.launch.streams, DESIGN.md §13)
    — measured execution overlap in the timeline, same numerics.

    ``flat`` (decoupled lanes, default True) keeps the parameters as the
    persistent per-group flat plane — param-dtype gossip wire, zero
    per-step repack (DESIGN.md §11); ``flat=False`` restores the legacy
    tree state + per-step f32 ravel (and per-leaf TP param sharding).
    ``use_pallas`` routes the gossip mix through the fused Pallas
    ``gossip_mix`` kernel (interpret mode off-TPU).

    ``wire="int8"`` (decoupled lanes, flat only) quantizes the gossip
    wire to int8 with error-feedback residuals — ~0.52× the bf16 wire
    bytes; ``compensate=λ > 0`` adds the staleness-aware delay
    compensation ``g + λ·g⊙g⊙(θ_now − θ_stale)`` in the backward lane
    (λ = 0.5 is the documented default when turning it on —
    DESIGN.md §14).

    ``faults`` (a :class:`repro.chaos.FaultPlan` or spec string,
    decoupled lanes, flat only) compiles the fault-tolerant membership
    lane (per-worker ``alive`` mask, live-set push-sum renormalization —
    DESIGN.md §15) and attaches a ``ChaosController`` for the plan on
    the returned step (``.chaos``); an empty plan enables the machinery
    without injecting anything.

    ``tuning`` (a :class:`repro.launch.tuner.TuningRecord` or a path to
    its JSON) replaces the hand-picked schedule defaults with the
    autotuned ones (DESIGN.md §16): any of ``fb_ratio``/``update_delay``/
    ``flat``/``max_inflight_steps`` still at its documented default takes
    the record's best candidate (explicit kwargs always win), and a
    loaded record implies ``overlap=True`` — the record tunes the stage
    schedule. A missing/corrupt/stale/mismatched record warns and leaves
    every default untouched, never raises."""
    from repro.optim import momentum, constant
    optimizer = optimizer or momentum(0.9, state_dtype=model.cfg.dtype)
    schedule = schedule or constant(0.1)
    if tuning is not None:
        from repro.launch.tuner import apply_tuning, resolve_tuning
        record = resolve_tuning(tuning)
        if record is not None:
            tuned = apply_tuning(record, fb_ratio=fb_ratio,
                                 update_delay=update_delay, flat=flat,
                                 max_inflight_steps=max_inflight_steps)
            fb_ratio = tuned["fb_ratio"]
            update_delay = tuned["update_delay"]
            flat = tuned["flat"]
            max_inflight_steps = tuned["max_inflight_steps"]
            overlap = True
    decoupled = fb_ratio > 1 or update_delay > 0 or overlap
    membership = faults is not None
    if streams > 1 and not overlap:
        raise ValueError("streams > 1 is a property of the stage-graph "
                         "pipeline; it requires overlap=True")
    _check_wire(wire, compensate, flat, membership)
    if (wire != "param" or float(compensate) > 0.0 or membership) \
            and not decoupled:
        raise ValueError("wire='int8' / compensate > 0 / faults belong to "
                         "the decoupled LayUp lane (fb_ratio/update_delay/"
                         "overlap)")
    if decoupled and (shape.kind != "train" or algo == "ddp"):
        raise ValueError(
            "fb_ratio/update_delay/overlap define the decoupled LayUp lane; "
            f"they do not apply to algo={algo!r} kind={shape.kind!r}")
    if shape.kind == "train":
        if algo == "ddp":
            return make_ddp_train_step(model, mesh, optimizer, schedule,
                                       shape, overrides, preset)
        if decoupled:
            if accum_steps > 1:
                raise ValueError(
                    "the decoupled lane does not compose with accum_steps")
            if overlap:
                from repro.launch.pipeline import make_layup_decoupled_pipeline
                step = make_layup_decoupled_pipeline(
                    model, mesh, optimizer, schedule, shape, shifts=shifts,
                    overrides=overrides, preset=preset, fb_ratio=fb_ratio,
                    update_delay=update_delay,
                    constrain_grads=constrain_grads, flat=flat,
                    use_pallas=use_pallas, streams=streams, wire=wire,
                    compensate=compensate, membership=membership,
                    max_inflight_steps=max_inflight_steps)
            else:
                step = make_layup_decoupled_train_step(
                    model, mesh, optimizer, schedule, shape, shifts,
                    overrides, preset, fb_ratio, update_delay,
                    constrain_grads, flat, use_pallas, wire, compensate,
                    membership)
            if membership:
                from repro.chaos import ChaosController
                step.chaos = ChaosController(
                    faults, num_workers(mesh), update_delay=update_delay,
                    wire=wire, compensate=compensate)
            return step
        return make_layup_train_step(model, mesh, optimizer, schedule, shape,
                                     shifts, overrides, preset, accum_steps,
                                     constrain_grads, use_pallas)
    if shape.kind == "prefill":
        return make_prefill_step(model, mesh, shape, overrides, preset)
    return make_decode_step(model, mesh, shape, overrides, preset)
