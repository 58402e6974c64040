"""Stage-graph pipeline engine for the decoupled LayUp lane (DESIGN.md §10).

The monolithic ``make_layup_decoupled_train_step`` fuses the R forward
slices, the delayed backward/update and the gossip collectives into ONE
jitted program, so on real hardware they serialize and the paper's overlap
(forward threads hiding communication/update latency — the source of the
up-to-5.95× speedups) cannot manifest. This module compiles the SAME lane
factories (``forward_slice_lane`` / ``backward_update_lane`` /
``gossip_lane`` from ``repro.launch.train``) into **separately jitted,
buffer-donating stage executables**:

    fwd-slice r   (read, batch)                  -> loss_r [, grads]
    bwd+update    (write, opt[, fifo], grads, t) -> write', opt'[, fifo'], stale
    gossip-mix    (write', w, versions,
                   losses, stale, t, s)          -> mixed, w', versions', metrics

(the gossip stage also folds the metric reduction, so one step is exactly
R + 2 dispatches — the CPU PJRT client bounds the number of in-flight
executions, and every extra executable per step is one less step of
host run-ahead before dispatch throttles)

and drives them from a host-side dispatch loop that exploits JAX **async
dispatch**: every stage call returns a future immediately, so the host can
enqueue step ``t+1``'s forward slices while step ``t``'s gossip collectives
and delayed update are still executing on the device — data dependencies
are sequenced by the runtime, not by python. Numerics are IDENTICAL to the
monolithic step (the stage bodies are the very same lane closures, split at
the same boundaries; the monolithic path remains the numerics oracle and
``tests/test_pipeline.py`` asserts loss/staleness parity at
(R, D) ∈ {(1,0), (1,1), (2,1)}).

**Buffer ownership / donation rules.** The engine manages the
double-buffered parameters instead of carrying them as step-state pytrees:

* the *read* buffer (forward input) is never donated — all R forward
  slices of a step share it;
* the update stage donates the optimizer state, the gradient FIFO and the
  incoming gradients, but NOT its parameter input: after the gossip swap
  the read and write handles alias one engine-owned buffer, and donating a
  buffer that a still-in-flight forward reads would alias a live input;
* the gossip stage donates its parameter input (the update stage's fresh
  output — sole reference), the push-sum weights and the version clocks.
  Its mixed output becomes BOTH next-step handles (read == write at every
  step boundary, exactly like the monolithic step — all numeric staleness
  lives in the gradient FIFO).

**Timestamps.** Every dispatch is recorded in a :class:`StageTimeline`
with the host dispatch time, the set of stages still in flight at that
moment (probed via non-blocking ``jax.Array.is_ready`` on a per-stage
fence output — stage executables complete atomically, so any output
serves), and the first-observed-ready completion time. Overlap is
therefore *measured*, not simulated: ``fwd_gossip_overlap_s`` sums, over
forward dispatches that found the previous step's gossip in flight, the
window between the dispatch and the gossip's completion. Completion times
are first-*observed*-ready (an upper bound — polling happens at dispatch
points and at ``finalize()``), so reported overlap is what the host
provably ran ahead of, never an extrapolation.
"""
from __future__ import annotations

import json
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.layerview import (
    FlatPartition, LayerPartition, send_fractions, stamp_groups,
)
from repro.launch.mesh import data_axes, num_workers
from repro.launch.train import (
    _abstract_batch, _check_wire, _decoupled_metrics, _opt_shardings_stacked,
    _ring_exchange, _worker_batch_pspec, backward_update_lane,
    forward_slice_lane, gossip_fused_lane, gossip_lane_legacy,
    gossip_plane_lane, make_decoupled_state, shard_map,
    straggler_active_fn,
)
from repro.launch import sharding as SH
from repro.optim.optimizers import Optimizer


# ---------------------------------------------------------------------------
# stage timeline: measured dispatch/complete timestamps + overlap accounting
# ---------------------------------------------------------------------------


def _donated(x) -> bool:
    """True for an array already consumed by a donating stage."""
    is_deleted = getattr(x, "is_deleted", None)
    return is_deleted is not None and is_deleted()


def _is_ready(x) -> bool:
    """Non-blocking readiness probe; arrays already consumed by a donating
    stage count as retired. Any other failure (a device execution that
    failed surfaces here) propagates."""
    return _donated(x) or bool(x.is_ready())


def _wait(x) -> None:
    """Block until ``x`` is computed; a donated array has retired."""
    if not _donated(x):
        jax.block_until_ready(x)


class StageTimeline:
    """Host-side record of every stage dispatch and stage execution.

    Two kinds of events share the list:

    * **dispatch events** (single-stream :class:`PipelineEngine`, via
      ``begin``/``commit``): ``{stage, step, slice, dispatch, complete,
      concurrent}``. ``dispatch`` is stamped when the host *initiates*
      the stage call, ``concurrent`` lists the ``(stage, step, slice)``
      triples whose fences were NOT ready at that moment — direct
      evidence the host ran ahead of the device — and ``complete`` is
      the first time the fence was observed ready (polled at subsequent
      dispatches and at ``finalize()``), i.e. an upper bound on the true
      completion.
    * **execution events** (:class:`~repro.launch.streams.StreamEngine`,
      via ``record_exec``, called from the stream threads): the same
      shape plus ``{stream, enqueue, exec_start, wait_s[, group]}``.
      ``[exec_start, complete]`` is a TRUE execution span — the owning
      stream thread launched the stage and blocked until its outputs
      were ready — so spans from different streams interleave exactly
      when the device executed two stages concurrently. ``dispatch`` is
      set to ``exec_start`` and ``concurrent`` to ``[]`` so the
      dispatch-level aggregations stay meaningful, and ``wait_s`` is the
      time the task spent blocked on its input signals/futures before
      launching (the signal-wait cost of the one-sided protocol)."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self._clock = clock
        self._lock = threading.Lock()
        self.events: List[Dict[str, Any]] = []
        self._pending: List[Tuple[Dict[str, Any], Any]] = []

    def begin(self, stage: str, step: int, slice_idx=None) -> Dict[str, Any]:
        """Open an event at stage-call initiation: timestamp + snapshot of
        the stages still in flight. Pair with :meth:`commit`."""
        now = self._clock()
        self.poll(now)
        concurrent = [(e["stage"], e["step"], e["slice"])
                      for e, _ in self._pending]
        ev = {"stage": stage, "step": int(step), "slice": slice_idx,
              "dispatch": now, "complete": None, "concurrent": concurrent}
        self.events.append(ev)
        return ev

    def commit(self, ev: Dict[str, Any], fence) -> None:
        """Attach the dispatched stage's fence output to its event."""
        self._pending.append((ev, fence))
        self.poll()

    def record_exec(self, stage: str, step: int, *, stream: str,
                    enqueue: Optional[float], exec_start: float,
                    complete: float, wait_s: float = 0.0,
                    slice_idx=None, group: Optional[str] = None) -> None:
        """Record one finished stage execution from a stream thread.

        Called by :class:`~repro.launch.streams.Stream` AFTER it blocked
        on the stage's outputs, so ``[exec_start, complete]`` is a closed
        execution span (no pending fence to poll). Thread-safe — stream
        threads record concurrently with the host reading ``summary``."""
        ev = {"stage": stage, "step": int(step), "slice": slice_idx,
              "dispatch": exec_start, "complete": complete,
              "concurrent": [], "stream": stream, "enqueue": enqueue,
              "exec_start": exec_start, "wait_s": float(wait_s)}
        if group is not None:
            ev["group"] = group
        with self._lock:
            self.events.append(ev)

    def poll(self, now: Optional[float] = None) -> None:
        if not self._pending:
            return
        now = self._clock() if now is None else now
        still = []
        for ev, fence in self._pending:
            if _is_ready(fence):
                ev["complete"] = now
            else:
                still.append((ev, fence))
        self._pending = still

    def finalize(self) -> None:
        """Block on every outstanding fence and close its event."""
        for ev, fence in self._pending:
            _wait(fence)
            ev["complete"] = self._clock()
        self._pending = []

    def reset(self) -> None:
        """Drop all recorded events (finalizing outstanding ones first) —
        for backends that re-init and measure a fresh run."""
        self.finalize()
        self.events = []

    def summary(self) -> Dict[str, Any]:
        """Aggregate the recorded events. Returned fields:

        * ``events`` — total events recorded (incl. still-pending ones);
          ``steps`` — ``max(step) + 1`` over closed events; ``wall_s`` —
          first dispatch to last completion.
        * ``stage_s`` — summed ``complete − dispatch`` per stage name
          (per-stage device occupancy upper bound; stages overlap, so
          the values can sum past ``wall_s``).
        * ``overlap_events`` / ``overlap_s`` — dispatch-level run-ahead:
          events whose initiation found ANY stage still in flight, and
          the summed window each provably overlapped (how far the host
          ran ahead — NOT proof of concurrent execution).
        * ``fwd_gossip_overlap_s`` — the paper's overlap: step ``t``
          forwards dispatched while step ``t−1`` gossip was in flight,
          counted once per adjacent step pair.
        * ``streams`` — distinct execution streams that recorded events
          (1 for the single-stream engine: everything shares the one
          dispatch lane).
        * ``exec_overlap_s`` — MEASURED execution concurrency: with each
          stream's ``[exec_start, complete]`` spans merged into busy
          intervals, the integral of ``(busy_streams − 1)`` over time.
          Zero unless two streams were executing at the same instant;
          same-stream pipelining never counts. This is the number the
          nightly M>1 gate asserts is positive (DESIGN.md §13).
        * ``stream_busy_s`` — per-stream merged busy time.
        * ``signal_wait_s`` — summed time stream tasks spent blocked on
          input signals/futures before launching (the wait side of the
          one-sided protocol; high values mean a starved stream)."""
        with self._lock:
            events = list(self.events)
        evs = [e for e in events if e["complete"] is not None]
        out: Dict[str, Any] = {
            "events": len(events), "steps": 0, "wall_s": 0.0,
            "overlap_events": 0, "overlap_s": 0.0,
            "fwd_gossip_overlap_s": 0.0, "stage_s": {},
            "streams": 1, "exec_overlap_s": 0.0, "stream_busy_s": {},
            "signal_wait_s": 0.0,
        }
        if not evs:
            return out
        t0 = min(e["dispatch"] for e in evs)
        out["steps"] = max(e["step"] for e in evs) + 1
        out["wall_s"] = max(e["complete"] for e in evs) - t0
        stage_s: Dict[str, float] = {}
        for e in evs:
            stage_s[e["stage"]] = (stage_s.get(e["stage"], 0.0)
                                   + e["complete"] - e["dispatch"])
        out["stage_s"] = stage_s
        index = {(e["stage"], e["step"], e["slice"]): e for e in evs}
        overlap = 0.0
        overlap_events = 0
        # the paper's overlap: step t's forward slices dispatched while
        # step t−1's gossip is still in flight. Count each gossip once,
        # from the EARLIEST forward that found it unretired, so neither
        # multiple slices nor deep run-ahead double-count the window.
        first_fwd: Dict[int, Dict[str, Any]] = {}
        for e in evs:
            window = 0.0
            for key in e["concurrent"]:
                g = index.get(tuple(key))
                if g is None or g["complete"] is None:
                    continue
                window = max(window, min(g["complete"], e["complete"])
                             - e["dispatch"])
                if (e["stage"] == "fwd" and key[0] == "gossip"
                        and key[1] == e["step"] - 1
                        and e["step"] not in first_fwd):
                    first_fwd[e["step"]] = e
            if e["concurrent"]:
                overlap_events += 1
                overlap += max(0.0, window)
        fwd_gossip = 0.0
        for t_step, e in first_fwd.items():
            g = index[("gossip", t_step - 1, None)]
            fwd_gossip += max(0.0, min(g["complete"], e["complete"])
                              - e["dispatch"])
        out["overlap_events"] = overlap_events
        out["overlap_s"] = overlap
        out["fwd_gossip_overlap_s"] = fwd_gossip

        # per-stream execution accounting (stream events only): merge each
        # stream's closed [exec_start, complete] spans into busy intervals,
        # then sweep the interval endpoints counting how many DISTINCT
        # streams are busy — exec_overlap_s integrates (busy − 1) over
        # time, so same-stream pipelining contributes nothing and the
        # value is > 0 iff two streams truly executed concurrently.
        sevs = [e for e in evs if e.get("stream")]
        if sevs:
            busy: Dict[str, List[List[float]]] = {}
            for e in sorted(sevs, key=lambda e: e["exec_start"]):
                iv = busy.setdefault(e["stream"], [])
                if iv and e["exec_start"] <= iv[-1][1]:
                    iv[-1][1] = max(iv[-1][1], e["complete"])
                else:
                    iv.append([e["exec_start"], e["complete"]])
            out["streams"] = len(busy)
            out["stream_busy_s"] = {
                n: sum(c - s for s, c in iv) for n, iv in busy.items()}
            out["signal_wait_s"] = sum(e.get("wait_s", 0.0) for e in sevs)
            edges = sorted((t, d) for iv in busy.values()
                           for s, c in iv for t, d in ((s, 1), (c, -1)))
            k, last, exec_overlap = 0, 0.0, 0.0
            for t, d in edges:
                if k > 1:
                    exec_overlap += (t - last) * (k - 1)
                k, last = k + d, t
            out["exec_overlap_s"] = exec_overlap
        return out

    def dump(self, path: str) -> str:
        """Write events (dispatch/complete relative to the first dispatch)
        plus the summary as JSON — the nightly per-stage timing artifact."""
        s = self.summary()
        with self._lock:
            snap = list(self.events)
        t0 = min((e["dispatch"] for e in snap), default=0.0)
        rel = lambda v: None if v is None else v - t0
        events = [{**e,
                   "dispatch": e["dispatch"] - t0,
                   "complete": rel(e["complete"]),
                   "concurrent": [list(c) for c in e["concurrent"]],
                   **({"enqueue": rel(e.get("enqueue")),
                       "exec_start": e["exec_start"] - t0}
                      if "stream" in e else {})}
                  for e in snap]
        with open(path, "w") as f:
            json.dump({"summary": s, "events": events}, f, indent=1)
        return path


# ---------------------------------------------------------------------------
# stage bodies (traced inside shard_map) — split at the lane boundaries
# ---------------------------------------------------------------------------


def _unstack(t):
    return jax.tree.map(lambda x: x[0], t)


def _unstack_opt(t):
    return jax.tree.map(lambda x: x[0] if x.ndim >= 1 else x, t)


def _restack(t):
    return jax.tree.map(lambda x: x[None], t)


def _stage_bodies(part: LayerPartition, R: int, D: int, M: int, worker_axes,
                  fwd_slices: Sequence[Callable], upd: Callable,
                  mix: Callable, *, squeeze_batch: bool = False,
                  active_fn: Optional[Callable] = None, flat: bool = False,
                  fused: bool = False, wire: str = "param",
                  compensate: float = 0.0, membership: bool = False):
    """Per-worker stage bodies. They compose the SAME lane closures as
    ``_decoupled_worker_fn``, split at the stage boundaries, so each
    stage's math is identical to the corresponding span of the monolithic
    body. The loss is NOT pmean'd per stage: each fwd stage returns its
    per-worker loss vector and the metrics stage combines slices first
    (monolithic order: ``(l0 + sum(rest)) / R``), then means over workers —
    bitwise-equal to ``lax.pmean`` of the per-worker combination for
    M ≤ 2, and within reduction-order noise beyond.

    ``flat``: read/write/opt/fifo are the persistent flat plane; the
    backward fwd slice packs its gradients before returning them, so the
    grads that cross the stage boundary are already plane buffers.
    ``fused`` (use_pallas): the update stage consumes the write plane
    READ-ONLY and returns the update deltas; the gossip stage takes
    (write, updates) and folds apply+mix into the fused kernel pass
    (``mix`` is then a :func:`gossip_fused_lane` closure).

    ``wire="int8"``: the gossip stage gains the error-feedback residual
    plane as an extra argument and returns its successor alongside the
    mixed plane; ``compensate > 0``: the update stage gains the stale-θ
    reference plane and returns this step's pre-update params as the
    next θ_prev (DESIGN.md §14).

    ``membership`` (DESIGN.md §15): both the update and the gossip stage
    gain the per-peer ``alive`` mask (a never-donated passthrough the
    engine threads from the chaos controller's state). Dead peers apply
    no updates, keep their version clocks frozen, and the alive-gated
    push-sum exchange conserves Σw over the live set. The update stage
    additionally returns the psum'd nonfinite-skip count (always — the
    guard is unconditional in :func:`backward_update_lane`)."""
    phi = jnp.asarray(send_fractions(part.num_groups))
    int8 = wire == "int8"
    comp = float(compensate) > 0.0

    def make_fwd_body(r):
        lane = fwd_slices[r]

        def fwd_body(read_st, batch):
            read = _unstack(read_st)
            if squeeze_batch:  # sim-layout batches carry a worker axis
                batch = _unstack(batch)
            loss, grads = lane(part.unpack(read) if flat else read, batch)
            if r == 0:
                if flat:
                    grads = part.pack(grads)
                return loss[None], _restack(grads)
            return loss[None]

        return fwd_body

    def update_body(*args):
        if D > 0:
            write_st, opt_st, fifo_g_st, fifo_stamp, grads_st = args[:5]
            rest = args[5:]
            fifo = {"g": _unstack(fifo_g_st), "stamp": fifo_stamp}
        else:
            write_st, opt_st, grads_st = args[:3]
            rest = args[3:]
            fifo = ()
        j = 0
        theta = None
        if comp:
            theta = _unstack(rest[j])
            j += 1
        alive_st = None
        if membership:
            alive_st = rest[j]
            j += 1
        step_idx = rest[-1]
        write = _unstack(write_st)
        opt_state = _unstack_opt(opt_st)
        grads = _unstack(grads_st)
        active = active_fn(step_idx) if active_fn is not None else None
        upd_out = upd(write, opt_state, grads, fifo, step_idx,
                      active=active, theta=theta) if comp else \
            upd(write, opt_state, grads, fifo, step_idx, active=active)
        out, opt_state, fifo, upd_stale, skips = upd_out[:5]
        if alive_st is not None:
            # a dead peer applies no updates (frozen until donor re-sync).
            # A SELECT, not an arithmetic `·a` folded into active: the
            # multiply changes XLA's FMA contraction and breaks empty-plan
            # bit-exactness; where(1.0, new, old) is the identity
            # bit-for-bit. Fused: ``out`` is the delta plane (gate to 0);
            # default: ``out`` is the updated write buffer (gate to prev).
            a = alive_st[0]
            out = (jax.tree.map(
                       lambda u: jnp.where(a > 0.0, u, jnp.zeros_like(u)),
                       out) if fused else
                   jax.tree.map(lambda n, o: jnp.where(a > 0.0, n, o),
                                out, write))
        # skips differs per worker (each sanitizes its own grads); the
        # monolithic body psums it, so the stage must too before the P()
        # out spec replicates it
        skips = jax.lax.psum(skips, worker_axes)
        # fused: ``out`` is the update-delta plane (write untouched);
        # default: ``out`` is the updated write buffer
        outs = [_restack(out), _restack(opt_state)]
        if D > 0:
            outs += [_restack(fifo["g"]), fifo["stamp"]]
        if comp:
            # θ_prev for the next step: this step's pre-update params.
            # The write input is NOT donated, so jit materializes this
            # output as a fresh copy — donatable next step without
            # aliasing the live read plane.
            outs += [_restack(upd_out[5])]
        return tuple(outs) + (upd_stale, skips)

    def gossip_body(*args):
        if fused:
            write_st, upd_st = args[:2]
            rest = args[2:]
        else:
            write_st = args[0]
            rest = args[1:]
        resid_st = rest[0] if int8 else None
        if int8:
            rest = rest[1:]
        if membership:
            w_st, versions, alive_st, step_idx, shift_idx = rest
            a = alive_st[0]
        else:
            w_st, versions, step_idx, shift_idx = rest
            a = None
        write = _unstack(write_st)
        w = w_st[0]
        resid = None
        if fused and int8:
            write, resid, w = mix(write, _unstack(resid_st),
                                  _unstack(upd_st), w, shift_idx, alive=a)
        elif fused:
            write, w = mix(write, _unstack(upd_st), w, shift_idx, alive=a)
        elif int8:
            write, resid, w = mix(write, _unstack(resid_st), w, shift_idx,
                                  alive=a)
        else:
            write, w = mix(write, w, shift_idx, alive=a)
        if M > 1:
            stamped = stamp_groups(versions,
                                   step_idx.astype(jnp.float32) + phi)
            # dead peers' clocks freeze: their replica stops advancing
            versions = stamped if a is None else \
                jnp.where(a > 0.0, stamped, versions)
        if int8:
            return _restack(write), _restack(resid), w[None], versions
        return _restack(write), w[None], versions

    def metrics_fn(losses, w, versions, upd_stale, step_idx, skips=None,
                   alive=None):
        per_worker = (losses[0] + sum(losses[1:])) / R
        if alive is None:
            loss = jnp.mean(per_worker)
        else:
            # live-weighted: a dead peer's (frozen) loss must not drag
            # the reported mean — same reduction as the monolithic
            # membership body's psum(loss*a)/psum(a)
            loss = jnp.sum(per_worker * alive) / jnp.sum(alive)
        return _decoupled_metrics(w, versions, loss, upd_stale, step_idx,
                                  skips=skips, alive=alive)

    return ([make_fwd_body(r) for r in range(R)], update_body, gossip_body,
            metrics_fn)


def _jit_stages(bodies, mesh, worker_axes, R: int, D: int, *, batch_specs,
                shardings: Optional[Dict[str, Any]] = None,
                fused: bool = False, wire: str = "param",
                compensate: float = 0.0, membership: bool = False):
    """shard_map + jit each stage body into its executable.

    ``shardings`` (Model path) pins jit-level in/out shardings so the model
    axis flows through GSPMD exactly like the monolithic step; the generic
    backend path omits it (plain jit, shardings inferred from shard_map).

    ``fused`` (use_pallas): the update stage's first output is the
    update-delta plane (its parameter input stays read-only — same
    donation set: opt/fifo/grads) and the gossip stage gains the deltas
    as a second argument. Gossip then donates the DELTAS instead of the
    plane: its plane input aliases the engine's read buffer, which the
    in-flight forward slices of the same step still read.

    ``wire="int8"``: gossip threads the residual plane (donated — its
    successor replaces it); ``compensate > 0``: update threads the θ_prev
    plane (donated — the stage returns a fresh copy of this step's
    pre-update params as the next θ_prev).

    ``membership``: the alive mask rides as an extra NEVER-donated input
    positioned after each stage's donated argument span, so every
    donation index formula below is unchanged. The update stage emits
    the nonfinite-skip count as a second trailing scalar (always)."""
    pw = P(worker_axes if len(worker_axes) > 1 else worker_axes[0])
    fwd_bodies, update_body, gossip_body, metrics_fn = bodies
    int8 = wire == "int8"
    comp = float(compensate) > 0.0

    def sm(f, in_specs, out_specs):
        return shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=set(worker_axes))

    fwd_sm = [sm(fwd_bodies[0], (pw, batch_specs), (pw, pw))]
    fwd_sm += [sm(b, (pw, batch_specs), pw) for b in fwd_bodies[1:]]
    fifo_in = (pw, P()) if D > 0 else ()
    theta_in = (pw,) if comp else ()
    alive_in = (pw,) if membership else ()
    update_sm = sm(update_body,
                   (pw, pw) + fifo_in + (pw,) + theta_in + alive_in + (P(),),
                   (pw, pw) + fifo_in + theta_in + (P(), P()))
    resid_in = (pw,) if int8 else ()
    gossip_in = (((pw, pw) if fused else (pw,)) + resid_in
                 + (pw, pw) + alive_in + (P(), P()))
    gossip_sm = sm(gossip_body, gossip_in, (pw,) + resid_in + (pw, pw))

    def gossip_step(*args):
        # gossip + the metric reduction in ONE executable: per-slice
        # per-worker losses combine in the monolithic order
        # ((l0 + sum(rest)) / R, then mean over workers) and the staleness
        # metrics read the freshly stamped clocks — identical math to
        # _decoupled_step_caller, one less dispatch per step
        if membership:
            *plane_args, w_st, versions, alive, losses, upd_stale, skips, \
                step_idx, shift_idx = args
        else:
            *plane_args, w_st, versions, losses, upd_stale, skips, \
                step_idx, shift_idx = args
            alive = None
        sm_args = (*plane_args, w_st, versions)
        if membership:
            sm_args += (alive,)
        outs = gossip_sm(*sm_args, step_idx, shift_idx)
        versions = outs[-1]
        metrics = metrics_fn(losses, outs[-2], versions, upd_stale, step_idx,
                             skips=skips, alive=alive)
        return outs[:-1] + (versions, metrics)

    n_upd = (5 if D > 0 else 3) + (1 if comp else 0)  # donate all but write
    donate_upd = tuple(range(1, n_upd))
    n_plane = (2 if fused else 1) + (1 if int8 else 0)
    # fused: skip the live plane (arg 0); non-fused: donate it too.
    # Then the resid (int8), the weights and the clocks.
    donate_gossip = tuple(range(1 if fused else 0, n_plane + 2))
    if shardings is None:
        fwd = [jax.jit(f) for f in fwd_sm]
        update = jax.jit(update_sm, donate_argnums=donate_upd)
        gossip = jax.jit(gossip_step, donate_argnums=donate_gossip)
    else:
        s = shardings
        fwd = [jax.jit(fwd_sm[0], in_shardings=(s["p"], s["batch"]),
                       out_shardings=(s["lossvec"], s["grads"]))]
        fwd += [jax.jit(f, in_shardings=(s["p"], s["batch"]),
                        out_shardings=s["lossvec"]) for f in fwd_sm[1:]]
        fifo_sh = (s["fifo_g"], s["scalar"]) if D > 0 else ()
        theta_sh = (s["p"],) if comp else ()
        alive_sh = (s["w"],) if membership else ()
        update = jax.jit(
            update_sm,
            in_shardings=(s["p"], s["opt"]) + fifo_sh
            + (s["grads"],) + theta_sh + alive_sh + (s["scalar"],),
            out_shardings=(s["upd"], s["opt"]) + fifo_sh + theta_sh
            + (s["scalar"], s["scalar"]),
            donate_argnums=donate_upd)
        R_loss = tuple([s["lossvec"]] * len(fwd_sm))
        resid_sh = (s["p"],) if int8 else ()
        gossip_p = ((s["p"], s["upd"]) if fused else (s["p"],)) + resid_sh
        gossip = jax.jit(
            gossip_step,
            in_shardings=gossip_p + (s["w"], s["w"]) + alive_sh
            + (R_loss, s["scalar"], s["scalar"], s["scalar"], s["scalar"]),
            out_shardings=(s["p"],) + resid_sh
            + (s["w"], s["w"], s["metrics"]),
            donate_argnums=donate_gossip)
    return {"fwd": fwd, "update": update, "gossip": gossip}


def _jit_group_stages(part: FlatPartition, mesh, worker_axes, M: int,
                      mix: Callable, metrics_fn: Callable,
                      shifts: Sequence[int], *, fused: bool = False,
                      shardings: Optional[Dict[str, Any]] = None,
                      R: int = 1, wire: str = "param",
                      membership: bool = False):
    """The gossip stage split at the layer-group boundary, for the stream
    engine (``streams > 1``): one jitted mix executable PER PLANE BUFFER
    plus one clock/metrics executable.

    Each mix calls the very same gossip lane closure on a single-buffer
    sub-dict ``{name: buf}`` — the lanes iterate ``plane.items()``, so the
    per-element f32 math is bitwise-identical to the full-plane stage; the
    push-sum weight exchange is recomputed per group (a scalar ppermute —
    cheap and deterministic, so every group derives the identical
    ``w_half``/``rw``) and the mixed weight is discarded. The clock stage
    recomputes the exchange ONCE more to produce the canonical new weight,
    stamps the version clocks (M > 1), and folds the metric reduction —
    together the group stages compute exactly what ``_jit_stages``' fused
    gossip stage computes, split so each group's mix can launch as soon as
    its own signal lands (one-sided gossip, DESIGN.md §13).

    Donation: the non-fused mix donates its fresh-plane input (the update
    stage's per-group output — sole live reference); the fused mix
    donates the update DELTAS and leaves the live plane alone (the
    forward slices of the same step still read it). Neither donates the
    push-sum weights: the clock donates those (and the clocks), which is
    safe only because the stream engine runs every mix of a step before
    its clock on the same FIFO stream.

    ``wire="int8"``: each mix gains its group's residual buffer and
    returns ``(mixed, resid)`` — the residual is donated alongside the
    usual set (its successor replaces it).

    ``membership``: every mix and the clock gain the (never-donated)
    alive mask just before ``shift_idx``; the clock also threads the
    update stage's nonfinite-skip scalar into the metric fold."""
    pw = P(worker_axes if len(worker_axes) > 1 else worker_axes[0])
    ax = worker_axes if len(worker_axes) > 1 else worker_axes[0]
    phi = jnp.asarray(send_fractions(part.num_groups))
    int8 = wire == "int8"

    def sm(f, in_specs, out_specs):
        return shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=set(worker_axes))

    def make_mix_body(name):
        # the alive mask (membership) rides just before shift_idx so the
        # donated-argument indices below stay put for every variant
        def mix_body(*args):
            if membership:
                *head, alive_st, shift_idx = args
                a = alive_st[0]
            else:
                *head, shift_idx = args
                a = None
            if fused and int8:
                buf_st, upd_st, resid_st, w_st = head
                mixed, resid, _ = mix({name: buf_st[0]}, {name: resid_st[0]},
                                      {name: upd_st[0]}, w_st[0], shift_idx,
                                      alive=a)
                return mixed[name][None], resid[name][None]
            if fused:
                buf_st, upd_st, w_st = head
                mixed, _ = mix({name: buf_st[0]}, {name: upd_st[0]},
                               w_st[0], shift_idx, alive=a)
                return mixed[name][None]
            if int8:
                buf_st, resid_st, w_st = head
                mixed, resid, _ = mix({name: buf_st[0]}, {name: resid_st[0]},
                                      w_st[0], shift_idx, alive=a)
                return mixed[name][None], resid[name][None]
            buf_st, w_st = head
            mixed, _ = mix({name: buf_st[0]}, w_st[0], shift_idx, alive=a)
            return mixed[name][None]
        return mix_body

    def clock_body(*args):
        if membership:
            w_st, versions, alive_st, step_idx, shift_idx = args
            al = alive_st[0]
        else:
            w_st, versions, step_idx, shift_idx = args
            al = None
        w = w_st[0]
        if M > 1:
            # the same scalar push-sum hop the full-plane gossip stage
            # performs, on an empty plane — only the weight ships
            _, w_keep, rw, _ = _ring_exchange({}, w, shift_idx, M, ax,
                                              shifts, alive=al)
            w = w_keep + rw
            stamped = stamp_groups(versions,
                                   step_idx.astype(jnp.float32) + phi)
            versions = stamped if al is None else \
                jnp.where(al > 0.0, stamped, versions)
        return w[None], versions

    resid_in = (pw,) if int8 else ()
    alive_in = (pw,) if membership else ()
    mix_in = (((pw, pw) if fused else (pw,)) + resid_in + (pw,)
              + alive_in + (P(),))
    mix_out = (pw, pw) if int8 else pw
    mix_sms = {name: sm(make_mix_body(name), mix_in, mix_out)
               for name in part.group_sizes}
    clock_sm = sm(clock_body, (pw, pw) + alive_in + (P(), P()), (pw, pw))

    def clock_step(*args):
        if membership:
            w_st, versions, alive, losses, upd_stale, skips, step_idx, \
                shift_idx = args
            clock_args = (w_st, versions, alive, step_idx, shift_idx)
        else:
            w_st, versions, losses, upd_stale, skips, step_idx, \
                shift_idx = args
            alive = None
            clock_args = (w_st, versions, step_idx, shift_idx)
        w, versions = clock_sm(*clock_args)
        metrics = metrics_fn(losses, w, versions, upd_stale, step_idx,
                             skips=skips, alive=alive)
        return w, versions, metrics

    if fused:
        donate_mix = (1, 2) if int8 else (1,)
    else:
        donate_mix = (0, 1) if int8 else (0,)
    if shardings is None:
        mixes = {name: jax.jit(f, donate_argnums=donate_mix)
                 for name, f in mix_sms.items()}
        clock = jax.jit(clock_step, donate_argnums=(0, 1))
    else:
        s = shardings
        buf = lambda name: s["p"][name]
        mixes = {}
        alive_sh = (s["w"],) if membership else ()
        for name, f in mix_sms.items():
            resid_sh = (buf(name),) if int8 else ()
            mix_sh = (((buf(name), s["upd"][name]) if fused
                       else (buf(name),)) + resid_sh + (s["w"],)
                      + alive_sh + (s["scalar"],))
            mix_out_sh = (buf(name), buf(name)) if int8 else buf(name)
            mixes[name] = jax.jit(f, in_shardings=mix_sh,
                                  out_shardings=mix_out_sh,
                                  donate_argnums=donate_mix)
        R_loss = tuple([s["lossvec"]] * R)
        clock = jax.jit(
            clock_step,
            in_shardings=(s["w"], s["w"]) + alive_sh
            + (R_loss, s["scalar"], s["scalar"], s["scalar"], s["scalar"]),
            out_shardings=(s["w"], s["w"], s["metrics"]),
            donate_argnums=(0, 1))
    return {"mix": mixes, "clock": clock}


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


class PipelineEngine:
    """Owns the stage executables, the double buffers and the timeline.

    ``step(state, batch, step_idx, shift_idx) -> (state, metrics)`` keeps
    the monolithic step's signature and state layout (``read``/``write``/
    ``opt``/``w``/``versions``[/``fifo``] dict), but every return value is
    an un-awaited future: the caller can dispatch the next step before this
    one finished, and the runtime chains the data dependencies. Blocking
    happens only when the caller converts a metric (or calls
    ``timeline.finalize()``)."""

    def __init__(self, *, R: int, D: int, M: int, stages: Dict[str, Any],
                 timeline: Optional[StageTimeline] = None, describe: str = "",
                 abstract_args: Optional[Dict[str, tuple]] = None,
                 max_inflight_steps: int = 3, fused: bool = False,
                 wire: str = "param", compensate: float = 0.0):
        self.R, self.D, self.M = int(R), int(D), int(M)
        self.fused = bool(fused)
        self.wire = wire
        self.compensate = float(compensate)
        self._stages = stages
        self.timeline = timeline if timeline is not None else StageTimeline()
        self.describe = describe
        self.abstract_args = abstract_args or {}
        # deferred-release buffers: dropping the LAST python reference to a
        # buffer that an in-flight stage still reads makes the CPU PJRT
        # client block the host until the readers retire — rebinding the
        # state dict each step would silently serialize the pipeline. The
        # engine therefore keeps each step's consumed handles alive until
        # that step's final fence is ready, and releases them on a later
        # (non-blocking) prune. ``max_inflight_steps`` is the backpressure
        # bound: the host blocks on the oldest step's fence rather than
        # run further ahead, capping the extra memory at that many
        # retired-but-held step states.
        self.max_inflight_steps = int(max_inflight_steps)
        self._graveyard: List[Tuple[Any, Any]] = []

    def step(self, state, batch, step_idx, shift_idx):
        """Dispatch one decoupled update iteration; never blocks on math.

        ``state`` is the decoupled state dict (``read``/``write``/``opt``/
        ``w``/``versions``[/``fifo``] — from ``make_decoupled_state``, or
        a previous ``step``'s return, whose leaves may be un-awaited
        futures). ``batch`` is one step's input; ``step_idx``/``shift_idx``
        should be python ints or numpy scalars — a ``jnp`` scalar is an
        eager device-0 computation whose reshard queues behind every
        in-flight stage and serializes the pipeline.

        Dispatches the R forward slices, the backward/update and the
        gossip(+metrics) stage as separate async jit calls and returns
        ``(new_state, metrics)`` immediately: every value is a future, the
        runtime chains the data dependencies, and the host may call
        ``step`` again for ``t+1`` while ``t`` still executes (bounded by
        ``max_inflight_steps`` backpressure). Converting any metric (e.g.
        ``float(metrics["loss"])``) blocks on that value only."""
        tl = self.timeline
        t = int(step_idx)
        # release buffers whose step has fully retired (never blocks), then
        # apply backpressure: at most max_inflight_steps steps in flight
        self._graveyard = [(f, p) for f, p in self._graveyard
                           if not _is_ready(f)]
        while len(self._graveyard) >= self.max_inflight_steps:
            _wait(self._graveyard[0][0])
            self._graveyard.pop(0)
            self._graveyard = [(f, p) for f, p in self._graveyard
                               if not _is_ready(f)]
        # numpy scalars, NOT jnp.asarray: an eager conversion is a tiny
        # computation committed to device 0 whose reshard-to-replicated
        # then queues behind every in-flight stage — one jnp scalar per
        # step silently serializes the whole pipeline (measured on the
        # CPU PJRT client). A numpy scalar rides the jit call's host→device
        # put, which never touches the execution queue.
        si = (step_idx if isinstance(step_idx, jax.Array)
              else np.int32(step_idx))
        sh = (shift_idx if isinstance(shift_idx, jax.Array)
              else np.int32(shift_idx))

        # forward lane: all R slices read the same (never-donated) buffer
        ev = tl.begin("fwd", t, slice_idx=0)
        loss0, grads = self._stages["fwd"][0](state["read"], batch)
        tl.commit(ev, loss0)
        losses = [loss0]
        for r in range(1, self.R):
            ev = tl.begin("fwd", t, slice_idx=r)
            lr = self._stages["fwd"][r](state["read"], batch)
            tl.commit(ev, lr)
            losses.append(lr)

        # backward/update lane: donates opt + fifo + grads (+ the stale-θ
        # plane when compensating), NOT the params (the write handle
        # aliases the read buffer the fwd slices consume). In fused
        # (use_pallas) mode the first output is the update-delta plane and
        # the write buffer is consumed read-only.
        comp = self.compensate > 0.0
        int8 = self.wire == "int8"
        # membership (chaos lane): the alive mask is a never-donated
        # passthrough — the chaos controller mutates it host-side at
        # fault events, every stage reads it
        alive = state.get("alive")
        ev = tl.begin("update", t)
        upd_args = (state["write"], state["opt"])
        if self.D > 0:
            upd_args += (state["fifo"]["g"], state["fifo"]["stamp"])
        upd_args += (grads,)
        if comp:
            upd_args += (state["theta"],)
        if alive is not None:
            upd_args += (alive,)
        upd_outs = self._stages["update"](*upd_args, si)
        write, opt = upd_outs[0], upd_outs[1]
        i = 2
        if self.D > 0:
            fifo_g, fifo_stamp = upd_outs[2], upd_outs[3]
            i = 4
        if comp:
            theta = upd_outs[i]
            i += 1
        upd_stale, skips = upd_outs[i], upd_outs[i + 1]
        tl.commit(ev, upd_stale)

        # gossip lane (+ fused metric reduction): the mixed result becomes
        # both next-step buffer handles. Default: donates the update's
        # fresh output — the flat plane itself — + w + versions. Fused:
        # the plane argument aliases the live read buffer, so the deltas
        # are donated instead of the plane. int8 wire: the EF residual
        # plane rides along (donated; its successor replaces it).
        ev = tl.begin("gossip", t)
        plane_args = (state["write"], write) if self.fused else (write,)
        if int8:
            plane_args += (state["resid"],)
        gossip_args = plane_args + (state["w"], state["versions"])
        if alive is not None:
            gossip_args += (alive,)
        gossip_outs = self._stages["gossip"](
            *gossip_args, tuple(losses), upd_stale, skips, si, sh)
        if int8:
            mixed, resid, w, versions, metrics = gossip_outs
        else:
            mixed, w, versions, metrics = gossip_outs
        tl.commit(ev, metrics["loss"])

        # hold EVERY handle this step touched until its last fence retires:
        # on the CPU PJRT client, dropping the final python reference to
        # any buffer an in-flight execution reads (the old read/write
        # params), was donated (opt/fifo/w/versions, grads), or has not
        # yet materialized (the previous metrics dict the caller rebinds)
        # blocks the host until that execution completes — any one of
        # those silently serializes the pipeline. Holding the handles is
        # free (no copies); they are released on a later non-blocking
        # prune once the fence is ready.
        self._graveyard.append(
            (metrics["loss"], (state, metrics, losses, upd_stale, skips,
                               grads, write)))

        new_state = {"read": mixed, "write": mixed, "opt": opt, "w": w,
                     "versions": versions}
        if self.D > 0:
            new_state["fifo"] = {"g": fifo_g, "stamp": fifo_stamp}
        if int8:
            new_state["resid"] = resid
        if comp:
            new_state["theta"] = theta
        if alive is not None:
            new_state["alive"] = alive
        return new_state, metrics

    def reset(self) -> None:
        """Prepare for a fresh measured run: finalize and drop the
        timeline's events, then release the held step handles (safe —
        finalize just retired every fence they wait on)."""
        self.timeline.reset()
        self._graveyard = []

    def stage_cutouts(self) -> Dict[str, Tuple[Any, tuple]]:
        """Every separately jitted stage executable paired with the
        abstract argument signature to synthesize inputs from — the
        autotuner's extraction point (``launch/tuner.py``, DESIGN.md
        §16). Keys match ``lower()``: ``fwd0..fwdR-1``, ``update``,
        ``gossip``. Raises when the engine carries no abstract args
        (legacy tree state) or the forward batch signature is still the
        backend path's placeholder (step once first)."""
        if not self.abstract_args:
            raise ValueError(
                "engine has no abstract args to cut stages out against "
                "(the flat-plane factories publish them at build; the "
                "legacy tree state has none)")
        if self.abstract_args["fwd"][-1] is None:
            raise ValueError(
                "forward batch abstract unknown: step the engine once so "
                "the backend path records the batch signature")
        out = {}
        for r, f in enumerate(self._stages["fwd"]):
            out[f"fwd{r}"] = (f, self.abstract_args["fwd"])
        for name in ("update", "gossip"):
            out[name] = (self._stages[name], self.abstract_args[name])
        return out

    def lower(self) -> Dict[str, Any]:
        """Lower every stage executable against its abstract args."""
        if not self.abstract_args:
            raise ValueError("engine has no abstract args to lower against")
        out = {}
        for r, f in enumerate(self._stages["fwd"]):
            out[f"fwd{r}"] = f.lower(*self.abstract_args["fwd"])
        for name in ("update", "gossip"):
            out[name] = self._stages[name].lower(*self.abstract_args[name])
        return out


@dataclass
class PipelineStep:
    """Drop-in analogue of :class:`~repro.launch.train.ProdStep` for the
    overlap engine: ``fn(state, batch, step_idx, shift_idx)`` like the
    monolithic decoupled step, ``init_state(params_stacked)`` builds the
    engine-managed state, ``lower()`` lowers every stage."""
    engine: PipelineEngine
    init_state: Callable
    describe: str = ""

    def fn(self, state, batch, step_idx, shift_idx):
        return self.engine.step(state, batch, step_idx, shift_idx)

    def lower(self):
        return self.engine.lower()

    @property
    def timeline(self) -> StageTimeline:
        return self.engine.timeline


# ---------------------------------------------------------------------------
# factories: Model/mesh path and generic-backend path
# ---------------------------------------------------------------------------


def flat_abstract_args(part, optimizer: Optimizer, M: int, R: int, D: int, *,
                       batch_abs=None, fused: bool = False,
                       wire: str = "param", compensate: float = 0.0,
                       membership: bool = False,
                       groups: bool = False) -> Dict[str, tuple]:
    """Abstract argument signatures for every stage executable of a
    FLAT-plane engine, keyed like ``PipelineEngine.abstract_args``
    (``"fwd"``/``"update"``/``"gossip"``, plus ``"mix:{group}"``/
    ``"clock"`` when ``groups=True`` for the stream engine).

    This is the cutout-extraction contract (``launch/tuner.py``,
    DESIGN.md §16): both factory paths publish these on the engine so
    each stage is independently lowerable and runnable in isolation.
    ``batch_abs=None`` leaves a placeholder the backend path fills from
    the first concrete batch it sees (``stage_cutouts()`` refuses to
    hand out the forward stage until then)."""
    stack = lambda s: jax.ShapeDtypeStruct((M,) + tuple(s.shape), s.dtype)
    stacked_params = part.abstract_plane((M,))
    stacked_opt = jax.tree.map(
        stack, jax.eval_shape(optimizer.init, part.abstract_plane()))
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    f32 = jax.ShapeDtypeStruct((), jnp.float32)
    w_abs = jax.ShapeDtypeStruct((M,), jnp.float32)
    v_abs = jax.ShapeDtypeStruct((M, part.num_groups), jnp.float32)
    lossvec_abs = jax.ShapeDtypeStruct((M,), jnp.float32)
    fifo_abs = ()
    if D > 0:
        fifo_abs = (part.abstract_plane((M, D)),
                    jax.ShapeDtypeStruct((D,), jnp.float32))
    upd_abs = (jax.eval_shape(
        lambda p: optimizer.update(p, optimizer.init(p), p, 0.1)[0],
        part.abstract_plane()) if fused else stacked_params)
    if fused:
        upd_abs = jax.tree.map(stack, upd_abs)
    int8 = wire == "int8"
    comp = float(compensate) > 0.0
    resid_abs = (stacked_params,) if int8 else ()
    theta_abs = (stacked_params,) if comp else ()
    alive_abs = (w_abs,) if membership else ()
    gossip_plane_abs = (((stacked_params, upd_abs) if fused
                         else (stacked_params,)) + resid_abs)
    out = {
        "fwd": (stacked_params, batch_abs),
        "update": (stacked_params, stacked_opt) + fifo_abs
                  + (stacked_params,) + theta_abs + alive_abs + (i32,),
        "gossip": gossip_plane_abs + (w_abs, v_abs) + alive_abs
                  + (tuple([lossvec_abs] * R), f32, f32, i32, i32),
    }
    if groups:
        for name in part.group_sizes:
            buf_abs = ((stacked_params[name], upd_abs[name]) if fused
                       else (stacked_params[name],))
            if int8:
                buf_abs = buf_abs + (stacked_params[name],)
            out[f"mix:{name}"] = buf_abs + (w_abs,) + alive_abs + (i32,)
        out["clock"] = ((w_abs, v_abs) + alive_abs
                        + (tuple([lossvec_abs] * R), f32, f32, i32, i32))
    return out


def make_layup_decoupled_pipeline(model, mesh, optimizer: Optimizer,
                                  schedule: Callable, shape,
                                  shifts: Sequence[int] = (1, 2, 4, 8),
                                  overrides: Optional[Dict[str, Any]] = None,
                                  preset: Optional[str] = None,
                                  fb_ratio: int = 2, update_delay: int = 1,
                                  constrain_grads: bool = False,
                                  timeline: Optional[StageTimeline] = None,
                                  flat: bool = True,
                                  use_pallas: bool = False,
                                  streams: int = 1, wire: str = "param",
                                  compensate: float = 0.0,
                                  membership: bool = False,
                                  max_inflight_steps: Optional[int] = None
                                  ) -> PipelineStep:
    """The decoupled LayUp lane as a stage-graph pipeline on the real mesh —
    same sharding/abstract setup as ``make_layup_decoupled_train_step``,
    split into separately jitted stages. ``flat=True`` (default): the
    engine's double buffers ARE the persistent flat plane and the gossip
    stage donates it; ``use_pallas`` swaps in the fused-kernel gossip
    stage (DESIGN.md §11). ``streams > 1`` runs the stages on per-stage
    execution streams with one-sided per-group signal gossip
    (:class:`repro.launch.streams.StreamEngine`, DESIGN.md §13) — same
    numerics, measured *execution* overlap; requires ``flat=True``.
    ``wire="int8"`` quantizes the gossip wire with error-feedback
    residuals; ``compensate > 0`` enables the staleness-aware delay
    correction in the update stage (DESIGN.md §14). ``membership`` adds
    the per-peer alive mask to the state and alive-gates every exchange
    (fault-tolerant lane, DESIGN.md §15)."""
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
    if streams > 1 and not flat:
        raise ValueError("streams > 1 ships the flat group plane across "
                         "the stream boundary; it requires flat=True")
    _check_wire(wire, compensate, flat, membership)
    int8 = wire == "int8"
    comp = float(compensate) > 0.0
    part = FlatPartition(model.abstract_params())
    fwd_slices = [forward_slice_lane(model.loss_fn, fb_ratio=R, slice_idx=r,
                                     grad_specs=grad_specs)
                  for r in range(R)]
    upd = backward_update_lane(optimizer, schedule, update_delay=D,
                               apply=not use_pallas, compensate=compensate)
    if use_pallas:
        mix = gossip_fused_lane(part, M, ax, shifts, wire=wire)
    elif flat:
        mix = gossip_plane_lane(part, M, ax, shifts, wire=wire)
    else:
        mix = gossip_lane_legacy(part, M, ax, shifts)
    bodies = _stage_bodies(part, R, D, M, worker_axes, fwd_slices, upd, mix,
                           flat=flat, fused=use_pallas, wire=wire,
                           compensate=compensate, membership=membership)

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
    batch_abs = _abstract_batch(cfg, shape)

    w_sh = NamedSharding(mesh, pw)
    scalar = NamedSharding(mesh, P())
    if flat:
        p_sh = jax.tree.map(lambda _: w_sh, stacked_params)
        opt_sh = jax.tree.map(lambda _: w_sh, stacked_opt)
        fifo_g_sh = jax.tree.map(lambda _: w_sh, fifo_g_abs)
    else:
        p_sh = SH.param_shardings(model, mesh, stacked_workers=M,
                                  overrides=overrides, preset=preset)
        opt_sh = _opt_shardings_stacked(abstract_opt_single, abstract_params,
                                        p_sh, mesh, M)
        fifo_g_sh = jax.tree.map(
            lambda s: NamedSharding(
                mesh, P(s.spec[0], None, *tuple(s.spec)[1:])), p_sh)
    b_sh = SH.batch_shardings(batch_abs, mesh, overrides=overrides,
                              preset=preset)
    metrics_sh = {"loss": scalar, "update_staleness": scalar,
                  "layer_staleness": scalar, "staleness_mean": scalar,
                  "weight_sum": scalar, "nonfinite_skips": scalar}
    if membership:
        metrics_sh["peers_live"] = scalar
    shardings = {
        "p": p_sh, "opt": opt_sh, "w": w_sh, "scalar": scalar, "batch": b_sh,
        "lossvec": w_sh, "grads": p_sh, "upd": p_sh,
        "fifo_g": fifo_g_sh,
        "metrics": metrics_sh,
    }
    batch_specs_sm = jax.tree.map(_worker_batch_pspec(ax), batch_abs)
    stages = _jit_stages(bodies, mesh, worker_axes, R, D,
                         batch_specs=batch_specs_sm, shardings=shardings,
                         fused=use_pallas, wire=wire, compensate=compensate,
                         membership=membership)

    if flat:
        # the shared helper IS the published stage-signature contract
        # (cutout extraction, DESIGN.md §16) — the backend path builds
        # the identical dict, minus the batch it learns at step one
        abstract_args = flat_abstract_args(
            part, optimizer, M, R, D, batch_abs=batch_abs,
            fused=use_pallas, wire=wire, compensate=compensate,
            membership=membership, groups=streams > 1)
    else:
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        f32 = jax.ShapeDtypeStruct((), jnp.float32)
        w_abs = jax.ShapeDtypeStruct((M,), jnp.float32)
        v_abs = jax.ShapeDtypeStruct((M, part.num_groups), jnp.float32)
        lossvec_abs = jax.ShapeDtypeStruct((M,), jnp.float32)
        fifo_abs = ()
        if D > 0:
            fifo_abs = (fifo_g_abs, jax.ShapeDtypeStruct((D,), jnp.float32))
        upd_abs = stacked_params
        resid_abs = (stacked_params,) if int8 else ()
        theta_abs = (stacked_params,) if comp else ()
        alive_abs = (w_abs,) if membership else ()
        gossip_plane_abs = (stacked_params,) + resid_abs
        abstract_args = {
            "fwd": (stacked_params, batch_abs),
            "update": (stacked_params, stacked_opt) + fifo_abs
                      + (stacked_params,) + theta_abs + alive_abs + (i32,),
            "gossip": gossip_plane_abs + (w_abs, v_abs) + alive_abs
                      + (tuple([lossvec_abs] * R), f32, f32, i32, i32),
        }
    tags = (f"{', pallas' if use_pallas else ''}"
            f"{', wire=int8' if int8 else ''}"
            f"{f', comp={float(compensate):g}' if comp else ''}"
            f"{', membership' if membership else ''}")
    inflight_kw = ({} if max_inflight_steps is None
                   else {"max_inflight_steps": int(max_inflight_steps)})
    if streams > 1:
        from repro.launch.streams import StreamEngine
        group_stages = _jit_group_stages(part, mesh, worker_axes, M, mix,
                                         bodies[3], shifts,
                                         fused=use_pallas,
                                         shardings=shardings, R=R,
                                         wire=wire, membership=membership)
        engine = StreamEngine(
            R=R, D=D, M=M, group_names=list(part.group_sizes),
            stages=stages, group_stages=group_stages, timeline=timeline,
            n_streams=streams, fused=use_pallas, wire=wire,
            compensate=compensate,
            describe=(f"layup decoupled stream pipeline (M={M}, R={R}, "
                      f"D={D}, shifts={shifts}, streams={streams}, "
                      f"groups={len(part.group_sizes)}{tags})"),
            abstract_args=abstract_args, **inflight_kw)
    else:
        engine = PipelineEngine(
            R=R, D=D, M=M, stages=stages, timeline=timeline,
            fused=use_pallas, wire=wire, compensate=compensate,
            describe=(f"layup decoupled pipeline (M={M}, R={R}, D={D}, "
                      f"shifts={shifts}, stages={R + 2}, flat={flat}"
                      f"{tags})"),
            abstract_args=abstract_args, **inflight_kw)

    def init_state(params_stacked):
        state = make_decoupled_state(params_stacked, optimizer,
                                     update_delay=D, part=part, flat=flat,
                                     wire=wire, compensate=compensate,
                                     membership=membership)
        if membership:
            # the alive mask is a passthrough (never a stage OUTPUT), so
            # unlike w it would keep its eager default-device placement
            # forever — commit it to the mesh like the stage inputs expect
            state["alive"] = jax.device_put(state["alive"], w_sh)
        return state

    return PipelineStep(engine, init_state, engine.describe)


def make_pipeline_backend_trainer(loss_fn: Callable, optimizer: Optimizer,
                                  schedule: Callable, mesh, *,
                                  shifts: Sequence[int] = (1, 2, 4, 8),
                                  fb_ratio: int = 1, update_delay: int = 0,
                                  straggler_delays=None,
                                  measure_drift: bool = False,
                                  timeline: Optional[StageTimeline] = None,
                                  flat: bool = True,
                                  use_pallas: bool = False,
                                  publisher=None,
                                  streams: int = 1, wire: str = "param",
                                  compensate: float = 0.0,
                                  membership: bool = False,
                                  max_inflight_steps: Optional[int] = None):
    """Pipeline-engine counterpart of ``make_decoupled_backend_trainer``:
    same generic pytree + loss_fn contract, same sim-layout batches, but
    the step is the stage-graph engine instead of one jitted program.

    ``wire="int8"`` quantizes the gossip wire (error-feedback residuals
    ride the state as an extra plane); ``compensate > 0`` turns on the
    staleness-aware delay correction in the update stage (DESIGN.md §14).

    ``streams > 1`` swaps in the :class:`repro.launch.streams.
    StreamEngine`: the same fwd/update stage executables plus the gossip
    stage split per layer group, run on dedicated execution streams
    coordinated by one-sided signals (DESIGN.md §13). Numerics stay
    loss/staleness-exact vs ``streams=1``; the timeline gains measured
    ``exec_overlap_s``. Requires ``flat=True``; ``publisher`` is not
    supported with ``streams > 1`` yet (the publisher contract expects
    concrete read-plane handles at publish time, not stream futures).

    ``publisher`` (a :class:`repro.serving.PlanePublisher`) receives the
    engine's read plane + version clocks + drift once per gossip round.
    This is the ZERO-COPY publish path: the engine never donates the read
    plane (all R forward slices of a step share it — see the donation
    rules above), so the published handles stay valid for the snapshot's
    lifetime and the publish is ``stable=True``. The (tiny) version/weight
    arrays ARE donated by the next step's gossip stage, so the publisher
    copies those; nothing in the publish blocks the host or disturbs the
    engine's dispatch run-ahead (DESIGN.md §12). Requires ``flat=True``.

    Returns ``(init_fn, step_fn, shifts, box)`` — ``box["engine"]`` holds
    the :class:`PipelineEngine` once ``init_fn`` has seen the params."""
    worker_axes = data_axes(mesh)
    ax = worker_axes if len(worker_axes) > 1 else worker_axes[0]
    M = num_workers(mesh)
    R, D = int(fb_ratio), int(update_delay)
    shifts = tuple(s % M for s in shifts if s % M != 0) or (1,)
    active_fn = straggler_active_fn(mesh, straggler_delays)
    pw = P(ax)
    box: Dict[str, Any] = {}

    if use_pallas and not flat:
        raise ValueError("use_pallas requires the flat plane (flat=True)")
    if publisher is not None and not flat:
        raise ValueError("publisher needs the flat plane (flat=True): the "
                         "legacy tree state has no per-group plane to "
                         "publish")
    if streams > 1 and not flat:
        raise ValueError("streams > 1 ships the flat group plane across "
                         "the stream boundary; it requires flat=True")
    if streams > 1 and publisher is not None:
        raise ValueError("publisher is not supported with streams > 1: "
                         "the stream engine's read plane is a future, not "
                         "a stable handle to publish (serve from a "
                         "streams=1 engine, or materialize snapshots)")
    _check_wire(wire, compensate, flat, membership)

    def build(params_single):
        part = FlatPartition(params_single)
        fwd_slices = [forward_slice_lane(loss_fn, fb_ratio=R, slice_idx=r)
                      for r in range(R)]
        upd = backward_update_lane(optimizer, schedule, update_delay=D,
                                   apply=not use_pallas,
                                   compensate=compensate)
        if use_pallas:
            mix = gossip_fused_lane(part, M, ax, shifts, wire=wire)
        elif flat:
            mix = gossip_plane_lane(part, M, ax, shifts, wire=wire)
        else:
            mix = gossip_lane_legacy(part, M, ax, shifts)
        bodies = _stage_bodies(part, R, D, M, worker_axes, fwd_slices, upd,
                               mix, squeeze_batch=True, active_fn=active_fn,
                               flat=flat, fused=use_pallas, wire=wire,
                               compensate=compensate, membership=membership)
        stages = _jit_stages(bodies, mesh, worker_axes, R, D, batch_specs=pw,
                             fused=use_pallas, wire=wire,
                             compensate=compensate, membership=membership)
        tags = (f"{', pallas' if use_pallas else ''}"
                f"{', wire=int8' if wire == 'int8' else ''}"
                f"{f', comp={float(compensate):g}' if compensate else ''}"
                f"{', membership' if membership else ''}")
        # publish the stage signatures so the tuner can cut stages out of
        # a backend-path engine too; the forward BATCH abstract is a
        # placeholder until step_fn sees the first concrete batch
        absargs = None
        if flat:
            absargs = flat_abstract_args(
                part, optimizer, M, R, D, fused=use_pallas, wire=wire,
                compensate=compensate, membership=membership,
                groups=streams > 1)
        inflight_kw = ({} if max_inflight_steps is None
                       else {"max_inflight_steps": int(max_inflight_steps)})
        if streams > 1:
            from repro.launch.streams import StreamEngine
            group_stages = _jit_group_stages(part, mesh, worker_axes, M,
                                             mix, bodies[3], shifts,
                                             fused=use_pallas, R=R,
                                             wire=wire,
                                             membership=membership)
            engine = StreamEngine(
                R=R, D=D, M=M, group_names=list(part.group_sizes),
                stages=stages, group_stages=group_stages,
                timeline=timeline, n_streams=streams, fused=use_pallas,
                wire=wire, compensate=compensate,
                describe=(f"stream pipeline backend (M={M}, R={R}, D={D}, "
                          f"streams={streams}, "
                          f"groups={len(part.group_sizes)}{tags})"),
                abstract_args=absargs, **inflight_kw)
        else:
            engine = PipelineEngine(
                R=R, D=D, M=M, stages=stages, timeline=timeline,
                fused=use_pallas, wire=wire, compensate=compensate,
                describe=(f"pipeline backend (M={M}, R={R}, D={D}, "
                          f"flat={flat}{tags})"),
                abstract_args=absargs, **inflight_kw)
        return engine, part

    def init_fn(rng, params_single):
        del rng
        stacked = jax.tree.map(
            lambda p: jnp.broadcast_to(p[None], (M,) + p.shape),
            params_single)
        if "engine" not in box:
            box["engine"], box["part"] = build(params_single)
            if measure_drift:
                from repro.core.api import disagreement
                box["drift"] = jax.jit(disagreement)
        state = make_decoupled_state(stacked, optimizer, update_delay=D,
                                     part=box["part"], flat=flat,
                                     wire=wire, compensate=compensate,
                                     membership=membership)
        if membership:
            # passthrough leaf: commit to the mesh once (see the Model
            # path's init_state) — no stage output ever re-shards it
            state["alive"] = jax.device_put(
                state["alive"], NamedSharding(mesh, pw))
        return state

    def step_fn(state, batch, step_idx, shift_idx):
        if "engine" not in box:
            raise RuntimeError("call init_fn before step_fn")
        eng = box["engine"]
        if eng.abstract_args and eng.abstract_args["fwd"][-1] is None:
            # the backend path learns the forward batch signature from
            # the first concrete batch — from here on stage cutouts
            # (launch/tuner.py) and lower() work like the Model path
            eng.abstract_args["fwd"] = (
                eng.abstract_args["fwd"][0],
                jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype),
                    batch))
        state, metrics = eng.step(state, batch, step_idx, shift_idx)
        if measure_drift:
            if streams > 1:
                # state leaves are stream futures: run the drift jit on
                # the gossip stream after the step's clock (FIFO — the
                # inputs are concrete by then, and w is read before the
                # next clock donates it)
                metrics["disagreement"] = box["engine"].submit_aux(
                    "drift", box["drift"], (state["read"], state["w"]),
                    int(step_idx))
            else:
                metrics["disagreement"] = box["drift"](state["read"],
                                                       state["w"])
        if publisher is not None:
            # stable=True: the engine never donates the read plane, so the
            # snapshot pins the live handles — zero-copy. Everything here
            # is an async dispatch or a reference swap; the host keeps its
            # run-ahead over the in-flight stages.
            publisher.publish(state["read"], state["versions"], state["w"],
                              int(step_idx),
                              drift=metrics.get("disagreement"),
                              stable=True)
        return state, metrics

    return init_fn, step_fn, shifts, box
