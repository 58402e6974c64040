"""Per-group execution streams with one-sided signal gossip (DESIGN.md §13).

The PR-3 pipeline engine (``repro.launch.pipeline``) overlaps *dispatch*:
one host thread initiates every stage call and the runtime chains the data
dependencies, so ``BENCH_overlap_stages.json`` shows the host running ahead
of the device — but a single dispatch lane gives the runtime no structural
guarantee that two stages ever *execute* concurrently, and the timeline
cannot even measure it (first-observed-ready completion times are an upper
bound polled from one thread). This module adds the missing layer:
**execution streams**.

A :class:`Stream` is one host thread that owns the execution of the stage
executables assigned to it: it resolves the stage's inputs (waiting on
signals), launches the jitted call, and **blocks until the result is
ready** before touching the next work item. Because the thread is
dedicated, the span between launch and readiness is a true *execution*
span on that stream, and spans recorded by different streams interleave
exactly when the device actually ran two stages concurrently —
``exec_overlap_s`` in :meth:`StageTimeline.summary
<repro.launch.pipeline.StageTimeline.summary>` is computed from those
spans, not from dispatch run-ahead. Off-TPU (this container, CI) the
streams are host threads over the multi-device CPU PJRT client — the
stand-in for real per-core TPU/GPU streams, with the same assignment of
stages to streams (see DESIGN.md §13 for the mapping onto real hardware).

**One-sided signal gossip.** Stages coordinate through a
:class:`SignalBoard` instead of rendezvous: the producer pushes a buffer
(the PR-4 flat *group plane* — one contiguous buffer per layer group, the
natural unit to ship across a stream boundary with zero repack) into a
named slot and flips the slot's **signal** to a new version; the consumer
spins on a ``signal_wait_until``-style predicate (``signal >= value``)
over exactly the slots it needs. The idiom is modeled on NVSHMEM's
``putmem_signal`` / ``signal_wait_until`` pair: payload delivery
happens-before the signal flip (release), and a successful wait
happens-after it (acquire) — here enforced by the board's condition
variable, on symmetric memory by the fenced signal word. The payoff is
per-*group* progress: each layer group's gossip mix launches as soon as
ITS plane signal lands, so a late group (or, across real peers, a slow
peer) delays only its own groups — the asynchrony DaSGD-style delayed
averaging assumes, instead of a full-plane barrier.

Stage-to-stream assignment (``streams=n``):

=========  =============================================================
n == 2     ``fwd`` (all R forward slices) | ``gossip`` (update + per-
           group mixes + clock/metrics)
n == 3     ``fwd`` | ``update`` | ``gossip``
n >= 4     ``fwd0..fwd{n-3}`` (slices round-robin) | ``update`` |
           ``gossip``
=========  =============================================================

Donation safety depends on per-stream FIFO order: the clock stage donates
the push-sum weights that the same step's per-group mixes read, which is
sound only because mixes and clock share the ``gossip`` stream and a
stream completes (blocks until ready) each task before starting the next.
Do not re-assign those stages to different streams without revisiting the
donation sets in ``repro.launch.pipeline``.

Numerics are EXACT vs the single-stream engine (and transitively vs the
monolithic oracle): the per-group mix applies the very same lane closure
to a single-group sub-dict — the same elementwise f32 expression on the
same inputs — and the clock stage recomputes the push-sum weight exchange
with the identical ``_ring_exchange`` ops. ``tests/test_streams.py``
asserts loss/staleness/param equality at (R, D) ∈ {(1, 1), (2, 1)}.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

__all__ = [
    "SignalBoard", "Stream", "StreamTask", "TaskOutput", "StreamEngine",
    "resolve_refs",
]

# generous guard against a lost signal turning a bug into a silent hang;
# every wait in this module times out with a diagnostic instead
_WAIT_TIMEOUT_S = 600.0


class SignalBoard:
    """One-sided signal slots: ``put_signal`` / ``wait_until``.

    Each slot holds a monotonically increasing integer **signal** (a
    version clock) and, per signalled version, an optional **payload**
    (the pushed buffer). ``put_signal(slot, signal, payload)`` stores the
    payload and then flips the signal — the memory-ordering contract is
    that a consumer which observes ``signal >= v`` also observes the
    payload pushed with ``v`` (release/acquire; here the condition
    variable's lock provides it, on symmetric memory the fenced signal
    word does). Signals never go backwards: a stale put raises instead of
    silently reordering.

    ``wait_until(slot, v)`` waits for ``signal >= v`` but returns the
    payload pushed **with v** — not the latest. A consumer of step ``t``
    that wakes up after a producer already signalled ``t+1`` must still
    read step ``t``'s buffer (e.g. a lagging forward slice of step ``t``
    racing the step's own gossip mix), so payloads are retained per
    version in a bounded window (``keep`` versions; the engine's
    bounded-queue backpressure keeps consumer lag far inside it)."""

    def __init__(self, keep: int = 64):
        self._cv = threading.Condition()
        self._keep = int(keep)
        self._signals: Dict[str, int] = {}
        self._payloads: Dict[str, Dict[int, Any]] = {}
        self._poison: Optional[BaseException] = None

    def put_signal(self, slot: str, signal: int, payload: Any = None) -> None:
        """Push ``payload`` into ``slot`` as version ``signal`` and flip
        the slot's signal (release). Evicts payload versions older than
        the retention window."""
        signal = int(signal)
        with self._cv:
            cur = self._signals.get(slot)
            if cur is not None and signal < cur:
                raise ValueError(
                    f"signal for slot {slot!r} must be monotone: "
                    f"have {cur}, got {signal}")
            d = self._payloads.setdefault(slot, {})
            d[signal] = payload
            for v in [v for v in d if v <= signal - self._keep]:
                del d[v]
            self._signals[slot] = signal
            self._cv.notify_all()

    def wait_until(self, slot: str, value: int,
                   timeout: float = _WAIT_TIMEOUT_S) -> Any:
        """Block until ``slot``'s signal is ``>= value``; return the
        payload pushed with version ``value`` (acquire). Raises
        ``TimeoutError`` after ``timeout`` seconds — a lost signal is a
        protocol bug, not a reason to hang — and ``KeyError`` if version
        ``value`` fell out of the retention window."""
        value = int(value)
        deadline = time.monotonic() + timeout
        with self._cv:
            while self._signals.get(slot, -(1 << 62)) < value:
                if self._poison is not None:
                    raise RuntimeError(
                        f"signal board poisoned while waiting on "
                        f"{slot!r} >= {value}") from self._poison
                remaining = deadline - time.monotonic()
                if remaining <= 0 or not self._cv.wait(remaining):
                    raise TimeoutError(
                        f"signal_wait_until({slot!r}, >= {value}) timed "
                        f"out at {self._signals.get(slot)!r}")
            if self._poison is not None:
                raise RuntimeError(
                    f"signal board poisoned while waiting on "
                    f"{slot!r} >= {value}") from self._poison
            d = self._payloads.get(slot, {})
            if value not in d:
                raise KeyError(
                    f"payload for {slot!r} version {value} evicted "
                    f"(retention window {self._keep}; have "
                    f"{sorted(d)[-4:]})")
            return d[value]

    def read(self, slot: str) -> Optional[int]:
        """Non-blocking probe of a slot's current signal (None if never
        signalled)."""
        with self._cv:
            return self._signals.get(slot)

    def poison(self, exc: BaseException) -> None:
        """Fail-fast kill switch: wake every waiter and make all current
        and future ``wait_until`` calls raise (chained to ``exc``). A
        task failure on one stream must not leave tasks on OTHER streams
        blocked on signals that will never arrive — without this, a
        poisoned pipeline strands daemon threads in 600 s timeouts."""
        with self._cv:
            if self._poison is None:
                self._poison = exc
            self._cv.notify_all()

    def reset(self) -> None:
        """Drop every slot and clear any poison (fresh run)."""
        with self._cv:
            self._signals.clear()
            self._payloads.clear()
            self._poison = None
            self._cv.notify_all()


class StreamTask:
    """One unit of stream work: resolve inputs, run a stage, signal.

    ``wait_fn()`` blocks on the task's input signals/futures and returns
    the resolved argument tuple (its duration is the task's recorded
    signal-wait time); ``run_fn(*args)`` launches the stage executable;
    ``signals_fn(out)`` (optional) performs the per-group push-and-signal
    protocol on the outputs. The owning :class:`Stream` blocks until the
    outputs are ready before completing the task, so ``result()`` always
    returns retired buffers. ``block_pick(out)`` (optional) selects WHICH
    outputs to block on — a producer whose signalled buffers are donated
    by a consumer on another stream must exclude them (``signals_fn``
    already blocked on each before flipping its signal, and stage
    executables complete atomically, so blocking on the remaining outputs
    still closes the execution span honestly)."""

    def __init__(self, stage: str, step: int, *, slice_idx=None, group=None,
                 wait_fn: Optional[Callable[[], tuple]] = None,
                 run_fn: Callable = None,
                 signals_fn: Optional[Callable[[Any], None]] = None,
                 block_pick: Optional[Callable[[Any], Any]] = None):
        self.stage, self.step = stage, int(step)
        self.slice_idx, self.group = slice_idx, group
        self.wait_fn, self.run_fn, self.signals_fn = wait_fn, run_fn, signals_fn
        self.block_pick = block_pick
        self.enqueue: Optional[float] = None
        self._done = threading.Event()
        self._result: Any = None
        self._exc: Optional[BaseException] = None

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: float = _WAIT_TIMEOUT_S) -> Any:
        if not self._done.wait(timeout):
            raise TimeoutError(f"stream task {self.stage}@{self.step} "
                               f"did not complete within {timeout}s")
        if self._exc is not None:
            raise self._exc
        return self._result


class TaskOutput:
    """Lazy, picklable-by-reference view into a task's (future) result.

    Supports ``float()`` / ``np.asarray()`` so metric dicts built from
    stream futures drop into the ``TrainerBackend`` contract unchanged —
    converting one blocks only on its producing task."""

    __slots__ = ("_task", "_pick")

    def __init__(self, task: StreamTask, pick: Callable[[Any], Any] = None):
        self._task = task
        self._pick = pick if pick is not None else (lambda r: r)

    def result(self) -> Any:
        return self._pick(self._task.result())

    def __float__(self) -> float:
        return float(self.result())

    def __array__(self, dtype=None):
        return np.asarray(self.result(), dtype=dtype)


def resolve_refs(tree: Any) -> Any:
    """Recursively replace :class:`TaskOutput` leaves in a (dict / tuple /
    list) tree with their concrete results — blocking on the producing
    tasks. Everything else passes through untouched."""
    if isinstance(tree, TaskOutput):
        return tree.result()
    if isinstance(tree, dict):
        return {k: resolve_refs(v) for k, v in tree.items()}
    if isinstance(tree, (tuple, list)):
        return type(tree)(resolve_refs(v) for v in tree)
    return tree


class Stream:
    """One executable stream: a host thread that runs stage tasks FIFO.

    The thread resolves each task's inputs (signal waits), launches the
    stage, and blocks until the outputs are ready — so the recorded
    ``[exec_start, complete]`` window is a true execution span on this
    stream and interleaving spans across streams are measured execution
    concurrency. The bounded queue is the backpressure: ``submit`` blocks
    once the stream is ``maxsize`` tasks behind, capping host run-ahead
    exactly like the single-stream engine's ``max_inflight_steps``."""

    _SHUTDOWN = object()

    def __init__(self, name: str, timeline, *, maxsize: int = 0,
                 clock: Callable[[], float] = time.perf_counter,
                 on_error: Optional[Callable[[StreamTask,
                                              BaseException], None]] = None):
        self.name = name
        self.timeline = timeline
        self._clock = clock
        self.on_error = on_error
        self._q: "queue.Queue" = queue.Queue(maxsize)
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=f"stream:{name}")
        self._thread.start()

    def submit(self, task: StreamTask) -> StreamTask:
        task.enqueue = self._clock()
        self._q.put(task)  # blocks when the stream is maxsize tasks behind
        return task

    def _loop(self) -> None:
        while True:
            task = self._q.get()
            if task is Stream._SHUTDOWN:
                return
            self._execute(task)

    def _execute(self, task: StreamTask) -> None:
        t0 = self._clock()
        t_exec = t0
        try:
            args = task.wait_fn() if task.wait_fn is not None else ()
            t_exec = self._clock()
            out = task.run_fn(*args)
            if task.signals_fn is not None:
                # per-group push-and-signal: blocks on each group buffer
                # then flips its slot — still inside this stream's span
                task.signals_fn(out)
            jax.block_until_ready(out if task.block_pick is None
                                  else task.block_pick(out))
            t_done = self._clock()
            task._result = out
        except BaseException as e:  # surfaced at result()/wait time
            task._exc = e
            t_done = self._clock()
            if self.on_error is not None:
                try:
                    self.on_error(task, e)
                except Exception:
                    pass  # the original failure must still surface
        if self.timeline is not None:
            self.timeline.record_exec(
                task.stage, task.step, stream=self.name,
                enqueue=task.enqueue, wait_s=t_exec - t0,
                exec_start=t_exec, complete=t_done,
                slice_idx=task.slice_idx, group=task.group)
        task._done.set()

    def close(self) -> None:
        self._q.put(Stream._SHUTDOWN)
        self._thread.join(timeout=5.0)


class StreamEngine:
    """The pipeline engine's stage graph on per-stage execution streams.

    Same external contract as :class:`~repro.launch.pipeline.
    PipelineEngine` — ``step(state, batch, step_idx, shift_idx) ->
    (state, metrics)`` with the decoupled state layout — but the stage
    executables run on dedicated :class:`Stream` threads coordinated
    through a :class:`SignalBoard`, and the gossip stage is split into
    one mix executable PER LAYER GROUP fed by push-and-signal:

    * ``fwd`` stream(s): each forward slice waits on the per-group plane
      signals for its step, then runs against the signalled buffers (the
      live read plane — never donated, so signal payloads stay valid);
    * ``update`` (own stream at ``streams >= 3``): waits on slice 0's
      gradient future, runs the backward/update executable, then pushes
      every group's post-update buffer (non-fused) or update-delta plane
      (fused) with signal value ``t``;
    * ``gossip`` stream: per-group mixes each wait on THEIR group's
      ``upd`` signal only, mix, and push the mixed group plane with
      signal ``t + 1`` (what the next step's forwards wait on); the
      clock stage then recomputes the push-sum weight exchange, stamps
      the version clocks and folds the metric reduction — identical math
      to the single-stream gossip stage, split at the group boundary.

    State leaves returned from ``step`` are :class:`TaskOutput` futures;
    pass them straight back into the next ``step`` (the streams resolve
    them), or call :meth:`materialize` for concrete arrays.
    """

    def __init__(self, *, R: int, D: int, M: int, group_names: Sequence[str],
                 stages: Dict[str, Any], group_stages: Dict[str, Any],
                 timeline=None, n_streams: int = 2, fused: bool = False,
                 describe: str = "", max_inflight_steps: int = 3,
                 abstract_args: Optional[Dict[str, tuple]] = None,
                 wire: str = "param", compensate: float = 0.0):
        if n_streams < 2:
            raise ValueError(f"StreamEngine needs >= 2 streams, got "
                             f"{n_streams} (streams=1 is the single-stream "
                             f"PipelineEngine)")
        self.R, self.D, self.M = int(R), int(D), int(M)
        self.fused = bool(fused)
        self.wire = wire
        self.compensate = float(compensate)
        self.group_names = list(group_names)
        self._stages = stages            # {"fwd": [R jits], "update": jit}
        self._group_stages = group_stages  # {"mix": {g: jit}, "clock": jit}
        if timeline is None:
            from repro.launch.pipeline import StageTimeline
            timeline = StageTimeline()
        self.timeline = timeline
        self.describe = describe
        self.abstract_args = abstract_args or {}
        self.max_inflight_steps = int(max_inflight_steps)
        # plane payloads are whole parameter planes, so retain only what a
        # reader can still ask for: plane t+2 is pushed by step t+1's mix,
        # which waits on step t's clock (its w), which waits on every
        # forward slice of step t — by then nothing reads plane t
        self.board = SignalBoard(keep=2)

        n = min(int(n_streams), self.R + 2)
        G = len(self.group_names)
        per_step_gossip = G + 2  # mixes + clock (+ the odd aux task)
        # any task failure poisons the board: tasks on OTHER streams
        # blocked in wait_until wake and fail instead of stranding their
        # daemon thread in a 600 s timeout (drained by finalize/close)
        mk = lambda name, per_step: Stream(
            name, timeline, maxsize=max(4, self.max_inflight_steps * per_step),
            on_error=lambda task, exc: self.board.poison(exc))
        self._gossip = mk("gossip", per_step_gossip)
        if n >= 3:
            self._update = mk("update", 2)
            n_fwd = n - 2
        else:
            self._update = self._gossip
            n_fwd = 1
        if n_fwd == 1:
            self._fwd = [mk("fwd", self.R + 1)]
        else:
            self._fwd = [mk(f"fwd{i}", self.R // n_fwd + 2)
                         for i in range(n_fwd)]
        self.n_streams = 1 + (self._update is not self._gossip) + len(self._fwd)
        self._tasks: List[StreamTask] = []

    # -- helpers -----------------------------------------------------------

    def _track(self, task: StreamTask) -> StreamTask:
        self._tasks.append(task)
        return task

    def _prune(self) -> None:
        self._tasks = [t for t in self._tasks if not t.done]

    @staticmethod
    def _plane_slot(g: str) -> str:
        return f"plane:{g}"

    @staticmethod
    def _upd_slot(g: str) -> str:
        return f"upd:{g}"

    def _seed_plane(self, read, t: int) -> None:
        """First step after (re-)init: the read plane is concrete — push
        every group buffer onto the board with signal ``t`` so the step's
        forwards/update find their inputs."""
        first = next(iter(read.values()))
        if isinstance(first, TaskOutput):
            return  # plane already lives on the board via mix signals
        for g in self.group_names:
            self.board.put_signal(self._plane_slot(g), t, read[g])

    # -- the step ----------------------------------------------------------

    def step(self, state, batch, step_idx, shift_idx):
        board = self.board
        t = int(step_idx)
        si = (step_idx if isinstance(step_idx, jax.Array)
              else np.int32(step_idx))
        sh = (shift_idx if isinstance(shift_idx, jax.Array)
              else np.int32(shift_idx))
        gnames = self.group_names
        int8 = self.wire == "int8"
        comp = self.compensate > 0.0
        self._prune()
        self._seed_plane(state["read"], t)

        def plane_wait():
            return {g: board.wait_until(self._plane_slot(g), t)
                    for g in gnames}

        # forward slices: wait on the per-group plane signals for step t,
        # run against the signalled buffers (round-robin over fwd streams)
        fwd_tasks = []
        for r in range(self.R):
            fn = self._stages["fwd"][r]
            task = StreamTask(
                "fwd", t, slice_idx=r,
                wait_fn=(lambda: (plane_wait(), batch)),
                run_fn=(lambda read, b, fn=fn: fn(read, b)))
            self._fwd[r % len(self._fwd)].submit(self._track(task))
            fwd_tasks.append(task)
        losses = [TaskOutput(fwd_tasks[0], lambda r: r[0])]
        losses += [TaskOutput(tk) for tk in fwd_tasks[1:]]
        grads_ref = TaskOutput(fwd_tasks[0], lambda r: r[1])

        # backward/update: waits on slice 0's gradients (cross-stream
        # future) + the plane signals; pushes each group's output buffer
        # (post-update plane, or the update-delta plane when fused) with
        # signal value t — the one-sided put the mixes wait on
        opt_ref, fifo_refs = state["opt"], state.get("fifo")
        theta_ref = state.get("theta")
        # membership (chaos lane): never-donated alive-mask passthrough,
        # mutated host-side by the chaos controller at fault events
        alive_ref = state.get("alive")
        upd_fn = self._stages["update"]

        def upd_wait():
            plane = plane_wait()
            args = [plane, resolve_refs(opt_ref)]
            if self.D > 0:
                fifo = resolve_refs(fifo_refs)
                args += [fifo["g"], fifo["stamp"]]
            args += [grads_ref.result()]
            if comp:
                # θ_prev plane: produced by the previous step's update on
                # THIS stream (FIFO) — safe to resolve and donate here
                args += [resolve_refs(theta_ref)]
            if alive_ref is not None:
                args += [resolve_refs(alive_ref)]
            return tuple(args) + (si,)

        def upd_signals(out):
            plane_out = out[0]
            for g in gnames:
                jax.block_until_ready(plane_out[g])
                board.put_signal(self._upd_slot(g), t, plane_out[g])

        # block_pick excludes the plane outputs: each was blocked on in
        # upd_signals before its signal, and the mixes (another stream)
        # donate them — blocking on a donated buffer raises
        upd_task = self._track(StreamTask(
            "update", t, wait_fn=upd_wait, run_fn=upd_fn,
            signals_fn=upd_signals, block_pick=lambda r: r[1:]))
        self._update.submit(upd_task)
        new_opt = TaskOutput(upd_task, lambda r: r[1])
        new_fifo = None
        if self.D > 0:
            new_fifo = {"g": TaskOutput(upd_task, lambda r: r[2]),
                        "stamp": TaskOutput(upd_task, lambda r: r[3])}
        new_theta = None
        if comp:
            theta_idx = 4 if self.D > 0 else 2
            new_theta = TaskOutput(upd_task,
                                   lambda r, i=theta_idx: r[i])
        upd_stale = TaskOutput(upd_task, lambda r: r[-2])
        skips = TaskOutput(upd_task, lambda r: r[-1])

        # per-group gossip mixes: each waits on ITS group's upd signal
        # only — a late group delays its own mix, nothing else — then
        # pushes the mixed plane with signal t+1 for the next forwards
        w_ref, versions_ref = state["w"], state["versions"]
        resid_refs = state.get("resid")
        mix_tasks: Dict[str, StreamTask] = {}
        for g in gnames:
            mix_fn = self._group_stages["mix"][g]
            resid_ref = resid_refs[g] if int8 else None

            def mix_tail():
                # never-donated alive mask rides just before shift_idx
                if alive_ref is not None:
                    return (resolve_refs(alive_ref), sh)
                return (sh,)

            if self.fused:
                def mix_wait(g=g, resid_ref=resid_ref):
                    # fused kernel contract: mix reads the LIVE plane
                    # (signal t) + the update deltas (upd signal t)
                    live = board.wait_until(self._plane_slot(g), t)
                    delta = board.wait_until(self._upd_slot(g), t)
                    if int8:
                        # EF residual: previous mix of THIS group on THIS
                        # stream produced it (FIFO) — resolve + donate
                        return (live, delta, resolve_refs(resid_ref),
                                resolve_refs(w_ref)) + mix_tail()
                    return (live, delta, resolve_refs(w_ref)) + mix_tail()
            else:
                def mix_wait(g=g, resid_ref=resid_ref):
                    fresh = board.wait_until(self._upd_slot(g), t)
                    if int8:
                        return (fresh, resolve_refs(resid_ref),
                                resolve_refs(w_ref)) + mix_tail()
                    return (fresh, resolve_refs(w_ref)) + mix_tail()

            def mix_signals(out, g=g):
                board.put_signal(self._plane_slot(g), t + 1,
                                 out[0] if int8 else out)

            task = self._track(StreamTask(
                "gossip", t, group=g, wait_fn=mix_wait, run_fn=mix_fn,
                signals_fn=mix_signals))
            self._gossip.submit(task)
            mix_tasks[g] = task
        if int8:
            mixed = {g: TaskOutput(tk, lambda r: r[0])
                     for g, tk in mix_tasks.items()}
            new_resid = {g: TaskOutput(tk, lambda r: r[1])
                         for g, tk in mix_tasks.items()}
        else:
            mixed = {g: TaskOutput(tk) for g, tk in mix_tasks.items()}

        # clock/metrics: recompute the push-sum weight exchange, stamp the
        # version clocks, fold the metric reduction (same math as the
        # single-stream gossip stage — split at the group boundary).
        # Donates w + versions: safe because the same step's mixes already
        # retired on this stream (FIFO).
        clock_fn = self._group_stages["clock"]

        def clock_wait():
            head = (resolve_refs(w_ref), resolve_refs(versions_ref))
            if alive_ref is not None:
                head += (resolve_refs(alive_ref),)
            return head + (tuple(l.result() for l in losses),
                           upd_stale.result(), skips.result(), si, sh)

        clock_task = self._track(StreamTask(
            "clock", t, wait_fn=clock_wait, run_fn=clock_fn))
        self._gossip.submit(clock_task)
        new_w = TaskOutput(clock_task, lambda r: r[0])
        new_versions = TaskOutput(clock_task, lambda r: r[1])
        metric_keys = ["loss", "update_staleness", "weight_sum",
                       "layer_staleness", "staleness_mean",
                       "nonfinite_skips"]
        if alive_ref is not None:
            metric_keys.append("peers_live")
        metrics = {k: TaskOutput(clock_task,
                                 (lambda r, k=k: r[2][k]))
                   for k in metric_keys}

        new_state = {"read": mixed, "write": mixed, "opt": new_opt,
                     "w": new_w, "versions": new_versions}
        if self.D > 0:
            new_state["fifo"] = new_fifo
        if int8:
            new_state["resid"] = new_resid
        if comp:
            new_state["theta"] = new_theta
        if alive_ref is not None:
            new_state["alive"] = alive_ref
        return new_state, metrics

    def submit_aux(self, stage: str, fn: Callable, arg_refs: tuple,
                   step: int) -> TaskOutput:
        """Run an auxiliary computation (e.g. the drift metric) on the
        gossip stream after the step's clock — its inputs may be
        :class:`TaskOutput` refs into the step just submitted."""
        task = self._track(StreamTask(
            stage, int(step),
            wait_fn=(lambda: tuple(resolve_refs(a) for a in arg_refs)),
            run_fn=fn))
        self._gossip.submit(task)
        return TaskOutput(task)

    # -- lifecycle ---------------------------------------------------------

    def materialize(self, tree):
        """Resolve every :class:`TaskOutput` leaf to a concrete array."""
        return resolve_refs(tree)

    def finalize(self) -> None:
        """Drain EVERY submitted task, then re-raise the first failure.

        Raising on the first failed task would leave later tasks (other
        streams) undrained and their threads potentially blocked on
        signals the failed task never produced; the board poison wakes
        them, and the full drain here guarantees every thread is idle
        before the exception surfaces."""
        first: Optional[BaseException] = None
        for task in self._tasks:
            try:
                task.result()
            except BaseException as e:
                if first is None:
                    first = e
        self._prune()
        if first is not None:
            raise first

    def reset(self) -> None:
        """Fresh measured run: drain the streams, clear the board and the
        timeline (mirrors ``PipelineEngine.reset``)."""
        self.finalize()
        self.board.reset()
        self.timeline.reset()

    def close(self) -> None:
        """Shut the stream threads down (tests; daemon threads otherwise
        die with the process). The streams are closed even when the drain
        raises — a poisoned pipeline must not leak its threads."""
        try:
            self.finalize()
        finally:
            seen = set()
            for s in [self._gossip, self._update, *self._fwd]:
                if id(s) not in seen:
                    seen.add(id(s))
                    s.close()

    def stage_cutouts(self) -> Dict[str, Tuple[Any, tuple]]:
        """Every separately jitted stage executable paired with its
        abstract argument signature — the autotuner's extraction point
        (``launch/tuner.py``, DESIGN.md §16; mirrors
        ``PipelineEngine.stage_cutouts``). Keys match ``lower()``:
        ``fwd0..fwdR-1``, ``update``, ``mix:{group}``, ``clock``."""
        if not self.abstract_args:
            raise ValueError(
                "engine has no abstract args to cut stages out against "
                "(the flat-plane factories publish them at build)")
        if self.abstract_args["fwd"][-1] is None:
            raise ValueError(
                "forward batch abstract unknown: step the engine once so "
                "the backend path records the batch signature")
        out = {}
        for r, f in enumerate(self._stages["fwd"]):
            out[f"fwd{r}"] = (f, self.abstract_args["fwd"])
        out["update"] = (self._stages["update"],
                         self.abstract_args["update"])
        for g in self.group_names:
            out[f"mix:{g}"] = (self._group_stages["mix"][g],
                               self.abstract_args[f"mix:{g}"])
        out["clock"] = (self._group_stages["clock"],
                        self.abstract_args["clock"])
        return out

    def lower(self) -> Dict[str, Any]:
        """Lower every stage executable against its abstract args (Model
        path only, mirrors ``PipelineEngine.lower``)."""
        if not self.abstract_args:
            raise ValueError("engine has no abstract args to lower against")
        out = {}
        for r, f in enumerate(self._stages["fwd"]):
            out[f"fwd{r}"] = f.lower(*self.abstract_args["fwd"])
        out["update"] = self._stages["update"].lower(
            *self.abstract_args["update"])
        for g in self.group_names:
            out[f"mix:{g}"] = self._group_stages["mix"][g].lower(
                *self.abstract_args[f"mix:{g}"])
        out["clock"] = self._group_stages["clock"].lower(
            *self.abstract_args["clock"])
        return out
