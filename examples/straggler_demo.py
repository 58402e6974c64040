"""Straggler robustness demo (paper Fig. 3): inject a slow worker and watch
LayUp keep converging at full speed while DDP's wall-clock blows up.

    PYTHONPATH=src python examples/straggler_demo.py [--delay 4]
    PYTHONPATH=src python examples/straggler_demo.py --backend prod \
        [--fb-ratio 2] [--update-delay 1] [--overlap [--streams 3]] \
        [--wire int8] [--compensate 0.5]

All execution engines run behind the same ``TrainerBackend`` protocol: the
numeric backend (``sim``: vmapped workers on one device; ``prod``: the
decoupled shard_map lane on an 8-device host mesh) produces the loss and
the measured per-layer staleness, while the event backend produces the
modeled wall-clock — stepped in lock-step per iteration. With ``--backend
prod`` the decoupled step *absorbs* the injected straggler delay: the slow
worker skips its local updates but keeps gossiping, the event simulator
predicts the wall-clock stays pinned to the fast workers, and the measured
per-layer staleness is printed next to the simulator's prediction.
"""
import argparse
import os

M = 8


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--delay", type=int, default=4)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--backend", choices=["sim", "prod"], default="sim")
    ap.add_argument("--fb-ratio", type=int, default=2,
                    help="prod backend: forward passes per backward")
    ap.add_argument("--update-delay", type=int, default=1,
                    help="prod backend: gradient FIFO depth D")
    ap.add_argument("--overlap", action="store_true",
                    help="prod backend: run the stage-graph pipeline engine "
                         "instead of the monolithic jitted step — separately "
                         "jitted fwd/update/gossip stages driven by an "
                         "async-dispatch host loop, with the measured "
                         "per-stage timeline (dispatch overlap, DESIGN.md "
                         "§10) printed after the run")
    ap.add_argument("--streams", type=int, default=1,
                    help="prod backend, needs --overlap: number of execution "
                         "streams (host threads standing in for device "
                         "streams). >1 runs forward slices, update and the "
                         "per-group one-sided signal gossip concurrently and "
                         "prints EXECUTION-level accounting (exec_overlap_s, "
                         "per-stream busy, signal-wait — DESIGN.md §13); "
                         "numerics stay bit-exact vs --streams 1")
    ap.add_argument("--wire", choices=["param", "int8"], default="param",
                    help="prod backend: gossip wire dtype. int8 ships "
                         "error-feedback quantized planes (values + "
                         "per-128-lane-row f32 scales — about half the "
                         "bf16 wire bytes, DESIGN.md §14); param is the "
                         "exact params-dtype wire")
    ap.add_argument("--compensate", type=float, default=0.0,
                    help="prod backend: strength λ of the staleness-aware "
                         "delay compensation g + λ·g⊙g⊙(θ_now − θ_stale) "
                         "applied to the popped stale gradient (0 = off, "
                         "DESIGN.md §14)")
    ap.add_argument("--faults", type=str, default=None,
                    help="prod backend: deterministic chaos plan, e.g. "
                         "'crash:peer=1,step=50,recover=120' or "
                         "'corrupt:step=30,group=0;hang:step=40,"
                         "seconds=0.1'. Turns the fault-tolerant "
                         "membership lane on (alive-gated push-sum, "
                         "deadline-guarded gossip, donor re-sync — "
                         "DESIGN.md §15) and prints the membership "
                         "timeline + degraded-round accounting after "
                         "the run. '' enables membership with no faults")
    args = ap.parse_args()
    if args.streams > 1 and not args.overlap:
        ap.error("--streams > 1 requires --overlap (DESIGN.md §13)")
    if (args.wire != "param" or args.compensate
            or args.faults is not None) and args.backend != "prod":
        ap.error("--wire / --compensate / --faults apply to the prod lane "
                 "only (use --backend prod)")

    if args.backend == "prod":
        # the prod lane needs one device per worker; on the CPU platform
        # (JAX_PLATFORMS=cpu) that is one host device each, a flag that
        # must be set before jax initializes (append — don't clobber any
        # flags the user already exported)
        flag = f"--xla_force_host_platform_device_count={M}"
        existing = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in existing:
            os.environ["XLA_FLAGS"] = (existing + " " + flag).strip()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import make_backend
    from repro.core.simulator import HardwareModel
    from repro.data.synthetic import SyntheticVision, make_worker_batches
    from repro.optim import constant, momentum

    ds = SyntheticVision(num_classes=10, dim=64, snr=1.2)

    def init(rng):
        k1, k2 = jax.random.split(rng)
        return {"l1": jax.random.normal(k1, (64, 128)) * 0.1,
                "l2": jax.random.normal(k2, (128, 10)) * 0.1}

    def loss_fn(p, b):
        h = jnp.tanh(b["x"] @ p["l1"])
        logits = h @ p["l2"]
        return -jnp.mean(jax.nn.log_softmax(logits)[
            jnp.arange(logits.shape[0]), b["labels"]]), {}

    delays = np.zeros(M, int)
    delays[0] = args.delay
    hw = HardwareModel(fwd_time=0.02, bwd_ratio=2.0, model_bytes=0.4e9,
                       allreduce_bandwidth=60e9)

    print(f"{M} workers, worker 0 is {args.delay}× slower\n")

    if args.backend == "prod":
        run_prod(args, hw, ds, init, loss_fn, delays)
        return

    print(f"{'algo':10s} {'final loss':>10s} {'wall-clock (s)':>15s} "
          f"{'vs no-straggler':>16s}")
    for algo_name in ("ddp", "slowmo", "gosgd", "layup"):
        num = make_backend("sim", algo_name, M=M, loss_fn=loss_fn,
                           optimizer=momentum(0.9), schedule=constant(0.05),
                           straggler_delays=delays)
        ev_slow = make_backend("event", algo_name, M=M, hw=hw,
                               straggler_delays=delays)
        ev_fast = make_backend("event", algo_name, M=M, hw=hw)
        st = num.init(jax.random.PRNGKey(0), init(jax.random.PRNGKey(1)))
        sl = ev_slow.init(jax.random.PRNGKey(0))
        fa = ev_fast.init(jax.random.PRNGKey(0))
        rng = jax.random.PRNGKey(2)
        loss = None
        for t in range(args.steps):
            batch = jax.tree.map(jnp.asarray,
                                 make_worker_batches(ds, M, 32, t))
            rng, r = jax.random.split(rng)
            st, m = num.step(st, batch, r)
            sl, _ = ev_slow.step(sl, None, None)
            fa, _ = ev_fast.step(fa, None, None)
            loss = float(m["loss"])
        t_slow = ev_slow.result().total_time
        t_fast = ev_fast.result().total_time
        print(f"{algo_name:10s} {loss:10.4f} {t_slow:15.1f} "
              f"{t_slow / t_fast:15.2f}×")


def run_prod(args, hw, ds, init, loss_fn, delays):
    """Decoupled prod lane vs the event simulator's prediction."""
    # jax is initialized by main() before this runs; imports are cached
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import make_backend
    from repro.data.synthetic import make_worker_batches
    from repro.optim import constant, momentum

    R, D = args.fb_ratio, args.update_delay
    if args.streams > 1:
        engine = f"stream engine, {args.streams} execution streams"
    elif args.overlap:
        engine = "stage-graph pipeline engine"
    else:
        engine = "monolithic jitted step"
    extras = ""
    if args.wire != "param":
        extras += f", {args.wire} wire"
    if args.compensate:
        extras += f", delay compensation λ={args.compensate:g}"
    if args.faults is not None:
        from repro.chaos import FaultPlan
        extras += (f", chaos: {FaultPlan.parse(args.faults).describe()}")
    print(f"prod decoupled lane: R={R}, D={D} "
          f"(double-buffered params, {D}-deep gradient FIFO, "
          f"{engine}{extras})\n")
    num = make_backend("prod", "layup", M=M, loss_fn=loss_fn,
                       optimizer=momentum(0.9), schedule=constant(0.05),
                       fb_ratio=R, update_delay=D,
                       straggler_delays=delays, shifts=(1, 2, 4),
                       overlap=args.overlap, streams=args.streams,
                       wire=args.wire, compensate=args.compensate,
                       faults=args.faults)
    ev_slow = make_backend("event", "layup", M=M, hw=hw,
                           straggler_delays=delays, fb_ratio=R,
                           update_delay=D)
    ev_fast = make_backend("event", "layup", M=M, hw=hw, fb_ratio=R,
                           update_delay=D)
    st = num.init(jax.random.PRNGKey(0), init(jax.random.PRNGKey(1)))
    sl = ev_slow.init(jax.random.PRNGKey(0))
    fa = ev_fast.init(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(2)
    m = None
    # the prod lane splits each worker batch into R forward slices
    bpw = 32 * max(R, 1)
    for t in range(args.steps):
        batch = jax.tree.map(jnp.asarray,
                             make_worker_batches(ds, M, bpw, t))
        rng, r = jax.random.split(rng)
        st, m = num.step(st, batch, r)
        sl, _ = ev_slow.step(sl, None, None)
        fa, _ = ev_fast.step(fa, None, None)

    r_slow = ev_slow.result()
    r_fast = ev_fast.result()
    iters = args.steps
    iter_time = r_slow.total_time / iters
    predicted_iters = (r_slow.mean_grad_staleness / iter_time
                       if iter_time > 0 else 0.0)
    print(f"final loss                 {float(m['loss']):.4f}")
    print(f"wall-clock (straggler)     {r_slow.total_time:.1f}s "
          f"({r_slow.total_time / r_fast.total_time:.2f}× the no-straggler "
          f"run — the decoupled lane absorbs the delay)")
    print(f"utilization                {r_slow.utilization:.3f} "
          f"(event-sim: compute never stalls on the NIC)")
    ls = np.asarray(m["layer_staleness"])
    print("\nmeasured per-layer staleness (iterations, prod lane) "
          "vs event-sim prediction:")
    for g, s in enumerate(ls):
        print(f"  group {g}: {s:.3f}")
    print(f"  mean measured            {float(m['staleness_mean']):.3f}")
    print(f"  update staleness (FIFO)  {float(m['update_staleness']):.3f} "
          f"(== D after warm-up)")
    print(f"  staleness delta vs D     "
          f"{float(m['update_staleness']) - D:+.3f} "
          f"(measured − nominal FIFO depth)")
    print(f"  event-sim grad staleness {predicted_iters:.3f} iterations "
          f"({r_slow.mean_grad_staleness * 1e3:.1f} ms)")
    wire_b = num.part.plane_nbytes(wire=args.wire)
    print(f"\ngossip wire                {args.wire} "
          f"({wire_b / 1e3:.1f} KB/round per worker, one full plane "
          f"across all layer groups)")
    if args.compensate:
        print(f"delay compensation         λ={args.compensate:g} "
              f"(g + λ·g⊙g⊙(θ_now − θ_stale) on the popped gradient)")

    if args.overlap:
        s = num.summary()
        tl = num.timeline.summary()
        print("\nmeasured stage timeline (pipeline engine, host "
              "dispatch/complete timestamps):")
        for stage, total in sorted(tl["stage_s"].items()):
            print(f"  {stage:8s} in-flight {total:8.3f}s total "
                  f"({total / args.steps * 1e3:7.2f} ms/step)")
        print(f"  wall                     {s['pipeline_wall_s']:.3f}s")
        print(f"  dispatches that found a stage in flight: "
              f"{int(s['overlap_events'])}")
        print(f"  fwd(t+1) over gossip(t)  {s['fwd_gossip_overlap_s']:.3f}s "
              f"(measured — the overlap the monolithic step cannot exhibit)")
        if args.streams > 1:
            print("\nmeasured execution concurrency (stream engine, "
                  "closed per-stream spans):")
            for name, busy in sorted(tl["stream_busy_s"].items()):
                print(f"  stream {name:8s} busy {busy:8.3f}s")
            print(f"  exec_overlap_s           {s['exec_overlap_s']:.3f}s "
                  f"(2+ streams executing simultaneously)")
            print(f"  signal_wait_s            {s['signal_wait_s']:.3f}s "
                  f"(one-sided signal predicates, DESIGN.md §13)")

    if args.faults is not None:
        s = num.summary()
        print("\nmembership timeline (fault-tolerant lane, DESIGN.md §15):")
        events = num.chaos.health.events
        if events:
            for epoch, peer, old, new in events:
                print(f"  step {epoch:4d}  peer {peer}  "
                      f"{old:>7s} -> {new}")
        else:
            print("  (no membership transitions — all peers stayed ALIVE)")
        print("degraded-round accounting:")
        print(f"  faults injected          {int(s['faults_injected'])}")
        print(f"  rounds degraded          {int(s['rounds_degraded'])} "
              f"(gossip rounds with <{M} live peers or a wire event)")
        print(f"  peers dead at exit       {int(s['peers_dead'])}")
        print(f"  donor re-syncs           {int(s['resyncs'])}")
        print(f"  nonfinite grads skipped  {s['nonfinite_skips']:g}")
        if "time_to_detect_steps" in s:
            print(f"  time to detect (steps)   "
                  f"{s['time_to_detect_steps']:g}")
        if "time_to_resync_steps" in s:
            print(f"  time to re-sync (steps)  "
                  f"{s['time_to_resync_steps']:g}")
        print(f"  push-sum mass Σw         {float(s['weight_sum']):.6f} "
              f"(conserved = 1.0 through crash/renorm/recovery)")


if __name__ == "__main__":
    main()
