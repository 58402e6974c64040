"""Serving demo: continuous batching over the decode step, static or live.

    PYTHONPATH=src python examples/serve.py [--arch granite-8b] [--tokens 32]
    PYTHONPATH=src python examples/serve.py --live [--train-steps 6]

Known --arch values (REDUCED variants on the CPU container; full configs
are exercised via the dry-runs):

    decoder LMs : gpt2-medium, gpt2-xl, granite-8b, stablelm-1.6b, yi-34b
    MoE         : mixtral-8x7b, moonlight-16b-a3b, qwen3-moe-30b-a3b
    SSM / hybrid: mamba2-780m, jamba-v0.1-52b
    multimodal  : qwen2-vl-2b, whisper-large-v3  (need modality inputs —
                  not servable by this text-only demo loop)

The default path serves a static parameter set (what you would load from a
checkpoint) through :class:`repro.launch.serve.ServeLoop` — slot-based
continuous batching with prefill-by-decode — and prints the loop's
``stats()`` summary.

``--live`` instead runs the decoupled trainer (M=1, one CPU device) in a
background thread with a :class:`repro.serving.PlanePublisher` attached:
each gossip round publishes the flat read plane, a
:class:`repro.serving.SwapPolicy` gates it, and the
:class:`repro.serving.LiveServer` hot-swaps accepted planes into the
serving params between decode steps — no checkpoint save/load anywhere
(DESIGN.md §12).
"""
import argparse
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced
from repro.launch.serve import Request, ServeLoop
from repro.models import build_model


def _requests(cfg, n, prompt_len, max_new):
    rs = np.random.default_rng(1)
    return [Request(uid=i,
                    prompt=rs.integers(0, cfg.vocab_size, prompt_len,
                                       dtype=np.int32),
                    max_new_tokens=max_new)
            for i in range(n)]


def _print_stats(stats):
    print("stats:")
    for k, v in stats.items():
        print(f"  {k:22s} {v}")


def serve_static(args):
    """Default path: static params (the checkpoint case), ServeLoop only."""
    cfg = reduced(get_config(args.arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    print(f"{cfg.name} ({cfg.family}): {args.batch} slots, "
          f"prompt={args.prompt_len}, decode={args.tokens}")

    loop = ServeLoop(model, params, num_slots=args.batch,
                     max_len=args.prompt_len + args.tokens)
    reqs = _requests(cfg, 2 * args.batch, args.prompt_len, args.tokens)
    t0 = time.time()
    out = loop.serve(reqs)
    dt = time.time() - t0
    print(f"served {len(out)} requests, {loop.tokens_emitted} tokens "
          f"in {dt:.2f}s ({loop.tokens_emitted / max(dt, 1e-9):.1f} tok/s)")
    print("generated token ids (uid 0):", out[0])
    _print_stats(loop.stats())


def serve_live(args):
    """--live: decoupled trainer publishes the read plane; the LiveServer
    swaps it into the serving params mid-decode, checkpoint-free."""
    from repro.core import make_backend
    from repro.data.synthetic import SyntheticLM, make_worker_batches
    from repro.optim import constant, momentum
    from repro.serving import (AdmissionQueue, LiveServer, PlanePublisher,
                               SwapPolicy)

    cfg = reduced(get_config(args.arch))
    model = build_model(cfg)
    pub = PlanePublisher()
    be = make_backend(
        "prod", "layup", M=1,
        loss_fn=lambda p, b: model.loss_fn(p, b, block_k=64),
        optimizer=momentum(0.9), schedule=constant(0.02),
        fb_ratio=2, update_delay=1, measure_drift=True, publisher=pub)
    params = model.init(jax.random.PRNGKey(0))
    state = be.init(jax.random.PRNGKey(1), params)
    ds = SyntheticLM(vocab=cfg.vocab_size, seq_len=32, temperature=1.2)
    print(f"{cfg.name} ({cfg.family}): live serving while training "
          f"{args.train_steps} steps on the same device")

    def train():
        st = state
        for t in range(args.train_steps):
            batch = jax.tree.map(jnp.asarray, make_worker_batches(ds, 1, 4, t))
            st, m = be.step(st, batch, None)
            print(f"  train step {t}: loss={float(m['loss']):.3f} "
                  f"(published {pub.stats.published})")

    loop = ServeLoop(model, params, num_slots=args.batch,
                     max_len=args.prompt_len + args.tokens)
    adm = AdmissionQueue(max_depth=4 * args.batch)
    # M=1 never stamps gossip version clocks, so gate on drift/cadence only
    srv = LiveServer(loop, be.part, pub,
                     policy=SwapPolicy(max_drift=args.max_drift),
                     admission=adm)
    for r in _requests(cfg, 2 * args.batch, args.prompt_len, args.tokens):
        ticket = adm.submit(r)
        if not ticket.accepted:
            print(f"  request {r.uid} rejected "
                  f"(retry in {ticket.retry_after_s:.2f}s)")

    trainer = threading.Thread(target=train)
    trainer.start()
    while trainer.is_alive() or adm.depth or any(
            s.req is not None for s in loop.slots):
        if not srv.step():
            time.sleep(0.002)
    trainer.join()
    srv.poll()  # pick up the final publish
    print(f"served on params_version={loop.params_version} "
          f"after {srv.swap_count} live swaps")
    _print_stats(srv.stats())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--batch", type=int, default=4,
                    help="decode slots (continuous batching width)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--live", action="store_true",
                    help="serve live weights from a concurrent trainer")
    ap.add_argument("--train-steps", type=int, default=6)
    ap.add_argument("--max-drift", type=float, default=None,
                    help="reject published planes above this figA1 "
                         "disagreement (live mode)")
    args = ap.parse_args()
    if args.live:
        serve_live(args)
    else:
        serve_static(args)


if __name__ == "__main__":
    main()
