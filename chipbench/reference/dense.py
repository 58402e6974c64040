"""Plain reference of a dense decoder trained by LayUp or DDP.

Written from the published descriptions, in straightforward ``jax.numpy``
and float32 at the highest matmul precision, and importing nothing of the
program under test. The block is the llama-style pre-norm decoder the
configuration files describe: RMSNorm, rotary positions on the first
``rope_fraction`` of each head (rotate-half form), causal softmax
attention, a gated SiLU MLP, and a tied or untied LM head; the loss is the
mean next-token cross-entropy.

The weights come from the seed by the configuration's stated rule: each
matrix normal(0, 0.02), the two output projections 0.02/sqrt(2 x layers),
norm scales one, each leaf drawn from the seed's key folded with the
CRC32 of its path. Layers are stacked along a leading axis.

Training follows LayUp's decoupled lane (arXiv 2410.05985, Alg. 1): each
worker splits its rows into R slices, runs the forward on all of them and
the backward on slice 0; the gradient waits D steps in a FIFO before the
momentum update; then each worker mixes its parameters with those of the
worker ``shift`` places before it on the ring, by push-sum weights, and
stamps each layer group's version clock with the time the group's update
was generated in step t: t + phi_g, where the backward reaches group g
(the top-level parameter groups in name order, 0 the input-most) after
(G - g)/G of its time and takes twice the forward's, so phi_g =
(1 + 2 (G - g)/G) / 3. DDP averages the workers' gradients and applies
one update to one copy. Parameters, momentum and the FIFO are held in
the configuration's dtype; the arithmetic is float32.

A ``Precision`` other than ``EXACT`` computes the same in a lower
precision: the control the comparison has to refuse.
"""
from __future__ import annotations

import zlib
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class Precision:
    name: str
    compute: Any = jnp.float32          # activations and elementwise math
    storage: Optional[Any] = None       # parameters, momentum, FIFO (None: config)
    matmul: str = "highest"             # jax matmul precision
    quantize: Optional[str] = None      # "fp8": matmul operands rounded to fp8


EXACT = Precision("exact")
BF16 = Precision("bf16", compute=jnp.bfloat16, storage=jnp.bfloat16,
                 matmul="default")
FP8 = Precision("fp8", quantize="fp8", matmul="default")

# the control of each stated precision: the nearest one below it
CONTROLS = {"float32": BF16, "bfloat16": FP8}

# the sizes this reference reads: a configuration file of the family states
# each one, and each is a size the program runs (``chipbench/cells.py``)
SIZES = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
         "d_ff", "vocab_size", "tie_embeddings", "rope_fraction",
         "rope_theta", "norm_eps")

# the step arguments the reference follows, and those that only choose how
# the same step runs (its engine, streams and kernels)
MODELED = {"layup": ("algo", "fb_ratio", "update_delay", "shifts"),
           "ddp": ("algo",)}
EXECUTION = ("overlap", "streams", "use_pallas", "max_inflight_steps")


def check_job(job: Dict[str, Any]) -> None:
    """Refuse a job whose step the reference does not follow: an unknown
    algorithm, a schedule left to the program's defaults, or an argument
    that changes the arithmetic (a quantized wire, delay compensation)."""
    step = job["step"]
    algo = step.get("algo")
    if algo not in MODELED:
        raise ValueError(f"the reference does not follow algo {algo!r}")
    missing = [k for k in MODELED[algo] if k not in step]
    unknown = sorted(set(step) - set(MODELED[algo]) - set(EXECUTION))
    if missing or unknown:
        raise ValueError(f"the reference needs {missing} stated and does "
                         f"not follow {unknown}")


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def param_shapes(c: Dict[str, Any]) -> Dict[str, Any]:
    """``{path: (shape, init, scale)}`` of every leaf."""
    L, d, H, Hkv, hd, f, V = (c["num_layers"], c["d_model"], c["num_heads"],
                             c["num_kv_heads"], c["head_dim"], c["d_ff"],
                             c["vocab_size"])
    out_scale = 0.02 / np.sqrt(max(2 * L, 1))
    leaves = {
        "['blocks']['sub0']['attn']['norm']": ((L, d), "ones", 0.0),
        "['blocks']['sub0']['attn']['wq']": ((L, d, H, hd), "normal", 0.02),
        "['blocks']['sub0']['attn']['wk']": ((L, d, Hkv, hd), "normal", 0.02),
        "['blocks']['sub0']['attn']['wv']": ((L, d, Hkv, hd), "normal", 0.02),
        "['blocks']['sub0']['attn']['wo']": ((L, H, hd, d), "normal",
                                             out_scale),
        "['blocks']['sub0']['mlp']['norm']": ((L, d), "ones", 0.0),
        "['blocks']['sub0']['mlp']['wi_gate']": ((L, d, f), "normal", 0.02),
        "['blocks']['sub0']['mlp']['wi_up']": ((L, d, f), "normal", 0.02),
        "['blocks']['sub0']['mlp']['wo']": ((L, f, d), "normal", out_scale),
        "['embed']['tok']": ((V, d), "normal", 0.02),
        "['final_norm']": ((d,), "ones", 0.0),
    }
    if not c["tie_embeddings"]:
        leaves["['embed']['unembed']"] = ((d, V), "normal", 0.02)
    return leaves


def init_params(c: Dict[str, Any], key, dtype) -> Dict[str, jax.Array]:
    out = {}
    for path, (shape, init, scale) in param_shapes(c).items():
        if init == "ones":
            out[path] = jnp.ones(shape, dtype)
            continue
        k = jax.random.fold_in(key, zlib.crc32(path.encode()) % (2**31))
        out[path] = (jax.random.normal(k, shape, jnp.float32)
                     * scale).astype(dtype)
    return out


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def _q8(x):
    """Round to float8 (e4m3), scaled per tensor so that its largest
    magnitude maps to the format's largest, as fp8 training does
    (straight-through gradient)."""
    xf = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-30) / 448.0
    q = (xf / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q.astype(x.dtype) - x)


def _mm(p: Precision):
    def mm(spec, a, b):
        if p.quantize == "fp8":
            a, b = _q8(a), _q8(b)
        return jnp.einsum(spec, a, b, precision=p.matmul,
                          preferred_element_type=jnp.float32
                          ).astype(p.compute)
    return mm


def _rmsnorm(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _rope(x, S, c):
    hd = x.shape[-1]
    rot = int(hd * c["rope_fraction"])
    rot -= rot % 2
    if rot == 0:
        return x
    inv = 1.0 / (c["rope_theta"] ** (np.arange(0, rot, 2, dtype=np.float32)
                                     / rot))
    ang = np.arange(S, dtype=np.float32)[:, None] * inv[None]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    x1 = x[..., :rot // 2].astype(jnp.float32)
    x2 = x[..., rot // 2:rot].astype(jnp.float32)
    return jnp.concatenate([(x1 * cos - x2 * sin).astype(x.dtype),
                            (x2 * cos + x1 * sin).astype(x.dtype),
                            x[..., rot:]], -1)


def _attention(q, k, v, p: Precision, rows: int = 1024):
    """Causal softmax attention, computed ``rows`` queries at a time."""
    mm = _mm(p)
    B, S, H, hd = q.shape
    outs = []
    for a in range(0, S, rows):
        qa = q[:, a:a + rows]
        s = mm("bqhd,bkhd->bhqk", qa, k).astype(jnp.float32) * hd ** -0.5
        qi = np.arange(a, min(a + rows, S))[:, None]
        s = jnp.where(jnp.asarray(np.arange(S)[None] <= qi), s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1).astype(p.compute)
        outs.append(mm("bhqk,bkhd->bqhd", w, v))
    return jnp.concatenate(outs, 1)


def loss_fn(params, tokens, labels, c, p: Precision = EXACT):
    """Mean next-token cross-entropy of ``(rows, S)`` tokens."""
    mm = _mm(p)
    eps = c["norm_eps"]
    # weights are cast to the compute dtype where used, a layer at a time
    P = params
    blk = "['blocks']['sub0']"
    S = tokens.shape[1]
    h = jnp.take(P["['embed']['tok']"], tokens, axis=0).astype(p.compute)

    @jax.checkpoint
    def layer(h, w):
        w = {k: v.astype(p.compute) for k, v in w.items()}
        x = _rmsnorm(h, w["an"], eps)
        q = _rope(mm("bsd,dhk->bshk", x, w["wq"]), S, c)
        k = _rope(mm("bsd,dhk->bshk", x, w["wk"]), S, c)
        v = mm("bsd,dhk->bshk", x, w["wv"])
        h = h + mm("bshk,hkd->bsd", _attention(q, k, v, p), w["wo"])
        x = _rmsnorm(h, w["mn"], eps)
        a = jax.nn.silu(mm("bsd,df->bsf", x, w["wg"])) * mm(
            "bsd,df->bsf", x, w["wu"])
        return h + mm("bsf,fd->bsd", a, w["w2"]), None

    ws = {"an": P[blk + "['attn']['norm']"], "wq": P[blk + "['attn']['wq']"],
          "wk": P[blk + "['attn']['wk']"], "wv": P[blk + "['attn']['wv']"],
          "wo": P[blk + "['attn']['wo']"], "mn": P[blk + "['mlp']['norm']"],
          "wg": P[blk + "['mlp']['wi_gate']"],
          "wu": P[blk + "['mlp']['wi_up']"], "w2": P[blk + "['mlp']['wo']"]}
    h, _ = jax.lax.scan(layer, h, ws)
    h = _rmsnorm(h, P["['final_norm']"].astype(p.compute), eps)
    if c["tie_embeddings"]:
        logits = mm("bsd,vd->bsv", h, P["['embed']['tok']"].astype(p.compute))
    else:
        logits = mm("bsd,dv->bsv", h,
                    P["['embed']['unembed']"].astype(p.compute))
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - gold)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


def _norms(tree) -> Dict[str, float]:
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))))
            for k, v in tree.items()}


def phi(G: int) -> np.ndarray:
    """The fraction of step t at which group g's update is generated."""
    g = np.arange(G, dtype=np.float32)
    return ((1.0 + 2.0 * (G - g) / G) / (1.0 + 2.0)).astype(np.float32)


def train(c: Dict[str, Any], job: Dict[str, Any], key, batches: List[dict],
          devices, p: Precision = EXACT, *, half_batch: bool = False,
          no_exchange: bool = False) -> Dict[str, Any]:
    """Run ``len(batches)`` steps of the job from the seed's weights.

    Returns the loss of each step (mean over workers and slices), each
    copy's per-leaf momentum norms after the first step that applies a
    gradient, each copy's per-leaf norms of the parameters' change over
    all the steps, and, for LayUp, each worker's version clocks. A copy is
    a LayUp worker, or DDP's one set of parameters. ``half_batch`` drops
    the second half of each worker's rows; ``no_exchange`` skips the
    gossip: the faults a check has to catch."""
    check_job(job)
    step = job["step"]
    layup = step["algo"] == "layup"
    M = int(job["workers"])
    copies = M if layup else 1
    R = int(step["fb_ratio"]) if layup else 1
    D = int(step["update_delay"]) if layup else 0
    shifts = ([s % M for s in step["shifts"] if s % M] or [1]) if layup \
        else [1]
    groups = sorted({path.split("'")[1] for path in param_shapes(c)})
    versions = np.zeros((M, len(groups)), np.float32)
    beta, lr = float(job["optimizer"]["beta"]), float(job["lr"])
    B = int(job["batch_per_worker"])
    store = p.storage or jnp.dtype(c["dtype"])
    ctx = jax.default_matmul_precision(p.matmul)

    with ctx:
        grad = jax.jit(jax.value_and_grad(
            lambda w, t, l: loss_fn(w, t, l, c, p)))
        fwd = jax.jit(lambda w, t, l: loss_fn(w, t, l, c, p))

        @jax.jit
        def update(theta, m, g):
            m = {k: beta * m[k] + g[k].astype(m[k].dtype) for k in m}
            u = {k: -jnp.float32(lr) * m[k] for k in m}
            return {k: theta[k] + u[k].astype(theta[k].dtype)
                    for k in theta}, m

        @jax.jit
        def mix(mine, recv, wk, rw):
            return {k: ((wk * mine[k].astype(jnp.float32)
                         + rw * recv[k].astype(jnp.float32))
                        / (wk + rw)).astype(mine[k].dtype) for k in mine}

        theta = [jax.device_put(init_params(c, key, store), devices[i])
                 for i in range(copies)]
        m = [jax.tree.map(jnp.zeros_like, t) for t in theta]
        fifo = [[jax.tree.map(jnp.zeros_like, t) for _ in range(D)]
                for t in theta]
        w = [1.0 / M] * M
        losses, m_norms = [], None
        first_applied = D
        for t, bt in enumerate(batches):
            step_losses, ddp_grads = [], []
            for i in range(M):
                rows = slice(i * B, (i + 1) * B)
                tok = bt["tokens"][rows]
                lab = bt["labels"][rows]
                if half_batch:
                    tok, lab = tok[:B // 2], lab[:B // 2]
                n = tok.shape[0] // R
                tok = jax.device_put(tok, devices[i])
                lab = jax.device_put(lab, devices[i])
                # the gradient comes back in the parameters' dtype, the
                # dtype the FIFO holds it in
                w_i = theta[i if layup else 0]
                if not layup:
                    w_i = jax.device_put(w_i, devices[i])
                l0, g = grad(w_i, tok[:n], lab[:n])
                ls = [l0] + [fwd(w_i, tok[r * n:(r + 1) * n],
                                 lab[r * n:(r + 1) * n]) for r in range(1, R)]
                step_losses.append(sum(float(x) for x in ls) / R)
                if not layup:
                    ddp_grads.append(jax.device_put(g, devices[0]))
                    continue
                if D:
                    fifo[i].append(g)
                    g = fifo[i].pop(0)
                theta[i], m[i] = update(theta[i], m[i], g)
                del g
            if ddp_grads:
                g = {k: (sum(x[k].astype(jnp.float32) for x in ddp_grads)
                         / M).astype(ddp_grads[0][k].dtype)
                     for k in ddp_grads[0]}
                theta[0], m[0] = update(theta[0], m[0], g)
                del g, ddp_grads
            if t == first_applied:
                m_norms = [_norms(mi) for mi in m]
            if layup and M > 1 and not no_exchange:
                s = shifts[t % len(shifts)]
                new_theta, new_w = [], []
                for j in range(M):
                    src = (j - s) % M
                    wk, rw = np.float32(w[j] * 0.5), np.float32(w[src] * 0.5)
                    recv = jax.device_put(theta[src], devices[j])
                    new_theta.append(mix(theta[j], recv, wk, rw))
                    new_w.append(float(np.float32(wk + rw)))
                theta, w = new_theta, new_w
                versions = np.maximum(versions, np.float32(t) + phi(
                    len(groups)))
            losses.append(float(np.mean(step_losses)))
        start = init_params(c, key, store)
        d_norms = []
        for i in range(copies):
            s0 = jax.device_put(start, devices[i])
            d_norms.append(_norms({k: theta[i][k].astype(jnp.float32)
                                   - s0[k].astype(jnp.float32)
                                   for k in theta[i]}))
    return {"losses": losses, "m_norms": m_norms, "d_norms": d_norms,
            "versions": versions if layup else None}
