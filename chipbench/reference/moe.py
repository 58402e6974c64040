"""Plain reference of a DeepSeek-V3-style MoE decoder trained by LayUp or DDP,
at one chip's share of an expert-parallel deployment.

Written from the published description (DeepSeek-V3, arXiv 2412.19437,
and the ``deepseek_v3`` config format) in straightforward ``jax.numpy``
and float32 at the highest matmul precision, importing nothing of the
program under test. The block:

- multi-head latent attention without q compression: q = x W_q, split
  into ``qk_nope_head_dim`` and ``qk_rope_head_dim`` columns per head;
  [c, k_pe] = x W_kva with c RMSNorm-ed (width ``kv_lora_rank``);
  [k_nope, v] = c W_kvb per head; rotary positions (rotate-half form) on
  q_pe and on the one k_pe head that every head shares; causal softmax
  over q·k at scale (nope + rope)^-1/2; o = p·v, then W_o;
- the first ``first_k_dense_replace`` layers: a gated SiLU MLP of width
  ``intermediate_size``;
- every other layer: scores s = sigmoid(x W_r) (or softmax) over all
  ``n_routed_experts``; the top ``num_experts_per_tok`` of s + b, b the
  correction bias (``noaux_tc``), chosen; gates the chosen s over their
  sum, times ``routed_scaling_factor``. This chip holds experts
  [0, n_routed_experts / ep_size): each of them computes every token
  densely and is masked by the gates (plain, not fast), and the absent
  experts' part is left out. The shared experts, one gated SiLU MLP of
  width n_shared_experts x moe_intermediate_size, are added once. The
  sequence-wise balance loss E/k Σ_e f_e P_e (per sequence, over the
  full-width scores normalised to sum one) is added to the loss with
  weight ``router_aux_weight``, summed over the layers;
- an untied LM head and the mean next-token cross-entropy.

Layers are computed one at a time under ``jax.checkpoint``, and attention
``ROWS`` query rows at a time, each block under ``jax.checkpoint`` too, so
that a whole layer's scores (16 heads x 8192^2 in float32, 4.3 GB) never
live at once.

The weights come from the seed by the configuration's stated rule (its
``assumed``): each matrix normal(0, 0.02), each output projection
0.02/sqrt(2 x layers), norm scales one, the correction bias zero, each
leaf drawn from the seed's key folded with the CRC32 of its path. The
LayUp lane and DDP are those of ``reference/dense.py``, written out again
over this block's leaves.
"""
from __future__ import annotations

import zlib
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.dense import (CONTROLS, EXACT, Precision,  # noqa: F401
                                       _mm, _norms, _rmsnorm, check_job, phi)

# the sizes this reference reads: a configuration file of the family states
# each one, under the published config.json's names where it has one
SIZES = ("num_hidden_layers", "hidden_size", "num_attention_heads",
         "num_key_value_heads", "intermediate_size", "vocab_size",
         "tie_word_embeddings", "rms_norm_eps", "rope_theta",
         "max_position_embeddings", "first_k_dense_replace",
         "n_routed_experts", "ep_size", "num_experts_per_tok",
         "moe_intermediate_size", "moe_layer_freq", "n_shared_experts",
         "scoring_func", "topk_method", "norm_topk_prob",
         "routed_scaling_factor", "n_group", "topk_group", "seq_aux",
         "router_aux_weight", "kv_lora_rank", "q_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "attention_bias", "hidden_act", "num_nextn_predict_layers",
         "model_type")
# the one value of each size that this block is written for
WRITTEN_FOR = {"attention_bias": False, "hidden_act": "silu",
               "q_lora_rank": None, "num_nextn_predict_layers": 0,
               "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
               "model_type": "deepseek_v3", "tie_word_embeddings": False}
# the kv latent's RMSNorm epsilon (the config's ``assumed``)
LATENT_EPS = 1e-6
# query rows per attention block
ROWS = 512


def check_config(c: Dict[str, Any], job: Dict[str, Any]) -> None:
    """Refuse a configuration or job this block is not written for."""
    bad = sorted(k for k, v in WRITTEN_FOR.items() if c[k] != v)
    if c["num_key_value_heads"] != c["num_attention_heads"]:
        bad.append("num_key_value_heads")
    if c["scoring_func"] not in ("sigmoid", "softmax"):
        bad.append("scoring_func")
    if c["topk_method"] not in ("noaux_tc", "greedy"):
        bad.append("topk_method")
    if int(job["seq_len"]) > c["max_position_embeddings"]:
        bad.append("max_position_embeddings")
    if bad:
        raise ValueError(f"the moe reference is not written for {bad}")


def held(c: Dict[str, Any]) -> int:
    return c["n_routed_experts"] // c["ep_size"]


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def param_shapes(c: Dict[str, Any]) -> Dict[str, Any]:
    """``{path: (shape, init, scale)}`` of every leaf."""
    L, d, H, V = (c["num_hidden_layers"], c["hidden_size"],
                  c["num_attention_heads"], c["vocab_size"])
    r, nope, rope, dv = (c["kv_lora_rank"], c["qk_nope_head_dim"],
                         c["qk_rope_head_dim"], c["v_head_dim"])
    lead = c["first_k_dense_replace"]
    E, F = c["n_routed_experts"], c["moe_intermediate_size"]
    Fs = c["n_shared_experts"] * F
    out_scale = 0.02 / np.sqrt(max(2 * L, 1))
    leaves = {}

    def attn(stack, n):
        p = f"['{stack}']['sub0']['attn']"
        leaves.update({
            p + "['norm']": ((n, d), "ones", 0.0),
            p + "['wq']": ((n, d, H, nope + rope), "normal", 0.02),
            p + "['wkv_a']": ((n, d, r + rope), "normal", 0.02),
            p + "['kv_norm']": ((n, r), "ones", 0.0),
            p + "['wkv_b']": ((n, r, H, nope + dv), "normal", 0.02),
            p + "['wo']": ((n, H, dv, d), "normal", out_scale),
        })

    def mlp(p, n, f, e=None):
        lead_shape = (n,) if e is None else (n, e)
        leaves.update({
            p + "['wi_gate']": (lead_shape + (d, f), "normal", 0.02),
            p + "['wi_up']": (lead_shape + (d, f), "normal", 0.02),
            p + "['wo']": (lead_shape + (f, d), "normal", out_scale),
        })

    if lead:
        attn("dense", lead)
        p = "['dense']['sub0']['mlp']"
        leaves[p + "['norm']"] = ((lead, d), "ones", 0.0)
        mlp(p, lead, c["intermediate_size"])
    n = L - lead
    attn("blocks", n)
    p = "['blocks']['sub0']['mlp']"
    leaves[p + "['norm']"] = ((n, d), "ones", 0.0)
    leaves[p + "['router']"] = ((n, d, E), "normal", 0.02)
    if c["topk_method"] == "noaux_tc":
        leaves[p + "['bias']"] = ((n, E), "zeros", 0.0)
    mlp(p, n, F, held(c))
    if Fs:
        mlp(p + "['shared']", n, Fs)
    leaves["['embed']['tok']"] = ((V, d), "normal", 0.02)
    leaves["['embed']['unembed']"] = ((d, V), "normal", 0.02)
    leaves["['final_norm']"] = ((d,), "ones", 0.0)
    return leaves


def init_params(c: Dict[str, Any], key, dtype) -> Dict[str, jax.Array]:
    out = {}
    for path, (shape, init, scale) in param_shapes(c).items():
        if init in ("ones", "zeros"):
            out[path] = (jnp.ones if init == "ones" else jnp.zeros)(shape,
                                                                   dtype)
            continue
        k = jax.random.fold_in(key, zlib.crc32(path.encode()) % (2**31))
        out[path] = (jax.random.normal(k, shape, jnp.float32)
                     * scale).astype(dtype)
    return out


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def _rope(x, S, theta):
    """Rotate-half rotary positions over all of x's last axis."""
    rot = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, rot, 2, dtype=np.float32) / rot))
    ang = np.arange(S, dtype=np.float32)[:, None] * inv[None]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    x1 = x[..., :rot // 2].astype(jnp.float32)
    x2 = x[..., rot // 2:].astype(jnp.float32)
    return jnp.concatenate([(x1 * cos - x2 * sin).astype(x.dtype),
                            (x2 * cos + x1 * sin).astype(x.dtype)], -1)


def _attention(q, k, v, p: Precision):
    """Causal softmax attention, ``ROWS`` queries at a time, each block
    under ``jax.checkpoint``."""
    mm = _mm(p)
    S, D = q.shape[1], q.shape[-1]

    def block(qa, k, v, a):
        s = mm("bqhd,bkhd->bhqk", qa, k).astype(jnp.float32) * D ** -0.5
        qi = np.arange(a, a + qa.shape[1])[:, None]
        s = jnp.where(jnp.asarray(np.arange(S)[None] <= qi), s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1).astype(p.compute)
        return mm("bhqk,bkhd->bqhd", w, v)

    return jnp.concatenate(
        [jax.checkpoint(block, static_argnums=(3,))(q[:, a:a + ROWS], k, v, a)
         for a in range(0, S, ROWS)], 1)


def _latent_attention(x, w, c, p: Precision):
    mm = _mm(p)
    B, S, _ = x.shape
    H, r = c["num_attention_heads"], c["kv_lora_rank"]
    nope, theta = c["qk_nope_head_dim"], c["rope_theta"]
    q = mm("bsd,dhk->bshk", x, w["wq"])
    kv = mm("bsd,dr->bsr", x, w["wkv_a"])
    lat = _rmsnorm(kv[..., :r], w["kv_norm"], LATENT_EPS)
    kvb = mm("bsr,rhk->bshk", lat, w["wkv_b"])
    k_pe = _rope(kv[..., None, r:], S, theta)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], S, theta)], -1)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_pe, (B, S, H, k_pe.shape[-1]))],
                        -1)
    o = _attention(q, k, kvb[..., nope:], p)
    return mm("bshk,hkd->bsd", o, w["wo"])


def _swiglu(x, wg, wu, wo, mm):
    a = jax.nn.silu(mm("...d,df->...f", x, wg)) * mm("...d,df->...f", x, wu)
    return mm("...f,fd->...d", a, wo)


def _experts(x, w, c, p: Precision):
    """This chip's share of one MoE layer and its balance loss."""
    mm = _mm(p)
    B, S, d = x.shape
    E, k = c["n_routed_experts"], c["num_experts_per_tok"]
    logits = mm("bsd,de->bse", x, w["router"]).astype(jnp.float32)
    if c["scoring_func"] == "sigmoid":
        s = jax.nn.sigmoid(logits)
    else:
        s = jax.nn.softmax(logits, -1)
    pick = s
    if c["topk_method"] == "noaux_tc":
        pick = s + jax.lax.stop_gradient(w["bias"].astype(jnp.float32))
    _, idx = jax.lax.top_k(pick, k)
    g = jnp.take_along_axis(s, idx, -1)
    if c["norm_topk_prob"]:
        g = g / (g.sum(-1, keepdims=True) + 1e-20)
    g = g * c["routed_scaling_factor"]
    # each held expert over every token, masked by its gate
    mine = jnp.einsum("bske,bsk->bse", jax.nn.one_hot(idx, held(c)), g)
    a = jax.nn.silu(mm("bsd,edf->ebsf", x, w["wg"])) * mm(
        "bsd,edf->ebsf", x, w["wu"])
    y = mm("ebsf,efd->ebsd", a, w["w2"])
    y = jnp.einsum("ebsd,bse->bsd", y.astype(jnp.float32), mine,
                   precision="highest").astype(p.compute)
    if "sg" in w:
        y = y + _swiglu(x, w["sg"], w["su"], w["s2"], mm)
    # sequence-wise balance loss over every expert's score
    f = jax.nn.one_hot(idx, E).sum(2).mean(1)              # (B, E)
    P = (s / s.sum(-1, keepdims=True)).mean(1)             # (B, E)
    balance = jnp.mean(E / k * jnp.sum(f * P, -1)) if c["seq_aux"] else (
        E / k * jnp.sum(f.mean(0) * P.mean(0)))
    return y, balance


def loss_fn(params, tokens, labels, c, p: Precision = EXACT):
    """Mean next-token cross-entropy of ``(rows, S)`` tokens, plus the
    weighted balance loss."""
    mm = _mm(p)
    eps = c["rms_norm_eps"]
    P = params
    h = jnp.take(P["['embed']['tok']"], tokens, axis=0).astype(p.compute)

    def weights(stack, moe):
        a, m = f"['{stack}']['sub0']['attn']", f"['{stack}']['sub0']['mlp']"
        w = {k: P[a + f"['{k}']"] for k in ("wq", "wkv_a", "kv_norm",
                                             "wkv_b", "wo")}
        w.update(an=P[a + "['norm']"], mn=P[m + "['norm']"],
                 wg=P[m + "['wi_gate']"], wu=P[m + "['wi_up']"],
                 w2=P[m + "['wo']"])
        if moe:
            w["router"] = P[m + "['router']"]
            if m + "['bias']" in P:
                w["bias"] = P[m + "['bias']"]
            if m + "['shared']['wo']" in P:
                s = m + "['shared']"
                w.update(sg=P[s + "['wi_gate']"], su=P[s + "['wi_up']"],
                         s2=P[s + "['wo']"])
        return w

    def layer(moe):
        @jax.checkpoint
        def run(h, w):
            w = {k: v.astype(p.compute) for k, v in w.items()}
            x = _rmsnorm(h, w["an"], eps)
            h = h + _latent_attention(x, w, c, p)
            x = _rmsnorm(h, w["mn"], eps)
            if not moe:
                return (h + _swiglu(x, w["wg"], w["wu"], w["w2"], mm),
                        jnp.zeros((), jnp.float32))
            y, balance = _experts(x, w, c, p)
            return h + y, balance
        return run

    aux = jnp.zeros((), jnp.float32)
    if c["first_k_dense_replace"]:
        h, _ = jax.lax.scan(layer(False), h, weights("dense", False))
    h, bal = jax.lax.scan(layer(True), h, weights("blocks", True))
    aux = aux + jnp.sum(bal)
    h = _rmsnorm(h, P["['final_norm']"].astype(p.compute), eps)
    logits = mm("bsd,dv->bsv", h,
                P["['embed']['unembed']"].astype(p.compute))
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - gold) + c["router_aux_weight"] * aux


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


def train(c: Dict[str, Any], job: Dict[str, Any], key, batches: List[dict],
          devices, p: Precision = EXACT, *, half_batch: bool = False,
          no_exchange: bool = False) -> Dict[str, Any]:
    """Run ``len(batches)`` steps of the job from the seed's weights, as
    ``reference/dense.py``'s ``train`` does over its block: the loss of
    each step, each copy's per-leaf momentum norms after the first step
    that applies a gradient, each copy's per-leaf norms of the
    parameters' change, and, for LayUp, each worker's version clocks."""
    check_job(job)
    check_config(c, job)
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

    with jax.default_matmul_precision(p.matmul):
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
        # the gradients waiting out the delay, kept in host memory: at 8k
        # tokens the grad's temporaries leave no room on the chip for them
        fifo = [[jax.tree.map(lambda a: np.zeros(a.shape, a.dtype), t)
                 for _ in range(D)] for t in theta]
        w = [1.0 / M] * M
        losses, m_norms = [], None
        for t, bt in enumerate(batches):
            step_losses, ddp_grads = [], []
            for i in range(M):
                rows = slice(i * B, (i + 1) * B)
                tok, lab = bt["tokens"][rows], bt["labels"][rows]
                if half_batch:
                    tok, lab = tok[:B // 2], lab[:B // 2]
                n = tok.shape[0] // R
                tok = jax.device_put(tok, devices[i])
                lab = jax.device_put(lab, devices[i])
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
                    fifo[i].append(jax.device_get(g))
                    g = fifo[i].pop(0)
                theta[i], m[i] = update(theta[i], m[i], g)
                del g
            if ddp_grads:
                g = {k: (sum(x[k].astype(jnp.float32) for x in ddp_grads)
                         / M).astype(ddp_grads[0][k].dtype)
                     for k in ddp_grads[0]}
                theta[0], m[0] = update(theta[0], m[0], g)
                del g, ddp_grads
            if t == D:
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
