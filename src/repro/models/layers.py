"""Shared neural-net building blocks (pure JAX, no flax).

Parameters are declared as ``ParamSpec`` trees (shape + logical axes +
initializer); ``init_params`` instantiates them and ``logical_axes`` extracts
the axis tree for the sharding rules in ``repro.launch.sharding``.
"""
from __future__ import annotations

import collections
import dataclasses
import zlib
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import splash
from repro.kernels.ops import auto_axes

# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ParamSpec:
    """Declarative parameter: shape, logical axes, initializer."""

    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"  # normal | zeros | ones | scaled
    scale: float = 0.02
    dtype: Any = None

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def init_params(rng: jax.Array, specs, dtype=jnp.float32):
    """Instantiate a ParamSpec tree into arrays (rng folded per leaf path)."""

    def make(path, spec: ParamSpec):
        key = jax.random.fold_in(rng, _path_hash(path))
        dt = spec.dtype or dtype
        if spec.init == "zeros":
            return jnp.zeros(spec.shape, dt)
        if spec.init == "ones":
            return jnp.ones(spec.shape, dt)
        scale = spec.scale
        return (jax.random.normal(key, spec.shape, jnp.float32) * scale).astype(dt)

    return jax.tree_util.tree_map_with_path(make, specs, is_leaf=is_spec)


def abstract_params(specs, dtype=jnp.float32):
    """ShapeDtypeStruct tree matching the spec tree (for dry-runs)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype or dtype), specs,
        is_leaf=is_spec)


def logical_axes(specs):
    return jax.tree.map(lambda s: s.axes, specs, is_leaf=is_spec)


def _path_hash(path) -> int:
    """Stable per-leaf fold-in value: the same in every process (the
    builtin ``hash`` of a str is salted per process)."""
    s = jax.tree_util.keystr(path)
    return zlib.crc32(s.encode()) % (2**31)


def param_count(params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm(x, gamma, eps=1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * gamma.astype(jnp.float32)).astype(x.dtype)


def rmsnorm_spec(d: int, axis: str = "embed") -> ParamSpec:
    return ParamSpec((d,), (axis,), init="ones")


# ---------------------------------------------------------------------------
# Rotary position embeddings (standard, partial, M-RoPE)
# ---------------------------------------------------------------------------


def _rope_freqs(head_dim: int, fraction: float, theta: float):
    rot_dim = int(head_dim * fraction)
    rot_dim -= rot_dim % 2
    inv = 1.0 / (theta ** (np.arange(0, rot_dim, 2, dtype=np.float32) / rot_dim))
    return rot_dim, jnp.asarray(inv)  # (rot_dim//2,)


def apply_rope(x, positions, *, theta=1e4, fraction=1.0):
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    head_dim = x.shape[-1]
    if theta <= 0:
        return x
    rot_dim, inv = _rope_freqs(head_dim, fraction, theta)
    if rot_dim == 0:
        return x
    ang = positions[..., :, None].astype(jnp.float32) * inv  # (..., S, rd/2)
    sin, cos = jnp.sin(ang)[..., None, :], jnp.cos(ang)[..., None, :]
    x_rot, x_pass = x[..., :rot_dim], x[..., rot_dim:]
    x1, x2 = x_rot[..., : rot_dim // 2], x_rot[..., rot_dim // 2:]
    out1 = (x1.astype(jnp.float32) * cos - x2.astype(jnp.float32) * sin)
    out2 = (x2.astype(jnp.float32) * cos + x1.astype(jnp.float32) * sin)
    return jnp.concatenate(
        [out1.astype(x.dtype), out2.astype(x.dtype), x_pass], axis=-1)


# M-RoPE (qwen2-vl): half-dim split into 3 sections fed by (t, h, w) ids.
_MROPE_FRACS = (0.25, 0.375, 0.375)


def apply_mrope(x, positions3, *, theta=1e6):
    """x: (B, S, H, D); positions3: (3, B, S) — temporal/height/width ids."""
    head_dim = x.shape[-1]
    half = head_dim // 2
    secs = [int(half * f) for f in _MROPE_FRACS]
    secs[-1] = half - secs[0] - secs[1]
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))
    inv = jnp.asarray(inv)  # (half,)
    # build per-frequency position ids by section
    pos = jnp.concatenate(
        [jnp.broadcast_to(positions3[i][..., None], positions3[i].shape + (secs[i],))
         for i in range(3)], axis=-1)  # (B, S, half)
    ang = pos.astype(jnp.float32) * inv  # (B, S, half)
    sin, cos = jnp.sin(ang)[..., None, :], jnp.cos(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out1 = x1.astype(jnp.float32) * cos - x2.astype(jnp.float32) * sin
    out2 = x2.astype(jnp.float32) * cos + x1.astype(jnp.float32) * sin
    return jnp.concatenate([out1.astype(x.dtype), out2.astype(x.dtype)], axis=-1)


def sinusoidal_positions(seq_len: int, d_model: int) -> jnp.ndarray:
    pos = np.arange(seq_len, dtype=np.float32)[:, None]
    dim = np.arange(0, d_model, 2, dtype=np.float32)[None, :]
    ang = pos / np.power(10000.0, dim / d_model)
    out = np.zeros((seq_len, d_model), np.float32)
    out[:, 0::2] = np.sin(ang)
    out[:, 1::2] = np.cos(ang)
    return jnp.asarray(out)


# ---------------------------------------------------------------------------
# Attention (GQA; chunked online-softmax "flash" in pure jnp)
# ---------------------------------------------------------------------------

NEG_INF = -1e30

# full-sequence attention calls traced in this process, by path
_PATH_COUNTS: collections.Counter = collections.Counter()


def attention_path_counts() -> Dict[str, int]:
    """How many full-sequence attention calls were traced on each path:
    ``{"kernel": n, "jnp": m}``. A trace-time count: a layer scan traces
    its body once."""
    return {"kernel": _PATH_COUNTS["kernel"], "jnp": _PATH_COUNTS["jnp"]}


def attention_path(q_shape, k_shape, *, causal: bool, window: int,
                   arange: bool) -> str:
    """``"kernel"`` where the TPU's block-sparse flash kernel
    (:mod:`repro.kernels.splash`) computes exactly this attention, else
    ``"jnp"`` (:func:`flash_attention_jnp`). q_shape (B, Sq, Hq, D),
    k_shape (B, Sk, Hkv, D); ``arange``: the positions are known statically
    to be ``arange(S)``, which the kernel derives from its block indices.

    The kernel needs the TPU, causal self-attention with no window, a
    sequence that its block divides, and no mesh axis that GSPMD would
    split it over: a compiled kernel cannot be partitioned, so inside a
    shard_map every axis left to GSPMD must have size 1, and a jit traced
    outside any mesh must see one device."""
    S = q_shape[1]
    blocks = splash.blocks_for(S)
    if jax.sharding.get_abstract_mesh().empty:
        split = jax.device_count() > 1
    else:
        split = any(n > 1 for n in auto_axes().values())
    ok = (jax.default_backend() == "tpu" and causal and window == 0
          and arange and S == k_shape[1] and not split
          and all(S % b == 0 and b % 128 == 0 for b in blocks))
    return "kernel" if ok else "jnp"


def causal_attention_kernel(q, k, v):
    """The kernel path of :func:`attention_path`, in
    :func:`flash_attention_jnp`'s layout: q, k (B, S, Hq|Hkv, D), v
    (B, S, Hkv, Dv), positions ``arange(S)``. The kernel's head-major view
    is a relabelling: XLA keeps S minor either way."""
    _PATH_COUNTS["kernel"] += 1
    t = lambda x: x.transpose(0, 2, 1, 3)
    scale = q.shape[-1] ** -0.5
    return t(splash.causal_attention(
        t(q * scale), t(k), t(v), interpret=jax.default_backend() != "tpu"))


def attention_specs(cfg, prefix_layers: Tuple[int, ...] = ()):
    """Projection specs for one attention sub-layer (optionally stacked)."""
    if cfg.mla:
        return latent_attention_specs(cfg, prefix_layers)
    d, hq, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    L = prefix_layers
    La = tuple("layers" for _ in L)
    sc = 0.02
    out = {
        "wq": ParamSpec(L + (d, hq, hd), La + ("embed", "heads", "hd"), scale=sc),
        "wk": ParamSpec(L + (d, hkv, hd), La + ("embed", "kv", "hd"), scale=sc),
        "wv": ParamSpec(L + (d, hkv, hd), La + ("embed", "kv", "hd"), scale=sc),
        "wo": ParamSpec(L + (hq, hd, d), La + ("heads", "hd", "embed"), init="scaled",
                        scale=sc / np.sqrt(max(2 * cfg.num_layers, 1))),
    }
    if cfg.qk_norm:
        out["q_norm"] = ParamSpec(L + (hd,), La + ("hd",), init="ones")
        out["k_norm"] = ParamSpec(L + (hd,), La + ("hd",), init="ones")
    return out


def latent_attention_specs(cfg, prefix_layers: Tuple[int, ...] = ()):
    """Multi-head latent attention without q compression (DeepSeek-V2/V3):
    ``wq`` to every head's q·k width, ``wkv_a`` to the kv latent and the
    one rotary key head, ``kv_norm`` on the latent, ``wkv_b`` from it to
    each head's key and value, ``wo`` from the values."""
    d, H, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    nope, rope, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    L = prefix_layers
    La = tuple("layers" for _ in L)
    sc = 0.02
    return {
        "wq": ParamSpec(L + (d, H, nope + rope), La + ("embed", "heads", "hd"),
                        scale=sc),
        "wkv_a": ParamSpec(L + (d, r + rope), La + ("embed", None), scale=sc),
        "kv_norm": ParamSpec(L + (r,), La + (None,), init="ones"),
        "wkv_b": ParamSpec(L + (r, H, nope + dv), La + (None, "heads", "hd"),
                           scale=sc),
        "wo": ParamSpec(L + (H, dv, d), La + ("heads", "hd", "embed"),
                        init="scaled",
                        scale=sc / np.sqrt(max(2 * cfg.num_layers, 1))),
    }


def _gqa_scores(q, k):
    """q: (B, Hkv, G, Sq, D), k: (B, Hkv, Sk, D) -> (B, Hkv, G, Sq, Sk) f32."""
    return jnp.einsum("bhgqd,bhkd->bhgqk", q, k,
                      preferred_element_type=jnp.float32)


def _block_mask(kp, q_positions, causal, window):
    """kp: (B, bk); q_positions: (B, Sq) → (B,1,1,Sq,bk) bool."""
    mask = (kp[:, None, None, None, :] >= 0)
    mask = jnp.broadcast_to(
        mask, (kp.shape[0], 1, 1, q_positions.shape[1], kp.shape[1]))
    if causal:
        mask = mask & (kp[:, None, None, None, :]
                       <= q_positions[:, None, None, :, None])
    if window > 0:
        mask = mask & ((q_positions[:, None, None, :, None]
                        - kp[:, None, None, None, :]) < window)
    return mask


def _flash_fwd(qh, kb, vb, kpos, q_positions, causal, window):
    """qh: (B,Hkv,G,Sq,D) pre-scaled; kb: (nblk,B,Hkv,bk,D); vb:
    (nblk,B,Hkv,bk,Dv); kpos: (nblk,B,bk). Returns (out_unnormalized→
    normalized, lse)."""
    B, Hkv, G, Sq, _ = qh.shape
    acc0 = jnp.zeros((B, Hkv, G, Sq, vb.shape[-1]), jnp.float32)
    m0 = jnp.full((B, Hkv, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)

    def body(carry, blk):
        acc, m, l = carry
        kblk, vblk, kp = blk
        s = _gqa_scores(qh, kblk)  # (B,Hkv,G,Sq,bk) f32
        s = jnp.where(_block_mask(kp, q_positions, causal, window), s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(vblk.dtype), vblk,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (acc_new, m_new, l_new), ()

    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), (kb, vb, kpos))
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out, lse


def flash_attention_jnp(q, k, v, *, q_positions, k_positions, causal=True,
                        window=0, block_k=1024):
    """Chunked online-softmax attention with a flash-style custom VJP:
    the backward pass RECOMPUTES per-block scores instead of saving the
    O(Sq·Sk) probability tensor (saves only out + logsumexp). This is the
    pure-jnp reference the Pallas kernel is validated against.

    q: (B, Sq, Hq, D);  k: (B, Sk, Hkv, D);  v: (B, Sk, Hkv, Dv).
    positions: (B, Sq) / (B, Sk) absolute token indices (negative = invalid).
    """
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    Dv = v.shape[-1]
    G = Hq // Hkv
    scale = D ** -0.5
    nblk = max(Sk // block_k, 1)
    bk = Sk // nblk
    assert Sk % nblk == 0, (Sk, block_k)

    _PATH_COUNTS["jnp"] += 1

    def prep(q, k, v, k_positions):
        qh = (q * scale).reshape(B, Sq, Hkv, G, D).transpose(0, 2, 3, 1, 4)
        kb = (k.transpose(0, 2, 1, 3)
              .reshape(B, Hkv, nblk, bk, D).transpose(2, 0, 1, 3, 4))
        vb = (v.transpose(0, 2, 1, 3)
              .reshape(B, Hkv, nblk, bk, Dv).transpose(2, 0, 1, 3, 4))
        kpos = k_positions.reshape(B, nblk, bk).transpose(1, 0, 2)
        return qh, kb, vb, kpos

    @jax.custom_vjp
    def run(q, k, v, q_pos, k_pos):
        qh, kb, vb, kpos = prep(q, k, v, k_pos)
        out, _ = _flash_fwd(qh, kb, vb, kpos, q_pos, causal, window)
        return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, Dv).astype(q.dtype)

    def run_fwd(q, k, v, q_pos, k_pos):
        qh, kb, vb, kpos = prep(q, k, v, k_pos)
        out, lse = _flash_fwd(qh, kb, vb, kpos, q_pos, causal, window)
        o = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, Dv).astype(q.dtype)
        return o, (q, k, v, q_pos, k_pos, out, lse)

    def run_bwd(res, do):
        q, k, v, q_pos, k_pos, out, lse = res
        qh, kb, vb, kpos = prep(q, k, v, k_pos)
        doh = (do.reshape(B, Sq, Hkv, G, Dv).transpose(0, 2, 3, 1, 4)
               .astype(jnp.float32))
        delta = jnp.sum(doh * out, axis=-1)  # (B,Hkv,G,Sq)

        dq0 = jnp.zeros((B, Hkv, G, Sq, D), jnp.float32)

        def body(dq_acc, blk):
            kblk, vblk, kp = blk
            s = _gqa_scores(qh, kblk)
            s = jnp.where(_block_mask(kp, q_pos, causal, window), s, NEG_INF)
            p = jnp.exp(s - lse[..., None])  # (B,Hkv,G,Sq,bk)
            dv = jnp.einsum("bhgqk,bhgqd->bhkd", p, doh,
                            preferred_element_type=jnp.float32)
            dp = jnp.einsum("bhgqd,bhkd->bhgqk", doh,
                            vblk.astype(jnp.float32),
                            preferred_element_type=jnp.float32)
            ds = p * (dp - delta[..., None])
            dq_acc = dq_acc + jnp.einsum(
                "bhgqk,bhkd->bhgqd", ds, kblk.astype(jnp.float32),
                preferred_element_type=jnp.float32)
            dk = jnp.einsum("bhgqk,bhgqd->bhkd", ds,
                            qh.astype(jnp.float32),
                            preferred_element_type=jnp.float32)
            return dq_acc, (dk, dv)

        dq_acc, (dkb, dvb) = jax.lax.scan(body, dq0, (kb, vb, kpos))
        dq = (dq_acc * scale).transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, D)
        dk = (dkb.transpose(1, 0, 3, 2, 4).reshape(B, Sk, Hkv, D))
        dv = (dvb.transpose(1, 0, 3, 2, 4).reshape(B, Sk, Hkv, Dv))
        f0 = lambda x: np.zeros(x.shape, jax.dtypes.float0)
        return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
                f0(q_pos), f0(k_pos))

    run.defvjp(run_fwd, run_bwd)
    return run(q, k, v, q_positions, k_positions)


def decode_attention_jnp(q, k_cache, v_cache, *, q_position, k_positions,
                         window=0, causal=True):
    """Single-token attention over a (possibly ring-buffered) cache.

    q: (B, 1, Hq, D); caches: (B, Sc, Hkv, D) and (B, Sc, Hkv, Dv);
    q_position: (B,);
    k_positions: (B, Sc) absolute positions per slot (negative = empty).
    """
    B, _, Hq, D = q.shape
    _, Sc, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    scale = D ** -0.5
    qh = (q * scale).reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bshd->bhgs", qh, k_cache,
                   preferred_element_type=jnp.float32)
    mask = k_positions >= 0
    if causal:
        mask &= k_positions <= q_position[:, None]
    if window > 0:
        mask &= (q_position[:, None] - k_positions) < window
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, Hq, v_cache.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLP (SwiGLU)
# ---------------------------------------------------------------------------


def mlp_specs(cfg, d_ff: int, prefix_layers: Tuple[int, ...] = ()):
    d = cfg.d_model
    L = prefix_layers
    La = tuple("layers" for _ in L)
    return {
        "wi_gate": ParamSpec(L + (d, d_ff), La + ("embed", "ffn")),
        "wi_up": ParamSpec(L + (d, d_ff), La + ("embed", "ffn")),
        "wo": ParamSpec(L + (d_ff, d), La + ("ffn", "embed"), init="scaled",
                        scale=0.02 / np.sqrt(max(2 * cfg.num_layers, 1))),
    }


def mlp_apply(p, x):
    h = jax.nn.silu(x @ p["wi_gate"]) * (x @ p["wi_up"])
    return h @ p["wo"]


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embed_specs(cfg):
    out = {"tok": ParamSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                            scale=0.02)}
    if not cfg.tie_embeddings:
        out["unembed"] = ParamSpec((cfg.d_model, cfg.vocab_size),
                                   ("embed", "vocab"), scale=0.02)
    return out


def embed_apply(p, tokens):
    return jnp.take(p["tok"], tokens, axis=0)


def unembed_apply(p, h, tie: bool):
    if tie:
        return jnp.einsum("...d,vd->...v", h, p["tok"],
                          preferred_element_type=jnp.float32)
    return jnp.einsum("...d,dv->...v", h, p["unembed"],
                      preferred_element_type=jnp.float32)


def cross_entropy(logits, labels):
    """logits: (..., V) f32; labels: (...) int32. Mean over all positions."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)
