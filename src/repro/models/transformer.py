"""Decoder-only transformer assembly: dense / MoE / SSM / hybrid / VLM.

Layers are scanned (`jax.lax.scan` over stacked parameter leaves) with remat
on the block body so 60-layer configs keep the HLO small and compile fast.
The hybrid (jamba) family scans over *super-blocks* of ``attn_layer_period``
sub-layers so the 1:7 mamba:attention interleave and the every-2nd-layer MoE
pattern stay homogeneous across scan steps.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.models.layers import ParamSpec

REMAT_POLICY = jax.checkpoint_policies.dots_with_no_batch_dims_saveable

# When True, layer scans are fully unrolled. Used by the dry-run's small
# (1- and 2-superblock) cost compiles: XLA cost_analysis counts a while-loop
# body once regardless of trip count, so unrolled lowerings give the true
# per-layer cost for the two-point fit (see launch/dryrun.py).
UNROLL_SCANS = False


def _scan(body, init, xs):
    return jax.lax.scan(body, init, xs, unroll=True if UNROLL_SCANS else 1)


# Optional PartitionSpec for the (B_local, S, d_model) hidden states. Set by
# the launcher for FSDP-style sharding (batch over the model axis → GSPMD
# gathers weights instead of all-reducing activations). None = let GSPMD
# propagate from the parameter shardings (baseline Megatron-TP behavior).
ACTIVATION_PSPEC = None


def constrain_h(h):
    if ACTIVATION_PSPEC is not None:
        h = jax.lax.with_sharding_constraint(h, ACTIVATION_PSPEC)
    return h


def remat_block(f):
    """Manual checkpointing with explicit residual + cotangent dtypes.

    ``jax.checkpoint`` + scan stacks f32 *copies* of the saved carries and
    emits f32 per-layer parameter cotangents (12.9 GB extra on
    stablelm-1.6b/train_4k). This wrapper pins residuals to exactly the
    block inputs and casts cotangents back to the input dtypes inside the
    loop, so the stacked buffers stay bf16.

    ``f(h, p, dc, ic)``: ``dc`` = differentiable consts (e.g. encoder
    states), ``ic`` = integer consts (positions — cotangent float0).
    Consts must be passed explicitly (custom_vjp cannot close over tracers).
    """

    @jax.custom_vjp
    def wrapped(h, p, dc, ic):
        return f(h, p, dc, ic)

    def fwd(h, p, dc, ic):
        return f(h, p, dc, ic), (h, p, dc, ic)

    def bwd(res, ct):
        h, p, dc, ic = res
        # the forward recomputed for the pullback; the pullback itself is
        # the backward, outside the scope
        with jax.named_scope("recompute"):
            _, vjp = jax.vjp(lambda h_, p_, dc_: f(h_, p_, dc_, ic),
                             h, p, dc)
        dh, dp, ddc = vjp(ct)
        cast = lambda t, like: jax.tree.map(
            lambda x, y: x.astype(y.dtype), t, like)
        ic_zeros = jax.tree.map(
            lambda x: np.zeros(x.shape, jax.dtypes.float0), ic)
        return cast(dh, h), cast(dp, p), cast(ddc, dc), ic_zeros

    wrapped.defvjp(fwd, bwd)
    return wrapped


# ---------------------------------------------------------------------------
# attention sub-layer
# ---------------------------------------------------------------------------


def attn_sublayer_specs(cfg, prefix):
    d = cfg.d_model
    La = tuple("layers" for _ in prefix)
    out = {"norm": ParamSpec(prefix + (d,), La + ("embed",), init="ones")}
    out.update(L.attention_specs(cfg, prefix))
    return out


def _project_qkv(p, x, cfg):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = L.rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = L.rmsnorm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


# the kv latent's RMSNorm epsilon: the default of DeepSeek-V3's published
# RMSNorm, which its kv_a_layernorm takes (the config's rms_norm_eps is not
# passed to it)
LATENT_NORM_EPS = 1e-6


def _project_latent(p, x, cfg, positions):
    """Latent attention's q, k (B, S, H, nope + rope) and v (B, S, H, dv):
    keys and values expanded from the normed kv latent, the one rotary key
    head shared by every head, rotary positions in rotate-half form."""
    H, r = cfg.num_heads, cfg.kv_lora_rank
    nope = cfg.qk_nope_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    with jax.named_scope("latent"):
        kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
        c = L.rmsnorm(kv[..., :r], p["kv_norm"], LATENT_NORM_EPS)
        kvb = jnp.einsum("bsr,rhk->bshk", c, p["wkv_b"])
        k_pe = L.apply_rope(kv[..., None, r:], positions, theta=cfg.rope_theta)
        q_pe = L.apply_rope(q[..., nope:], positions, theta=cfg.rope_theta)
        q = jnp.concatenate([q[..., :nope], q_pe], -1)
        k = jnp.concatenate(
            [kvb[..., :nope],
             jnp.broadcast_to(k_pe, k_pe.shape[:2] + (H, k_pe.shape[-1]))], -1)
    return q, k, kvb[..., nope:]


def _rope_qk(q, k, cfg, positions, mrope_pos):
    if cfg.mrope and mrope_pos is not None:
        return (L.apply_mrope(q, mrope_pos, theta=cfg.rope_theta),
                L.apply_mrope(k, mrope_pos, theta=cfg.rope_theta))
    return (L.apply_rope(q, positions, theta=cfg.rope_theta,
                         fraction=cfg.rope_fraction),
            L.apply_rope(k, positions, theta=cfg.rope_theta,
                         fraction=cfg.rope_fraction))


def attn_sublayer(p, h, cfg, *, positions, mrope_pos=None, window=0,
                  causal=True, block_k=1024):
    """Full-sequence attention (train / prefill). Returns (h', (k, v)).
    ``positions`` (B, S), or None for ``arange(S)`` in every row."""
    x = L.rmsnorm(h, p["norm"], cfg.norm_eps)
    B, S = h.shape[:2]
    hd, hkv = ((cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.num_heads)
               if cfg.mla else (cfg.head_dim, cfg.num_kv_heads))
    path = L.attention_path(
        (B, S, cfg.num_heads, hd), (B, S, hkv, hd), causal=causal,
        window=window, arange=positions is None and mrope_pos is None)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    if cfg.mla:
        q, k, v = _project_latent(p, x, cfg, positions)
    else:
        q, k, v = _project_qkv(p, x, cfg)
        q, k = _rope_qk(q, k, cfg, positions, mrope_pos)
    with jax.named_scope("attention"):
        if path == "kernel":
            out = L.causal_attention_kernel(q, k, v)
        else:
            out = L.flash_attention_jnp(q, k, v, q_positions=positions,
                                        k_positions=positions, causal=causal,
                                        window=window, block_k=block_k)
    o = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return h + o, (k, v)


def attn_sublayer_decode(p, h, cfg, cache, *, position, window=0):
    """One-token attention against the KV cache (possibly ring-buffered).

    cache: {"k": (B, Sc, Hkv, hd), "v": ...}; position: (B,).
    """
    B = h.shape[0]
    Sc = cache["k"].shape[1]
    x = L.rmsnorm(h, p["norm"], cfg.norm_eps)
    pos_b = position[:, None]  # (B,1)
    if cfg.mla:
        q, k, v = _project_latent(p, x, cfg, pos_b)
    else:
        q, k, v = _project_qkv(p, x, cfg)
        mp = (jnp.broadcast_to(position[None, :, None], (3, B, 1))
              if cfg.mrope else None)
        q, k = _rope_qk(q, k, cfg, pos_b, mp)
    slot = jnp.where(window > 0, position % Sc, jnp.minimum(position, Sc - 1))
    kc = jax.vmap(lambda c, s, u: jax.lax.dynamic_update_slice(c, u, (s, 0, 0))
                  )(cache["k"], slot, k)
    vc = jax.vmap(lambda c, s, u: jax.lax.dynamic_update_slice(c, u, (s, 0, 0))
                  )(cache["v"], slot, v)
    if window > 0:
        # ring buffer: slot i holds the largest pos' <= pos with pos' ≡ i (mod Sc)
        idx = jnp.arange(Sc)[None, :]
        k_positions = position[:, None] - ((position[:, None] - idx) % Sc)
    else:
        k_positions = jnp.broadcast_to(jnp.arange(Sc)[None, :], (B, Sc))
    out = L.decode_attention_jnp(q, kc, vc, q_position=position,
                                 k_positions=k_positions, window=window)
    o = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return h + o, {"k": kc, "v": vc}


def attn_cache_specs(cfg, B, seq_len, window, prefix=(), dtype=None):
    Sc = min(seq_len, window) if window > 0 else seq_len
    dt = dtype or cfg.dtype
    if cfg.mla:   # expanded keys and values, not the latent
        H = cfg.num_heads
        kd = prefix + (B, Sc, H, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
        vd = prefix + (B, Sc, H, cfg.v_head_dim)
    else:
        kd = vd = prefix + (B, Sc, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jax.ShapeDtypeStruct(kd, dt),
            "v": jax.ShapeDtypeStruct(vd, dt)}


# ---------------------------------------------------------------------------
# mlp / moe sub-layers
# ---------------------------------------------------------------------------


def mlp_sublayer_specs(cfg, prefix, *, use_moe):
    d = cfg.d_model
    La = tuple("layers" for _ in prefix)
    out = {"norm": ParamSpec(prefix + (d,), La + ("embed",), init="ones")}
    if use_moe:
        out.update(M.moe_specs(cfg, prefix))
    else:
        out.update(L.mlp_specs(cfg, cfg.d_ff, prefix))
    return out


def no_aux():
    """What a layer adds to the loss's aux: the experts' balance loss and
    the held experts' load (``models/moe.py``); zero without experts."""
    zero = jnp.zeros((), jnp.float32)
    return {"balance": zero, "held_rows": zero, "held_peak": zero,
            "held_tiles": zero}


def mlp_sublayer(p, h, cfg, *, use_moe):
    x = L.rmsnorm(h, p["norm"], cfg.norm_eps)
    if use_moe:
        y, aux = M.moe_apply(p, x, cfg)
        return h + y, aux
    return h + L.mlp_apply(p, x), no_aux()


def _add_aux(a, b):
    return {"balance": a["balance"] + b["balance"],
            "held_rows": a["held_rows"] + b["held_rows"],
            "held_peak": jnp.maximum(a["held_peak"], b["held_peak"]),
            "held_tiles": a["held_tiles"] + b["held_tiles"]}


# ---------------------------------------------------------------------------
# ssm sub-layer
# ---------------------------------------------------------------------------


def ssm_sublayer_specs(cfg, prefix):
    d = cfg.d_model
    La = tuple("layers" for _ in prefix)
    out = {"norm": ParamSpec(prefix + (d,), La + ("embed",), init="ones")}
    out.update(S.ssm_specs(cfg, prefix))
    return out


def ssm_sublayer(p, h, cfg, *, init_state=None, conv_tail=None,
                 return_state=False):
    x = L.rmsnorm(h, p["norm"], cfg.norm_eps)
    if return_state:
        y, st = S.ssm_block_apply(p, x, cfg, init_state=init_state,
                                  conv_tail=conv_tail, return_state=True)
        return h + y, st
    return h + S.ssm_block_apply(p, x, cfg), None


def ssm_sublayer_decode(p, h, cfg, cache):
    x = L.rmsnorm(h, p["norm"], cfg.norm_eps)
    y, (st, tail) = S.ssm_block_decode(p, x, cfg, cache["state"],
                                       cache["conv_tail"])
    return h + y, {"state": st, "conv_tail": tail}


def ssm_cache_specs(cfg, B, prefix=(), dtype=None):
    dt = dtype or cfg.dtype
    conv_dim = cfg.d_inner + 2 * cfg.ssm_state
    return {
        "state": jax.ShapeDtypeStruct(
            prefix + (B, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim), dt),
        "conv_tail": jax.ShapeDtypeStruct(
            prefix + (B, cfg.ssm_conv - 1, conv_dim), dt),
    }


# ---------------------------------------------------------------------------
# layer-type layout
# ---------------------------------------------------------------------------


def layer_kinds(cfg):
    """Per-layer (mixer_kind, use_moe): mixer_kind in {'attn','ssm'}."""
    kinds = []
    for l in range(cfg.num_layers):
        mixer = "attn" if cfg.is_attn_layer(l) else "ssm"
        kinds.append((mixer, cfg.is_moe_layer(l)))
    return kinds


def _superblock_period(cfg) -> int:
    """Scan period of the stack after the leading dense layers: smallest p
    such that layer kinds repeat with period p."""
    kinds = layer_kinds(cfg)[cfg.first_k_dense_replace:]
    n = len(kinds)
    for p in range(1, n + 1):
        if n % p:
            continue
        if all(kinds[i] == kinds[i % p] for i in range(n)):
            return p
    return n


def _stacks(cfg):
    """The scanned stacks, in order: ``(params key, kinds of one period,
    periods)``. Leading dense layers (``first_k_dense_replace``) are a
    stack of their own, ``"dense"``, before ``"blocks"``."""
    kinds = layer_kinds(cfg)
    lead = cfg.first_k_dense_replace
    out = []
    if lead:
        if len(set(kinds[:lead])) != 1:
            raise ValueError("leading dense layers of more than one kind")
        out.append(("dense", kinds[:1], lead))
    period = _superblock_period(cfg)
    out.append(("blocks", kinds[lead:lead + period],
                (cfg.num_layers - lead) // period))
    return out


# ---------------------------------------------------------------------------
# specs for the whole decoder stack
# ---------------------------------------------------------------------------


def _stack_specs(cfg, kinds, n):
    prefix = (n,)
    blocks: Dict[str, Any] = {}
    for i, (mixer, use_moe) in enumerate(kinds):
        sub: Dict[str, Any] = {}
        if mixer == "attn":
            sub["attn"] = attn_sublayer_specs(cfg, prefix)
        else:
            sub["ssm"] = ssm_sublayer_specs(cfg, prefix)
        if cfg.d_ff or cfg.num_experts:
            sub["mlp"] = mlp_sublayer_specs(cfg, prefix, use_moe=use_moe)
        blocks[f"sub{i}"] = sub
    return blocks


def decoder_specs(cfg) -> Dict[str, Any]:
    specs = {"embed": L.embed_specs(cfg),
             "final_norm": L.rmsnorm_spec(cfg.d_model)}
    for key, kinds, n in _stacks(cfg):
        specs[key] = _stack_specs(cfg, kinds, n)
    return specs


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------


def decoder_forward(params, h, cfg, *, positions, mrope_pos=None,
                    collect_cache=False, block_k=1024):
    """Run the full stack over a sequence of hidden states ``h`` (B,S,d).

    Returns (h, aux, cache|None): aux as :func:`no_aux`, summed over the
    layers (``held_peak`` their largest); cache leaves are stacked
    (n_super,...), under ``"dense"`` too where leading dense layers are.
    """
    ic = {"positions": positions}
    if mrope_pos is not None:
        ic["mrope"] = mrope_pos
    aux, caches = no_aux(), {}
    for key, kinds, _ in _stacks(cfg):
        h, (a, c) = _stack_forward(params[key], h, cfg, kinds, ic,
                                   collect_cache, block_k)
        aux = _add_aux(aux, {"balance": jnp.sum(a["balance"]),
                             "held_rows": jnp.sum(a["held_rows"]),
                             "held_peak": jnp.max(a["held_peak"]),
                             "held_tiles": jnp.sum(a["held_tiles"])})
        if key == "dense":
            caches["dense"] = c
        else:
            caches.update(c or {})
    return h, aux, caches if collect_cache else None


def _stack_forward(stack, h, cfg, kinds, ic, collect_cache, block_k):
    window = cfg.sliding_window

    def superblock(h, block_params, dc, ic):
        del dc
        h = constrain_h(h)
        positions = ic["positions"]
        mrope_pos = ic.get("mrope")
        aux_total = no_aux()
        caches = {}
        for i, (mixer, use_moe) in enumerate(kinds):
            sub = block_params[f"sub{i}"]
            if mixer == "attn":
                h, (k, v) = attn_sublayer(sub["attn"], h, cfg,
                                          positions=positions,
                                          mrope_pos=mrope_pos, window=window,
                                          block_k=block_k)
                if collect_cache:
                    caches[f"sub{i}"] = {"k": k, "v": v}
            else:
                h, st = ssm_sublayer(sub["ssm"], h, cfg,
                                     return_state=collect_cache)
                if collect_cache:
                    caches[f"sub{i}"] = {"state": st[0], "conv_tail": st[1]}
            if "mlp" in sub:
                h, aux = mlp_sublayer(sub["mlp"], h, cfg, use_moe=use_moe)
                aux_total = _add_aux(aux_total, aux)
        return h, (aux_total, caches if collect_cache else None)

    wrapped = remat_block(superblock)

    def body(h, block_params):
        return wrapped(h, block_params, {}, ic)

    return _scan(body, h, stack)


def decoder_decode_step(params, h, cfg, cache, *, position, window):
    """One-token step through the stack. h: (B,1,d); cache stacked (n_super,…)."""
    new_cache = {}
    for key, kinds, _ in _stacks(cfg):
        if key == "dense":
            h, new_cache["dense"] = _stack_decode(
                params[key], h, cfg, kinds, cache["dense"],
                position=position, window=window)
        else:
            h, c = _stack_decode(
                params[key], h, cfg, kinds,
                {k: v for k, v in cache.items() if k != "dense"},
                position=position, window=window)
            new_cache.update(c)
    return h, new_cache


def _stack_decode(stack, h, cfg, kinds, cache, *, position, window):
    def superblock(h, inp):
        block_params, block_cache = inp
        new_cache = {}
        for i, (mixer, _) in enumerate(kinds):
            sub = block_params[f"sub{i}"]
            if mixer == "attn":
                h, c = attn_sublayer_decode(sub["attn"], h, cfg,
                                            block_cache[f"sub{i}"],
                                            position=position, window=window)
            else:
                h, c = ssm_sublayer_decode(sub["ssm"], h, cfg,
                                           block_cache[f"sub{i}"])
            new_cache[f"sub{i}"] = c
            if "mlp" in sub:
                h, _ = mlp_sublayer(sub["mlp"], h, cfg,
                                    use_moe=kinds[i][1])
        return h, new_cache

    return _scan(superblock, h, (stack, cache))


def decoder_cache_specs(cfg, B, seq_len, window, dtype=None):
    out = {}
    for key, kinds, n in _stacks(cfg):
        prefix = (n,)
        specs = {}
        for i, (mixer, _) in enumerate(kinds):
            if mixer == "attn":
                specs[f"sub{i}"] = attn_cache_specs(cfg, B, seq_len, window,
                                                    prefix, dtype)
            else:
                specs[f"sub{i}"] = ssm_cache_specs(cfg, B, prefix, dtype)
        if key == "dense":
            out["dense"] = specs
        else:
            out.update(specs)
    return out
