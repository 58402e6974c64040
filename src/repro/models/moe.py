"""Mixture-of-Experts layer: a router over every expert, and the part of the
result that the experts held here give, with no token dropped.

A chip that shares an expert layer with ``cfg.ep_size - 1`` others holds
``cfg.experts_held`` of the ``num_experts`` routed experts, ``[first,
first + held)``. It scores every token against all of them with the
published rule (softmax, or DeepSeek-V3's sigmoid with a correction bias
that only selects), then groups the assignments that fall on its own
experts and runs grouped products over just those rows: the work follows
the rows routed here, not the T·k assignments nor a capacity. What the
absent experts would add is left to their chips. Shared experts, which
every chip computes for its own tokens, are added here once.

``GROUPS > 1`` selects the dry-run's capacity dispatch instead
(``launch/dryrun.py``): tokens in groups sharded over the expert axis,
each expert's rows in a buffer of fixed capacity, overflow dropped.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import ParamSpec, mlp_apply, mlp_specs


def moe_specs(cfg, prefix_layers: Tuple[int, ...] = ()):
    d, E, F = cfg.d_model, cfg.num_experts, cfg.expert_d_ff()
    H = cfg.experts_held
    L = prefix_layers
    La = tuple("layers" for _ in L)
    out = {
        "router": ParamSpec(L + (d, E), La + ("embed", None), scale=0.02),
        "wi_gate": ParamSpec(L + (H, d, F), La + ("experts", "embed", "ffn")),
        "wi_up": ParamSpec(L + (H, d, F), La + ("experts", "embed", "ffn")),
        "wo": ParamSpec(L + (H, F, d), La + ("experts", "ffn", "embed"),
                        init="scaled",
                        scale=0.02 / np.sqrt(max(2 * cfg.num_layers, 1))),
    }
    if cfg.topk_method == "noaux_tc":
        # the correction bias: it selects experts and takes no gradient
        out["bias"] = ParamSpec(L + (E,), La + (None,), init="zeros")
    if cfg.n_shared_experts:
        out["shared"] = mlp_specs(cfg, cfg.n_shared_experts * F, prefix_layers)
    return out


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------


def route(xt, p, cfg):
    """xt (T, d) → scores (T, E) f32 over every expert, the chosen experts
    (T, k) and their gates (T, k) f32."""
    logits = jnp.dot(xt, p["router"], preferred_element_type=jnp.float32)
    if cfg.scoring_func == "sigmoid":
        s = jax.nn.sigmoid(logits)
    elif cfg.scoring_func == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"scoring_func {cfg.scoring_func!r}")
    pick = s
    if "bias" in p:
        pick = s + jax.lax.stop_gradient(p["bias"].astype(jnp.float32))
    _, idx = jax.lax.top_k(pick, cfg.experts_per_token)
    gates = jnp.take_along_axis(s, idx, axis=1)
    if cfg.norm_topk_prob:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    return s, idx, gates * cfg.routed_scaling_factor


def balance_loss(s, idx, cfg, rows: int):
    """E/k · Σ_e f_e P_e, with f_e the share of tokens that chose expert e
    and P_e its mean normalised score, over each sequence when
    ``cfg.seq_aux`` (DeepSeek-V3 §2.1.2) or over all ``rows`` at once,
    averaged."""
    E, k = s.shape[1], idx.shape[1]
    groups = rows if cfg.seq_aux else 1
    f = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(1)
    sn = s / s.sum(-1, keepdims=True)
    f = f.reshape(groups, -1, E).mean(1)
    P = sn.reshape(groups, -1, E).mean(1)
    return jnp.mean(E / k * jnp.sum(f * P, -1))


# ---------------------------------------------------------------------------
# the held experts' products
# ---------------------------------------------------------------------------


@jax.custom_vjp
def _gather_rows(x, idx, back):
    """``x[idx]`` whose pullback is a gather too: ``back`` lists, for each
    row of ``x``, where its copies sit in the result, in (rows, copies)
    layout, so the cotangent is summed over copies without a scatter."""
    return x[idx]


def _gather_rows_fwd(x, idx, back):
    return x[idx], (back, x.shape[0])


def _gather_rows_bwd(res, ct):
    back, n = res
    g = ct[back.reshape(-1)].reshape(n, -1, ct.shape[-1])
    return g.sum(1, dtype=jnp.float32).astype(ct.dtype), None, None


_gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


def _permute(x, perm, inv):
    """``x[perm]`` for a permutation ``perm`` with inverse ``inv``."""
    return _gather_rows(x, perm, inv[:, None])


def grouped_matmul(x, w, sizes):
    """Rows of ``x`` in groups of ``sizes[g]`` rows times ``w[g]``, then
    the rows of no held group (``sizes[-1]`` of them, last), which give
    zeros and take no gradient. x (m, a), w (G, a, b), sizes (G + 1,).
    XLA's ragged dot, which the TPU's compiler turns into a tiled kernel
    over the groups' rows: custom calls named ``ragged-dot-*``, which
    carry none of the program's scopes. That kernel leaves the rows past
    the groups unwritten, in the result and in the rows' gradient, so
    both are masked here."""
    held = (jnp.arange(x.shape[0]) < jnp.sum(sizes[:-1]))[:, None]
    y = jax.lax.ragged_dot(jnp.where(held, x, 0), w, sizes[:-1])
    return jnp.where(held, y, 0)


def held_experts(p, xt, idx, gates, first: int = 0):
    """The part of the layer's output that the held experts
    ``[first, first + held)`` give: xt (T, d), the chosen experts and
    gates (T, k) → ((T, d) f32, rows routed here per held expert)."""
    T, d = xt.shape
    k = idx.shape[1]
    H = p["wi_gate"].shape[0]
    with jax.named_scope("router"):
        local = idx.reshape(-1) - first
        held = (local >= 0) & (local < H)
        key = jnp.where(held, local, H)
        order = jnp.argsort(key, stable=True)        # held groups first
        inv = jnp.argsort(order)
        sizes = jnp.bincount(key, length=H + 1).astype(jnp.int32)
        w = jnp.where(held.reshape(T, k), gates, 0.0)
    with jax.named_scope("experts"):
        # the T·k assignments in expert order: held rows lead, the rest
        # give zeros
        xs = _gather_rows(xt, order // k, inv.reshape(T, k))
        h = jax.nn.silu(grouped_matmul(xs, p["wi_gate"], sizes)) * \
            grouped_matmul(xs, p["wi_up"], sizes)
        out = grouped_matmul(h, p["wo"], sizes)
        back = _permute(out, inv, order).reshape(T, k, d)
        y = jnp.einsum("tkd,tk->td", back, w.astype(back.dtype),
                       preferred_element_type=jnp.float32)
    return y, sizes[:H]


def moe_apply(p, x, cfg, *, first: int = 0):
    """x: (B, S, d) → (B, S, d), aux: the balance loss and the held
    experts' load (``held_rows``, rows routed here; ``held_peak``, the
    busiest held expert's rows over the held mean)."""
    if GROUPS > 1:
        return capacity_apply(p, x, cfg)
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    with jax.named_scope("router"):
        s, idx, gates = route(xt, p, cfg)
        balance = balance_loss(s, idx, cfg, B)
    y, load = held_experts(p, xt, idx, gates, first)
    if "shared" in p:
        y = y + mlp_apply(p["shared"], xt).astype(jnp.float32)
    rows = jnp.sum(load).astype(jnp.float32)
    aux = {"balance": balance, "held_rows": rows,
           "held_peak": jnp.max(load) * load.shape[0] / jnp.maximum(rows, 1)}
    return y.astype(x.dtype).reshape(B, S, d), aux


def moe_apply_dense(p, x, cfg, *, first: int = 0):
    """Oracle: every held expert computes every token, masked by the gates.
    O(T·held) compute; only for tests on tiny shapes."""
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    _, idx, gates = route(xt, p, cfg)
    H = p["wi_gate"].shape[0]
    h = jax.nn.silu(jnp.einsum("td,edf->etf", xt, p["wi_gate"])) * \
        jnp.einsum("td,edf->etf", xt, p["wi_up"])
    full = jnp.einsum("etf,efd->etd", h, p["wo"])
    mask = jax.nn.one_hot(idx - first, H, dtype=jnp.float32)  # (T, k, H)
    w = jnp.einsum("tke,tk->te", mask, gates).astype(x.dtype)
    y = jnp.einsum("etd,te->td", full, w)
    if "shared" in p:
        y = y + mlp_apply(p["shared"], xt)
    return y.reshape(B, S, d)


# --- the dry-run's grouped capacity dispatch ---------------------------------
# With tokens replicated over the expert-parallel axis, GSPMD lowers a
# capacity-buffer scatter as full-buffer all-reduces (measured: 5.4 GB f32
# per MoE layer on mixtral/prefill_32k). Splitting tokens into GROUPS
# sharded over that axis makes the dispatch local per group; the
# group-sharded → expert-sharded buffer transpose is then a cheap
# all-to-all. Set by the dry-run (``--moe-groups``); 1 = the drop-free
# layer above.
GROUPS = 1
GROUP_PSPEC = None   # PartitionSpec for (G, ...) group-major tensors
EXPERT_PSPEC = None  # PartitionSpec for (E, ...) expert-major tensors


def capacity(tokens: int, num_experts: int, k: int, factor: float) -> int:
    c = int(math.ceil(tokens * k / num_experts * factor))
    return max(c, k)  # at least k slots so tiny smoke shapes work


def _wsc(x, spec):
    if spec is not None:
        x = jax.lax.with_sharding_constraint(x, spec)
    return x


def _dispatch_group(xt, p, cfg, C):
    """Local top-k dispatch of one token group. xt: (Tg, d).
    Returns (buf (E,C,d), combine metadata, router scores, choices)."""
    Tg, d = xt.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    probs, gate_idx, gate_vals = route(xt, p, cfg)

    # capacity slots: rank of each assignment within its expert
    flat_e = gate_idx.reshape(-1)  # (Tg*k,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    ranks = jnp.cumsum(onehot, axis=0) - onehot
    slot = jnp.take_along_axis(ranks, flat_e[:, None], axis=1)[:, 0]
    keep = slot < C

    tok_idx = jnp.repeat(jnp.arange(Tg), k)
    safe_e = jnp.where(keep, flat_e, 0)
    safe_s = jnp.where(keep, slot, C - 1)
    buf = jnp.zeros((E, C, d), xt.dtype)
    contrib = jnp.where(keep[:, None], xt[tok_idx], 0)
    buf = buf.at[safe_e, safe_s].add(contrib, mode="drop")
    meta = (tok_idx, safe_e, safe_s, keep, gate_vals)
    return buf, meta, probs, gate_idx


def _combine_group(out_buf, meta, Tg, d, dtype):
    tok_idx, safe_e, safe_s, keep, gate_vals = meta
    gathered = out_buf[safe_e, safe_s]  # (Tg*k, d)
    w = jnp.where(keep, gate_vals.reshape(-1), 0.0).astype(dtype)
    return jnp.zeros((Tg, d), dtype).at[tok_idx].add(gathered * w[:, None])


def _expert_ffn(p, buf):
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wi_gate"])) * \
        jnp.einsum("ecd,edf->ecf", buf, p["wi_up"])
    return jnp.einsum("ecf,efd->ecd", h, p["wo"])  # (E, C, d)


def capacity_apply(p, x, cfg):
    """The dry-run's dispatch over every expert (held = all): tokens in
    ``GROUPS`` groups sharded over the expert axis, each group's rows in
    an (E, C) buffer, the overflow past ``capacity_factor`` dropped; the
    group ↔ expert reshard lowers to an all-to-all."""
    B, S, d = x.shape
    T = B * S
    E, k = cfg.num_experts, cfg.experts_per_token
    G = GROUPS if T % GROUPS == 0 else 1
    Tg = T // G
    Cg = capacity(Tg, E, k, cfg.capacity_factor)
    xg = _wsc(x.reshape(G, Tg, d), GROUP_PSPEC)
    bufs, metas, probs, gate_idx = jax.vmap(
        lambda xg_: _dispatch_group(xg_, p, cfg, Cg))(xg)
    # (G, E, Cg, d) group-sharded → (E, G·Cg, d) expert-sharded: a2a
    ebuf = _wsc(bufs.transpose(1, 0, 2, 3).reshape(E, G * Cg, d),
                EXPERT_PSPEC)
    out = _expert_ffn(p, ebuf)
    # back: expert-sharded → group-sharded: second a2a
    og = _wsc(out.reshape(E, G, Cg, d).transpose(1, 0, 2, 3), GROUP_PSPEC)
    y = jax.vmap(lambda ob, m: _combine_group(ob, m, Tg, d, x.dtype)
                 )(og, metas)
    y = y.reshape(B, S, d)
    if "shared" in p:
        y = y + mlp_apply(p["shared"], x)
    balance = balance_loss(probs.reshape(T, E), gate_idx.reshape(T, k), cfg,
                           B)
    zero = jnp.zeros((), jnp.float32)
    return y, {"balance": balance, "held_rows": zero, "held_peak": zero}
