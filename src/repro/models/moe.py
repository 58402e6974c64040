"""Mixture-of-Experts layer: a router over every expert, and the part of the
result that the experts held here give, with no token dropped.

A chip that shares an expert layer with ``cfg.ep_size - 1`` others holds
``cfg.experts_held`` of the ``num_experts`` routed experts, ``[first,
first + held)``. It scores every token against all of them with the
published rule (softmax, or DeepSeek-V3's sigmoid with a correction bias
that only selects), then sorts the T·k assignments so that the n falling
on its own experts lead, grouped by expert, in buffers of T·k rows (all
of them may be held). The grouped products (XLA's ragged dot) visit only
the groups' row tiles; every other op of the path, forward and pullback,
runs over the ``ceil(n / row_tile)`` tiles of those n rows: the gather
into expert order, the SiLU·up activation, the weighted scatter back into
the tokens. What the products leave in the rows past the held groups
(unwritten memory on the TPU) reaches no result and no gradient: the row
work selects those rows away inside its last tile and never visits the
tiles past it, and writes zeros there, into buffers made zero, so every
buffer a product reads is zero past n.
What the absent experts would add is left to their chips. Shared experts,
which every chip computes for its own tokens, are added here once.

``GROUPS > 1`` selects the dry-run's capacity dispatch instead
(``launch/dryrun.py``): tokens in groups sharded over the expert axis,
each expert's rows in a buffer of fixed capacity, overflow dropped.
"""
from __future__ import annotations

import functools
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

# Rows per tile of the row work: the TPU's ragged dot tiles the rows by 512
# too, so the tiles this path writes are the tiles the products visit.
ROW_TILE = 512


def row_tile(m: int) -> int:
    """The row tile over the T·k = m assignment rows."""
    return min(ROW_TILE, m)


def _over_tiles(n, body, init, tile: int):
    """``body(carry, start, valid)`` over the ``ceil(n / tile)`` row tiles
    that cover the first n rows of buffers of whole tiles: ``start`` the
    tile's first row, ``valid`` its rows under n."""
    def step(i, carry):
        start = i * tile
        return body(carry, start, start + jnp.arange(tile) < n)

    return jax.lax.fori_loop(0, (n + tile - 1) // tile, step, init)


def _tile(x, start, tile):
    return jax.lax.dynamic_slice_in_dim(x, start, tile)


def _put(buf, rows, start):
    return jax.lax.dynamic_update_slice_in_dim(buf, rows.astype(buf.dtype),
                                               start, 0)


def _to_tokens(rows, weight, src, n, T: int):
    """Σ over the first n rows r of ``rows[r] · weight[r]`` into token
    ``src[r]``, in f32: (m, d) → (T, d), a scatter-add over the held tiles
    that reads no row past n."""
    m, d = rows.shape
    tile = row_tile(m)

    def body(y, start, valid):
        r = _tile(rows, start, tile).astype(jnp.float32)
        if weight is not None:
            r = r * _tile(weight, start, tile)[:, None]
        return y.at[_tile(src, start, tile)].add(
            jnp.where(valid[:, None], r, 0))

    return _over_tiles(n, body, jnp.zeros((T, d), jnp.float32), tile)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_in(xt, src, n, T: int):
    """The held rows in expert order: row r < n is ``xt[src[r]]``, the
    rows past n zero; only the tiles over the first n rows are written,
    and the pullback sums those rows back into their tokens."""
    m, d = src.shape[0], xt.shape[1]
    tile = row_tile(m)

    def body(xs, start, valid):
        rows = xt[_tile(src, start, tile)]
        return _put(xs, jnp.where(valid[:, None], rows, 0), start)

    return _over_tiles(n, body, jnp.zeros((m, d), xt.dtype), tile)


def _rows_in_fwd(xt, src, n, T):
    return _rows_in(xt, src, n, T), (src, n)


def _rows_in_bwd(T, res, ct):
    src, n = res
    return _to_tokens(ct, None, src, n, T).astype(ct.dtype), None, None


_rows_in.defvjp(_rows_in_fwd, _rows_in_bwd)


def grouped_matmul(x, w, sizes):
    """Rows of ``x`` in groups of ``sizes[g]`` rows times ``w[g]``; the
    rows past the groups (``sizes[-1]`` assignments of no held expert,
    then the padding to whole tiles) no group takes. x (m, a), w (G, a,
    b), sizes (G + 1,). XLA's ragged dot,
    which the TPU's compiler turns into a tiled kernel over the groups'
    rows: custom calls named ``ragged-dot-*``, which carry none of the
    program's scopes. That kernel visits only the groups' tiles and
    leaves the rows past the groups unwritten, in the result and in the
    rows' gradient (the CPU's writes zeros): callers read no row there."""
    return jax.lax.ragged_dot(x, w, sizes[:-1])


@jax.custom_vjp
def _gate_up(xs, wg, wu, sizes, n):
    """``silu(xs @ wg[g]) * (xs @ wu[g])`` over the held groups' rows of
    xs (the first n), as a zero-filled (m, F) buffer: the activation runs
    over the tiles of the first n rows only, and reads no row past n of
    the products."""
    return _gate_up_fwd(xs, wg, wu, sizes, n)[0]


def _gate_up_fwd(xs, wg, wu, sizes, n):
    g, u = grouped_matmul(xs, wg, sizes), grouped_matmul(xs, wu, sizes)
    tile = row_tile(g.shape[0])

    def body(h, start, valid):
        gt = _tile(g, start, tile).astype(jnp.float32)
        a = jax.nn.silu(gt) * _tile(u, start, tile).astype(jnp.float32)
        return _put(h, jnp.where(valid[:, None], a, 0), start)

    h = _over_tiles(n, body, jnp.zeros(g.shape, g.dtype), tile)
    return h, (xs, wg, wu, sizes, n, g, u)


def _gate_up_bwd(res, dh):
    xs, wg, wu, sizes, n, g, u = res
    tile = row_tile(xs.shape[0])

    def body(carry, start, valid):
        dg, du = carry
        gt = _tile(g, start, tile).astype(jnp.float32)
        ut = _tile(u, start, tile).astype(jnp.float32)
        dht = _tile(dh, start, tile).astype(jnp.float32)
        sig = jax.nn.sigmoid(gt)
        v = valid[:, None]
        dg = _put(dg, jnp.where(v, dht * ut * sig * (1 + gt * (1 - sig)), 0),
                  start)
        du = _put(du, jnp.where(v, dht * gt * sig, 0), start)
        return dg, du

    def add(dx, start, valid):
        s = _tile(dx, start, tile) + _tile(dxu, start, tile)
        return _put(dx, jnp.where(valid[:, None], s, 0), start)

    zero = jnp.zeros(g.shape, g.dtype)
    dg, du = _over_tiles(n, body, (zero, zero), tile)
    dxg, dwg = jax.vjp(lambda x, w: grouped_matmul(x, w, sizes), xs, wg)[1](dg)
    dxu, dwu = jax.vjp(lambda x, w: grouped_matmul(x, w, sizes), xs, wu)[1](du)
    # the sum is written over dxg: past the held tiles it keeps what the
    # product left there, which only _rows_in's pullback reads, and that
    # over the held tiles alone
    dxs = _over_tiles(n, add, dxg, tile)
    return dxs, dwg, dwu, None, None


_gate_up.defvjp(_gate_up_fwd, _gate_up_bwd)


@jax.custom_vjp
def _combine(out, w, order, inv, src, n):
    """y[t] = Σ_j out[r] · w[t, j] over token t's assignments j to held
    experts, whose rows r (``order[r] = t·k + j``, ``inv`` its inverse,
    ``src[r] = t``) lie under n, in f32: (m, d) rows → (T, d). Forward and
    pullback run over the tiles of the first n rows only."""
    return _combine_fwd(out, w, order, inv, src, n)[0]


def _combine_fwd(out, w, order, inv, src, n):
    gate = jnp.pad(w.reshape(-1)[order], (0, out.shape[0] - order.shape[0]))
    y = _to_tokens(out, gate, src, n, w.shape[0])
    return y, (out, gate, inv, src, n)


def _combine_bwd(res, dy):
    out, gate, inv, src, n = res
    m, d = out.shape
    tile = row_tile(m)

    def body(carry, start, valid):
        dout, dgate = carry
        dyt = dy[_tile(src, start, tile)]
        ot = _tile(out, start, tile).astype(jnp.float32)
        gt = _tile(gate, start, tile)
        dout = _put(dout, jnp.where(valid[:, None], dyt * gt[:, None], 0),
                    start)
        dgate = _put(dgate, jnp.where(valid, jnp.sum(ot * dyt, -1), 0),
                     start)
        return dout, dgate

    dout, dgate = _over_tiles(n, body, (jnp.zeros((m, d), out.dtype),
                                        jnp.zeros((m,), gate.dtype)), tile)
    # the rows past n hold zero: an absent expert's gate gets none
    return dout, dgate[inv].reshape(dy.shape[0], -1), None, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def held_experts(p, xt, idx, gates, first: int = 0):
    """The part of the layer's output that the held experts
    ``[first, first + held)`` give: xt (T, d), the chosen experts and
    gates (T, k) → ((T, d) f32, rows routed here per held expert, row
    tiles the row work ran over)."""
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
        # the T·k assignments in expert order, in buffers of whole row
        # tiles: the n held rows lead, and the row work runs over the
        # tiles of those rows alone
        m = T * k
        tile = row_tile(m)
        n = jnp.sum(sizes[:H])
        src = jnp.pad(order // k, (0, -m % tile))
        xs = _rows_in(xt, src, n, T)
        h = _gate_up(xs, p["wi_gate"], p["wi_up"], sizes, n)
        out = grouped_matmul(h, p["wo"], sizes)
        y = _combine(out, w, order, inv, src, n)
        tiles = (n + tile - 1) // tile
    return y, sizes[:H], tiles


def moe_apply(p, x, cfg, *, first: int = 0):
    """x: (B, S, d) → (B, S, d), aux: the balance loss and the held
    experts' load (``held_rows``, rows routed here; ``held_peak``, the
    busiest held expert's rows over the held mean; ``held_tiles``, the
    row tiles the row work ran over, ``ceil(held_rows / row_tile(T·k))``)."""
    if GROUPS > 1:
        return capacity_apply(p, x, cfg)
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    with jax.named_scope("router"):
        s, idx, gates = route(xt, p, cfg)
        balance = balance_loss(s, idx, cfg, B)
    y, load, tiles = held_experts(p, xt, idx, gates, first)
    if "shared" in p:
        y = y + mlp_apply(p["shared"], xt).astype(jnp.float32)
    rows = jnp.sum(load).astype(jnp.float32)
    aux = {"balance": balance, "held_rows": rows,
           "held_peak": jnp.max(load) * load.shape[0] / jnp.maximum(rows, 1),
           "held_tiles": tiles.astype(jnp.float32)}
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
    return y, {"balance": balance, "held_rows": zero, "held_peak": zero,
               "held_tiles": zero}
