"""Pallas TPU flash attention (forward), GQA + causal + sliding window.

TPU-native design (DESIGN.md §8): grid (B·Hq, Sq/bq, Sk/bk) with the KV
dimension innermost ("arbitrary" semantics); online-softmax statistics and
the output accumulator live in VMEM scratch and persist across the KV grid
steps. Block shapes keep the working set in VMEM and the matmul operands
MXU-aligned (bq, bk, head_dim multiples of 128 on real hardware; tests sweep
smaller shapes in interpret mode).

Validated in interpret mode against ``repro.kernels.ref.attention_ref``;
the training path uses the pure-jnp flash (custom VJP) in
``repro.models.layers`` — this kernel is the TPU deployment artifact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# grid (B·H, q blocks, kv blocks): the kv axis carries the online-softmax
# (or gradient) accumulators, so it runs in order
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _flash_kernel_lse(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                      l_ref, *, bq: int, bk: int, nk: int, causal: bool,
                      window: int, scale: float):
    """Forward kernel that also emits logsumexp (for the backward pass)."""
    _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                  bq=bq, bk=bk, nk=nk, causal=causal, window=window,
                  scale=scale)

    @pl.when(pl.program_id(2) == nk - 1)
    def _write_lse():
        lse_ref[0, 0] = (m_ref[...]
                         + jnp.log(jnp.maximum(l_ref[...], 1e-30)))[:, 0]


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  bq: int, bk: int, nk: int, causal: bool, window: int,
                  scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)          # (bk, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = (acc_ref[...] * corr
                    + jax.lax.dot_general(p.astype(v.dtype), v,
                                          (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32))

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False, return_lse: bool = False):
    """q: (B, Hq, Sq, d); k, v: (B, Hkv, Sk, d) → (B, Hq, Sq, d)
    [+ lse (B, Hq, Sq) when return_lse]."""
    B, Hq, Sq, d = q.shape
    _, Hkv, Sk, _ = k.shape
    G = Hq // Hkv
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    nq, nk = Sq // bq, Sk // bk
    scale = d ** -0.5

    kernel = functools.partial(
        _flash_kernel_lse if return_lse else _flash_kernel,
        bq=bq, bk=bk, nk=nk, causal=causal, window=window, scale=scale)

    grid = (B * Hq, nq, nk)

    def qmap(bh, qi, ki):
        return (bh // Hq, bh % Hq, qi, 0)

    def kvmap(bh, qi, ki):
        return (bh // Hq, (bh % Hq) // G, ki, 0)

    out_specs = pl.BlockSpec((1, 1, bq, d), qmap)
    out_shape = jax.ShapeDtypeStruct((B, Hq, Sq, d), q.dtype)
    if return_lse:
        lse_spec = pl.BlockSpec((1, 1, bq), lambda bh, qi, ki:
                                (bh // Hq, bh % Hq, qi))
        out_specs = (out_specs, lse_spec)
        out_shape = (out_shape, jax.ShapeDtypeStruct((B, Hq, Sq),
                                                     jnp.float32))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), qmap),
            pl.BlockSpec((1, 1, bk, d), kvmap),
            pl.BlockSpec((1, 1, bk, d), kvmap),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward kernels (flash bwd: recompute scores per block; two passes)
# ---------------------------------------------------------------------------


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, acc_ref, *, bq, bk, nk, causal, window,
                         scale):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)          # (bq, d)
    lse = lse_ref[0, 0][:, None]                   # (bq, 1)
    delta = delta_ref[0, 0][:, None]               # (bq, 1)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    acc_ref[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0, 0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, bq, bk, nq,
                          causal, window, scale):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0, 0].astype(jnp.float32) * scale
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0][:, None]
    delta = delta_ref[0, 0][:, None]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse)                           # (bq, bk)
    dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    dk_acc[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *, causal=True, window=0,
                        block_q=128, block_k=128, interpret=False):
    """Backward kernels. q/o/do: (B, Hq, Sq, d); k, v: (B, Hkv, Sk, d);
    lse: (B, Hq, Sq). Returns (dq, dk, dv) with GQA group-summing done
    on the per-q-head partials."""
    B, Hq, Sq, d = q.shape
    _, Hkv, Sk, _ = k.shape
    G = Hq // Hkv
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    nq, nk = Sq // bq, Sk // bk
    scale = d ** -0.5
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    def q_of(order):
        # order: which grid dim indexes the q blocks
        def f(bh, x, y):
            qi = x if order == 1 else y
            return (bh // Hq, bh % Hq, qi, 0)
        return f

    def kv_of(order):
        def f(bh, x, y):
            ki = x if order == 1 else y
            return (bh // Hq, (bh % Hq) // G, ki, 0)
        return f

    def lse_of(order):
        def f(bh, x, y):
            qi = x if order == 1 else y
            return (bh // Hq, bh % Hq, qi)
        return f

    # ---- pass 1: dq, grid (B·Hq, nq, nk) -----------------------------------
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, bq=bq, bk=bk, nk=nk,
                          causal=causal, window=window, scale=scale),
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), q_of(1)),
            pl.BlockSpec((1, 1, bk, d), kv_of(2)),
            pl.BlockSpec((1, 1, bk, d), kv_of(2)),
            pl.BlockSpec((1, 1, bq, d), q_of(1)),
            pl.BlockSpec((1, 1, bq), lse_of(1)),
            pl.BlockSpec((1, 1, bq), lse_of(1)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), q_of(1)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret, compiler_params=_COMPILER_PARAMS,
    )(q, k, v, do, lse, delta)

    # ---- pass 2: dk/dv per q-head, grid (B·Hq, nk, nq) ---------------------
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, bq=bq, bk=bk, nq=nq,
                          causal=causal, window=window, scale=scale),
        grid=(B * Hq, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), q_of(2)),
            pl.BlockSpec((1, 1, bk, d), kv_of(1)),
            pl.BlockSpec((1, 1, bk, d), kv_of(1)),
            pl.BlockSpec((1, 1, bq, d), q_of(2)),
            pl.BlockSpec((1, 1, bq), lse_of(2)),
            pl.BlockSpec((1, 1, bq), lse_of(2)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, bk, d),
                         lambda bh, ki, qi: (bh // Hq, bh % Hq, ki, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bh, ki, qi: (bh // Hq, bh % Hq, ki, 0)),
        ),
        out_shape=(jax.ShapeDtypeStruct((B, Hq, Sk, d), jnp.float32),
                   jax.ShapeDtypeStruct((B, Hq, Sk, d), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret, compiler_params=_COMPILER_PARAMS,
    )(q, k, v, do, lse, delta)
    # GQA: sum the per-q-head partials within each kv group
    dk = dk_h.reshape(B, Hkv, G, Sk, d).sum(axis=2).astype(k.dtype)
    dv = dv_h.reshape(B, Hkv, G, Sk, d).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


def flash_attention_trainable(q, k, v, *, causal=True, window=0,
                              block_q=128, block_k=128, interpret=False):
    """Differentiable flash attention: Pallas forward + Pallas backward
    (saves only out + lse; scores recomputed block-wise in the bwd)."""
    kw = dict(causal=causal, window=window, block_q=block_q,
              block_k=block_k, interpret=interpret)

    @jax.custom_vjp
    def run(q, k, v):
        return flash_attention(q, k, v, **kw)

    def fwd(q, k, v):
        o, lse = flash_attention(q, k, v, return_lse=True, **kw)
        return o, (q, k, v, o, lse)

    def bwd(res, do):
        return flash_attention_bwd(*res, do, **kw)

    run.defvjp(fwd, bwd)
    return run(q, k, v)
