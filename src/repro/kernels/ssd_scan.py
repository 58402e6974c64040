"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

Grid (B·H, S/chunk) with the chunk dimension innermost ("arbitrary"): the
carried SSM state (N × P) lives in VMEM scratch and persists across chunk
steps. Each chunk step is matmul-heavy (the "dual form"): an intra-chunk
(chunk × chunk) masked attention-like product plus state ingest/emit
matmuls — all MXU work, which is exactly why SSD beats the sequential
Mamba1 scan on TPU.

B/C are shared across heads (ngroups=1) and indexed via the BlockSpec index
map, not broadcast. Validated in interpret mode against
``repro.kernels.ref.ssd_ref`` (sequential recurrence).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref, *,
                chunk: int, nc: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)    # (chunk, P)
    dt = dt_ref[0, 0].astype(jnp.float32)  # (chunk,)
    A = a_ref[0, 0]                        # scalar (negative decay rate)
    Bm = b_ref[0].astype(jnp.float32)      # (chunk, N)
    Cm = c_ref[0].astype(jnp.float32)      # (chunk, N)

    dA = dt * A                            # (chunk,)
    cum = jnp.cumsum(dA)                   # inclusive

    # ---- intra-chunk dual form ---------------------------------------------
    seg = cum[:, None] - cum[None, :]      # decay j→i
    causal = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    Lmat = jnp.where(causal, jnp.exp(seg), 0.0)
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    W = CB * Lmat * dt[None, :]
    y = jax.lax.dot_general(W, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # ---- contribution from the carried state -------------------------------
    decay_in = jnp.exp(cum)[:, None]       # (chunk, 1)
    y += decay_in * jax.lax.dot_general(
        Cm, state_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # ---- state update --------------------------------------------------------
    decay_out = jnp.exp(cum[-1] - cum) * dt          # (chunk,)
    ingest = jax.lax.dot_general(Bm * decay_out[:, None], x,
                                 (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (N, P)
    state_ref[...] = state_ref[...] * jnp.exp(cum[-1]) + ingest

    y_ref[0, 0] = y.astype(y_ref.dtype)


def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 128, interpret: bool = False):
    """x: (B, H, S, P); dt: (B, H, S); A: (H,); Bm/Cm: (B, S, N).

    Returns y: (B, H, S, P). (The model-side wrapper reshapes from/to the
    (B, S, H, P) layout and applies D-skip/gating outside the kernel.)
    """
    B, H, S, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk, nc=nc)
    grid = (B * H, nc)

    def xmap(bh, ci):
        return (bh // H, bh % H, ci, 0)

    def dtmap(bh, ci):
        return (bh // H, bh % H, ci)

    def amap(bh, ci):
        return (bh // H, bh % H)

    def bcmap(bh, ci):
        return (bh // H, ci, 0)

    a2 = jnp.broadcast_to(A.reshape(1, H), (B, H)).astype(jnp.float32)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), xmap),
            pl.BlockSpec((1, 1, chunk), dtmap),
            pl.BlockSpec((1, 1), amap),
            pl.BlockSpec((1, chunk, N), bcmap),
            pl.BlockSpec((1, chunk, N), bcmap),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, P), xmap),
        out_shape=jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
        # the chunk axis carries the SSM state scratch, so it runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(x, dt, a2, Bm, Cm)
