"""Jit'd dispatch wrappers for the Pallas kernels.

``interpret`` defaults to True on the CPU and False on a TPU, so the same
call sites work in both environments. The gossip lanes run the kernels
with ``use_pallas=True``; the model's attention takes its TPU kernel
(``repro.kernels.splash``) by itself where it applies. ``per_shard`` makes
a compiled kernel callable inside a partly manual ``shard_map``.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Dict

import jax
from jax.sharding import PartitionSpec as P

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.gossip_mix import gossip_mix as _gossip, gossip_mix_tree
from repro.kernels.quantize import dequant_mix as _dequant_mix
from repro.kernels.quantize import quantize_plane as _quantize_plane
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm
from repro.kernels.ssd_scan import ssd_scan as _ssd


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def auto_axes() -> Dict[str, int]:
    """``{name: size}`` of the axes of the mesh being traced that GSPMD
    partitions (not manual); empty outside a mesh."""
    mesh = jax.sharding.get_abstract_mesh()
    return {a: n for a, n, t in zip(mesh.axis_names, mesh.axis_sizes,
                                    mesh.axis_types)
            if t != jax.sharding.AxisType.Manual}


def per_shard(kernel: Callable) -> Callable:
    """Run a Pallas kernel inside a shard_map body that is manual over some
    mesh axes (the workers) and leaves the rest ('model') to GSPMD. XLA
    cannot partition a compiled (Mosaic) kernel over those, so the call is
    made manual over them too; its operands are replicated over them, and
    every shard computes its full copy."""
    def call(*args):
        auto = set(auto_axes())
        if not auto:
            return kernel(*args)
        return jax.shard_map(kernel, in_specs=P(), out_specs=P(),
                             axis_names=auto, check_vma=False)(*args)
    return call


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, block_q=128,
                    block_k=128, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _flash(q, k, v, causal=causal, window=window, block_q=block_q,
                  block_k=block_k, interpret=interpret)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk=128, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _ssd(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def gossip_mix(x, x_recv, upd, alpha, beta, *, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _gossip(x, x_recv, upd, alpha, beta, interpret=interpret)


@partial(jax.jit, static_argnames=("eps", "tile_rows", "interpret"))
def rmsnorm(x, gamma, *, eps=1e-5, tile_rows=256, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _rmsnorm(x, gamma, eps=eps, tile_rows=tile_rows,
                    interpret=interpret)


@partial(jax.jit, static_argnames=("tile_rows", "interpret"))
def quantize_plane(x, residual=None, *, tile_rows=256, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _quantize_plane(x, residual, tile_rows=tile_rows,
                           interpret=interpret)


@partial(jax.jit, static_argnames=("tile_rows", "interpret"))
def dequant_mix(x, q, scales, upd, alpha, beta, *, tile_rows=256,
                interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _dequant_mix(x, q, scales, upd, alpha, beta, tile_rows=tile_rows,
                        interpret=interpret)


__all__ = ["flash_attention", "ssd_scan", "gossip_mix", "gossip_mix_tree",
           "rmsnorm", "quantize_plane", "dequant_mix", "auto_axes",
           "per_shard"]
