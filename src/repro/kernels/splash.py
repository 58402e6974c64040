"""Causal self-attention as a block-sparse flash kernel: an adapter over the
splash attention kernel that ships with JAX
(``jax.experimental.pallas.ops.tpu.splash_attention``).

The kernel reads the causal mask as a block map, so a block of keys above
the diagonal is neither fetched nor computed (it would add exactly zero).
Softmax statistics and accumulators are f32; the forward saves only the
output and its logsumexp, and one fused backward kernel returns dq, dk and
dv in the inputs' dtype. Positions are the block indices, so the caller must
know the positions to be ``arange(S)`` (``repro.models.layers`` decides).
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.experimental.pallas.ops.tpu import splash_attention as SA

from repro.kernels.ops import per_shard

# The kernel reads q, k and v with the sequence minor: that is the layout
# XLA gives the projections' outputs, where head_dim 64 minor would be
# padded to 128 lanes and each operand relaid in HBM. On a v5e one
# attention sub-layer ran 1.45-1.95x faster forward, 1.25-1.50x forward
# and backward, than with head_dim minor (PERF.md).
#
# (query, key) rows per grid step of the forward, then of the fused
# backward: the fastest of 256, 512 and 1024 for each in a sweep on a v5e
# chip at (8, 16, 1024, 64) f32 and (2, 32, 4096, 64) bf16 (PERF.md;
# ``chipbench/tools/attention_sweep.py``)
BLOCKS = (1024, 1024, 1024, 1024)


def blocks_for(seq_len: int,
               blocks: Tuple[int, ...] = BLOCKS) -> Tuple[int, ...]:
    """The blocks the kernel takes at ``seq_len``: each of ``blocks``, or
    the whole sequence where it is shorter."""
    return tuple(min(b, seq_len) for b in blocks)


def causal_attention(q, k, v, *, blocks: Tuple[int, ...] = BLOCKS,
                     interpret: bool = False):
    """q: (B, Hq, S, D), already scaled; k: (B, Hkv, S, D), v: (B, Hkv, S,
    Dv), with Hkv dividing Hq → (B, Hq, S, Dv) in q's dtype (the kernel
    takes the value width on its own: latent attention's q·k at 192 and
    values at 128 need no padding). Every block of
    :func:`blocks_for` must divide S. Made manual over the mesh axes the
    caller leaves to GSPMD (:func:`~repro.kernels.ops.per_shard`)."""
    S = q.shape[2]
    bq, bkv, bq_dkv, bkv_dkv = blocks_for(S, blocks)
    seq_minor = SA.QKVLayout.SEQ_MINOR
    sizes = SA.BlockSizes(block_q=bq, block_kv=bkv, block_kv_compute=bkv,
                          block_q_dkv=bq_dkv, block_kv_dkv=bkv_dkv,
                          block_kv_dkv_compute=bkv_dkv,
                          use_fused_bwd_kernel=True, q_layout=seq_minor,
                          k_layout=seq_minor, v_layout=seq_minor)
    mask = SA.MultiHeadMask([SA.CausalMask((S, S))] * q.shape[1])
    kernel = SA.make_splash_mha(mask, block_sizes=sizes, head_shards=1,
                                q_seq_shards=1, interpret=interpret)
    return per_shard(jax.vmap(kernel))(q, k, v)
