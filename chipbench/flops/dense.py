"""Operations a dense decoder's training step requires, from its shapes.

Only matrix products are counted, at two operations per multiply-add:
the q/k/v/o projections, the three MLP matrices, the LM head, and the
attention products QK^T and PV over the causal half of the score matrix
(S^2/2 pairs per sequence, not the S^2 a kernel may compute). Embedding
lookups, norms, softmax and the optimizer are not matrix products and
are left out. Recomputation (remat) is not counted: this is what the
algorithm needs, not what runs. The attention core's least HBM bytes are
counted too, for the kernel that computes it (``chipbench/kernels``).
"""
from __future__ import annotations

from typing import Any, Dict

# bytes of one element of each dtype a configuration may state
ITEMSIZE = {"float32": 4, "bfloat16": 2}


def params(c: Dict[str, Any]) -> int:
    """Parameters of the configuration, norm scales included."""
    d, f, V, L = c["d_model"], c["d_ff"], c["vocab_size"], c["num_layers"]
    q = c["num_heads"] * c["head_dim"]
    kv = c["num_kv_heads"] * c["head_dim"]
    layer = 2 * d + d * q + 2 * d * kv + q * d + 3 * d * f
    head = 0 if c["tie_embeddings"] else d * V
    return V * d + head + L * layer + d


def forward_flops_per_token(c: Dict[str, Any], seq: int) -> Dict[str, float]:
    """Forward matmul operations per token, split into the dense
    projections, the attention products and the LM head."""
    d, f, V, L = c["d_model"], c["d_ff"], c["vocab_size"], c["num_layers"]
    q = c["num_heads"] * c["head_dim"]
    kv = c["num_kv_heads"] * c["head_dim"]
    dense = L * 2 * (d * q + 2 * d * kv + q * d + 3 * d * f)
    # QK^T and PV, 2*q flops per (query, key) pair each, over seq/2 keys
    attention = L * 2 * 2 * q * (seq / 2)
    head = 2 * d * V
    return {"dense": float(dense), "attention": float(attention),
            "head": float(head)}


def step_flops_by_part(c: Dict[str, Any],
                       job: Dict[str, Any]) -> Dict[str, float]:
    """Matmul operations of one training step over all workers, by part
    of the model (``dense``, ``attention``, ``head``).

    LayUp: the forward over every token of the worker's rows and the
    backward (twice the forward) over the backward slice, 1/R of them;
    the R-1 forward-only slices get no backward. DDP: forward and backward
    over every token."""
    S = int(job["seq_len"])
    tokens = int(job["batch_per_worker"]) * S
    if job["step"]["algo"] == "layup":
        passes = tokens + 2 * tokens / int(job["step"]["fb_ratio"])
    else:
        passes = 3 * tokens
    return {part: f * passes * int(job["workers"])
            for part, f in forward_flops_per_token(c, S).items()}


def step_bytes_by_part(c: Dict[str, Any],
                       job: Dict[str, Any]) -> Dict[str, float]:
    """The least HBM bytes of one training step over all workers, for the
    parts a kernel computes that may be bound by bytes: the attention
    core, whose forward reads q, k and v and writes o, and whose backward
    reads q, k, v, o and o's gradient and writes the gradients of q, k and
    v, each once, in the configuration's dtype (softmax statistics left
    out). The dense projections and the head are bound by operations."""
    S = int(job["seq_len"])
    tokens = int(job["batch_per_worker"]) * S
    bwd = (tokens / int(job["step"]["fb_ratio"])
           if job["step"]["algo"] == "layup" else tokens)
    item = ITEMSIZE[c["dtype"]]
    q = c["num_heads"] * c["head_dim"]
    kv = c["num_kv_heads"] * c["head_dim"]
    per_layer = tokens * (2 * q + 2 * kv) + bwd * (4 * q + 4 * kv)
    return {"attention": float(c["num_layers"] * per_layer * item
                               * int(job["workers"]))}


def step_flops(c: Dict[str, Any], job: Dict[str, Any]) -> float:
    """Matmul operations of one training step over all workers: the sum of
    :func:`step_flops_by_part`."""
    return sum(step_flops_by_part(c, job).values())


def trained_tokens_per_step(job: Dict[str, Any]) -> int:
    """Tokens that enter a backward pass in one step, over all workers."""
    tokens = int(job["batch_per_worker"]) * int(job["seq_len"])
    if job["step"]["algo"] == "layup":
        tokens //= int(job["step"]["fb_ratio"])
    return tokens * int(job["workers"])
