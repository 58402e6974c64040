"""Operations a DeepSeek-V3-style MoE decoder's training step requires, at
one chip's share, from its shapes.

Only matrix products are counted, at two operations per multiply-add, by
part of the model:

- ``dense``: the latent attention's projections (q, the kv down- and
  up-projections, the output), the shared experts, and the leading dense
  layers' MLP;
- ``attention``: the latent attention core, q·k over the nope + rope
  width and p·v over the value width, over the causal half of the score
  matrix (S^2/2 pairs per sequence);
- ``experts``: the held routed experts' three products, over the rows
  routed to them at the balanced expectation: k · held / E of the tokens;
- ``router``: the router's scores over every expert;
- ``head``: the LM head over the vocabulary slice.

Norms, softmax, the sort and permute around the experts, and the
optimizer are not matrix products and are left out, as is recomputation.
The least HBM bytes are counted for the parts a kernel computes: the
attention core and the routed experts (``chipbench/kernels``).
"""
from __future__ import annotations

from typing import Any, Dict

# bytes of one element of each dtype a configuration may state
ITEMSIZE = {"float32": 4, "bfloat16": 2}


def _moe_layers(c: Dict[str, Any]) -> int:
    return c["num_hidden_layers"] - c["first_k_dense_replace"]


def _held(c: Dict[str, Any]) -> int:
    return c["n_routed_experts"] // c["ep_size"]


def params(c: Dict[str, Any]) -> int:
    """Parameters this chip holds, norm scales and correction bias
    included."""
    d, H, V = c["hidden_size"], c["num_attention_heads"], c["vocab_size"]
    r, nope, rope, dv = (c["kv_lora_rank"], c["qk_nope_head_dim"],
                         c["qk_rope_head_dim"], c["v_head_dim"])
    E, F = c["n_routed_experts"], c["moe_intermediate_size"]
    attn = (d + d * H * (nope + rope) + d * (r + rope) + r
            + r * H * (nope + dv) + H * dv * d)
    dense = attn + d + 3 * d * c["intermediate_size"]
    moe = (attn + d + d * E + (E if c["topk_method"] == "noaux_tc" else 0)
           + 3 * d * F * (_held(c) + c["n_shared_experts"]))
    return (2 * V * d + d + c["first_k_dense_replace"] * dense
            + _moe_layers(c) * moe)


def forward_flops_per_token(c: Dict[str, Any], seq: int) -> Dict[str, float]:
    """Forward matmul operations per token, by part."""
    d, H, V = c["hidden_size"], c["num_attention_heads"], c["vocab_size"]
    r, nope, rope, dv = (c["kv_lora_rank"], c["qk_nope_head_dim"],
                         c["qk_rope_head_dim"], c["v_head_dim"])
    L, lead, Lm = c["num_hidden_layers"], c["first_k_dense_replace"], \
        _moe_layers(c)
    E, k, F = (c["n_routed_experts"], c["num_experts_per_tok"],
               c["moe_intermediate_size"])
    proj = 2 * (d * H * (nope + rope) + d * (r + rope) + r * H * (nope + dv)
                + H * dv * d)
    shared = 3 * 2 * d * F * c["n_shared_experts"]
    lead_mlp = 3 * 2 * d * c["intermediate_size"]
    return {
        "dense": float(L * proj + Lm * shared + lead * lead_mlp),
        # q·k over nope + rope, p·v over dv, each over seq/2 keys
        "attention": float(L * 2 * H * (nope + rope + dv) * (seq / 2)),
        "experts": float(Lm * 3 * 2 * d * F * k * _held(c) / E),
        "router": float(Lm * 2 * d * E),
        "head": float(2 * d * V),
    }


def _passes(job: Dict[str, Any]):
    """(forward calls, tokens per call, backward calls) of one worker's
    step: LayUp's R forward slices and the backward of slice 0; DDP's one
    forward and backward over every row."""
    tokens = int(job["batch_per_worker"]) * int(job["seq_len"])
    if job["step"]["algo"] == "layup":
        R = int(job["step"]["fb_ratio"])
        return R, tokens / R, 1
    return 1, tokens, 1


def step_flops_by_part(c: Dict[str, Any],
                       job: Dict[str, Any]) -> Dict[str, float]:
    """Matmul operations of one training step over all workers, by part:
    the forward over every token of the worker's rows and the backward
    (twice the forward) over the backward slice (LayUp), or forward and
    backward over every token (DDP)."""
    fwd, per_call, bwd = _passes(job)
    passes = fwd * per_call + 2 * bwd * per_call
    return {part: f * passes * int(job["workers"])
            for part, f in forward_flops_per_token(
                c, int(job["seq_len"])).items()}


def step_bytes_by_part(c: Dict[str, Any],
                       job: Dict[str, Any]) -> Dict[str, float]:
    """The least HBM bytes of one training step over all workers, in the
    configuration's dtype, for the parts a kernel computes:

    - ``attention``: per forward token q, k (nope + rope) and v read, o
      written; per backward token q, k, v, o and o's gradient read and the
      gradients of q, k and v written (softmax statistics left out);
    - ``experts``: per forward call the held experts' three matrices read
      once and each routed row read and its output written; per backward
      call the matrices read and their gradients written, and each routed
      row's input and output gradient read and input gradient written."""
    fwd, per_call, bwd = _passes(job)
    item = ITEMSIZE[c["dtype"]]
    H, d = c["num_attention_heads"], c["hidden_size"]
    qk = H * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"])
    v = H * c["v_head_dim"]
    L, Lm = c["num_hidden_layers"], _moe_layers(c)
    attention = L * (fwd * per_call * (2 * qk + 2 * v)
                     + bwd * per_call * (2 * qk + 3 * v + 2 * qk + v))
    E, k, F = (c["n_routed_experts"], c["num_experts_per_tok"],
               c["moe_intermediate_size"])
    W = 3 * _held(c) * d * F
    rows = per_call * k * _held(c) / E
    experts = Lm * (fwd * (W + 2 * rows * d) + bwd * (2 * W + 3 * rows * d))
    M = int(job["workers"])
    return {"attention": float(attention * item * M),
            "experts": float(experts * item * M)}


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
