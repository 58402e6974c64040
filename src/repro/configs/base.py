"""Model & input-shape configuration system.

Every assigned architecture gets a ``ModelConfig`` in its own module under
``repro.configs``; the registry here exposes them by id for ``--arch`` flags.
``input_specs`` builds ShapeDtypeStruct stand-ins for dry-runs (no device
allocation), and ``reduced`` derives the CPU smoke-test variant of a config.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ModelConfig:
    """Architecture hyper-parameters (decoder-only unless ``enc_dec``)."""

    name: str
    family: str  # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // num_heads

    # MoE
    num_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0  # per-expert hidden dim (0 -> d_ff)
    moe_layer_period: int = 1  # MoE every k-th layer (jamba: 2)
    capacity_factor: float = 1.25  # the dry-run's grouped dispatch only
    router_aux_weight: float = 0.01  # the balance loss's weight (alpha)
    # DeepSeek-V3-style experts, under the names of the published config.json
    n_shared_experts: int = 0  # always-on experts: one SwiGLU of n x moe_d_ff
    first_k_dense_replace: int = 0  # leading dense layers (MLP width d_ff)
    scoring_func: str = "softmax"  # router scores: softmax | sigmoid
    topk_method: str = "greedy"  # greedy | noaux_tc (select on scores + bias)
    norm_topk_prob: bool = True  # the chosen scores normalised to sum 1
    routed_scaling_factor: float = 1.0  # gates scaled after normalising
    n_group: int = 1  # group-limited routing: only n_group == topk_group
    topk_group: int = 1
    seq_aux: bool = False  # balance loss per sequence, not per batch
    # chips sharing each expert layer; this one holds experts
    # [0, num_experts // ep_size) and routes over all of them
    ep_size: int = 1

    # SSM (mamba2-style SSD)
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    attn_layer_period: int = 0  # hybrid: attention every k-th layer (jamba: 8)

    # multi-head latent attention (DeepSeek-V2/V3) when kv_lora_rank > 0:
    # keys and values expanded from a normed latent of kv_lora_rank, q·k
    # over qk_nope_head_dim + qk_rope_head_dim, values of v_head_dim
    kv_lora_rank: int = 0
    q_lora_rank: Optional[int] = None  # a compressed q: not built
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # attention details
    sliding_window: int = 0  # 0 -> full attention
    rope_theta: float = 10000.0
    rope_fraction: float = 1.0  # stablelm2: 0.25
    qk_norm: bool = False  # qwen3
    mrope: bool = False  # qwen2-vl (3-axis positions)

    # encoder-decoder (whisper)
    enc_dec: bool = False
    enc_layers: int = 0
    enc_seq: int = 1500  # whisper audio frames after conv stub

    # frontend stub: None | 'audio' | 'vision'
    frontend: Optional[str] = None

    tie_embeddings: bool = True
    norm_eps: float = 1e-5
    dtype: Any = jnp.float32  # compute/param dtype (bf16 for dry-runs)

    source: str = ""  # citation

    # published config.json keys of which one value is built
    attention_bias: bool = False
    hidden_act: str = "silu"
    num_nextn_predict_layers: int = 0
    max_position_embeddings: int = 0  # 0: not stated
    model_type: str = ""

    # a published config.json's names for the sizes above (HF_NAMES); each
    # is taken in place of its own field's value and reads None after
    hidden_size: Optional[int] = None
    num_hidden_layers: Optional[int] = None
    num_attention_heads: Optional[int] = None
    num_key_value_heads: Optional[int] = None
    intermediate_size: Optional[int] = None
    n_routed_experts: Optional[int] = None
    num_experts_per_tok: Optional[int] = None
    moe_intermediate_size: Optional[int] = None
    moe_layer_freq: Optional[int] = None
    rms_norm_eps: Optional[float] = None
    tie_word_embeddings: Optional[bool] = None

    def __post_init__(self):
        for alias, name in HF_NAMES.items():
            v = getattr(self, alias)
            if v is not None:
                object.__setattr__(self, name, v)
                object.__setattr__(self, alias, None)
        if self.head_dim == 0 and self.num_heads > 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        unbuilt = {"attention_bias": self.attention_bias,
                   "hidden_act": self.hidden_act != "silu",
                   "q_lora_rank": self.q_lora_rank is not None,
                   "num_nextn_predict_layers": self.num_nextn_predict_layers,
                   "topk_group": self.topk_group != self.n_group,
                   "ep_size": self.ep_size < 1 or (
                       self.num_experts % self.ep_size != 0)}
        bad = sorted(k for k, v in unbuilt.items() if v)
        if bad:
            raise ValueError(f"{self.name}: no model is built for the "
                             f"stated {bad}")

    # ---- derived quantities -------------------------------------------------
    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def d_inner(self) -> int:
        """SSM inner width."""
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    def is_attn_layer(self, l: int) -> bool:
        """Hybrid interleave: layer ``l`` is attention iff period says so."""
        if self.family == "ssm":
            return False
        if self.attn_layer_period <= 0:
            return True
        # jamba: 1 attention layer per `period` block, at position period//2
        return (l % self.attn_layer_period) == self.attn_layer_period // 2

    def is_moe_layer(self, l: int) -> bool:
        if self.num_experts == 0 or l < self.first_k_dense_replace:
            return False
        l -= self.first_k_dense_replace
        return (l % self.moe_layer_period) == self.moe_layer_period - 1

    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def experts_held(self) -> int:
        """Routed experts this chip holds: ``[0, experts_held)``."""
        return self.num_experts // self.ep_size

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    def with_(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # ---- parameter counting (for roofline MODEL_FLOPS) ----------------------
    def param_counts(self) -> Dict[str, float]:
        """Approximate total and active parameter counts."""
        d, V = self.d_model, self.vocab_size
        embed = V * d * (1 if self.tie_embeddings else 2)

        def attn_params():
            if self.mla:
                H, r = self.num_heads, self.kv_lora_rank
                qk = self.qk_nope_head_dim + self.qk_rope_head_dim
                return (d * H * qk + d * (r + self.qk_rope_head_dim) + r
                        + r * H * (self.qk_nope_head_dim + self.v_head_dim)
                        + H * self.v_head_dim * d)
            return d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d

        def dense_mlp():
            return 3 * d * self.d_ff  # swiglu

        def moe_mlp(active: bool):
            e = self.experts_per_token if active else self.num_experts
            e += self.n_shared_experts
            # experts (swiglu) + router
            return 3 * d * self.expert_d_ff() * e + d * self.num_experts

        def ssm_params():
            di = self.d_inner
            # in_proj (z,x,B,C,dt) + conv + out_proj (mamba2-ish)
            return d * (2 * di + 2 * self.ssm_state + self.ssm_heads) + di * self.ssm_conv + di * d

        total = embed
        active = embed
        n_layers = self.num_layers + (self.enc_layers if self.enc_dec else 0)
        for l in range(self.num_layers):
            if self.family in ("ssm", "hybrid") and not self.is_attn_layer(l):
                total += ssm_params(); active += ssm_params()
            else:
                total += attn_params(); active += attn_params()
                if self.enc_dec:  # cross attention in decoder
                    total += attn_params(); active += attn_params()
            if self.is_moe_layer(l):
                total += moe_mlp(False); active += moe_mlp(True)
            else:
                total += dense_mlp(); active += dense_mlp()
        if self.enc_dec:
            for _ in range(self.enc_layers):
                total += attn_params() + dense_mlp()
                active += attn_params() + dense_mlp()
        return {"total": float(total), "active": float(active)}


# a published Hugging Face config.json's key → the field it sets
HF_NAMES = {
    "hidden_size": "d_model", "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff", "n_routed_experts": "num_experts",
    "num_experts_per_tok": "experts_per_token",
    "moe_intermediate_size": "moe_d_ff", "moe_layer_freq": "moe_layer_period",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
}


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # 'train' | 'prefill' | 'decode'


INPUT_SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, ModelConfig] = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    _ensure_loaded()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_configs():
    _ensure_loaded()
    return sorted(_REGISTRY)


_ARCH_MODULES = [
    "jamba_v0_1_52b", "qwen2_vl_2b", "mamba2_780m", "mixtral_8x7b",
    "granite_8b", "qwen3_moe_30b_a3b", "yi_34b", "stablelm_1_6b",
    "moonlight_16b_a3b", "whisper_large_v3", "gpt2_medium", "gpt2_xl",
]

_LOADED = False


def _ensure_loaded():
    global _LOADED
    if _LOADED:
        return
    import importlib
    for m in _ARCH_MODULES:
        importlib.import_module(f"repro.configs.{m}")
    _LOADED = True


# ---------------------------------------------------------------------------
# Reduced (smoke-test) variants
# ---------------------------------------------------------------------------


def reduced(cfg: ModelConfig) -> ModelConfig:
    """Tiny same-family variant: ≤2 layers, d_model ≤ 512, ≤4 experts."""
    kw: Dict[str, Any] = dict(
        name=cfg.name + "-reduced",
        num_layers=2,
        d_model=256,
        d_ff=512,
        vocab_size=512,
        head_dim=32,
        num_heads=4,
        num_kv_heads=min(cfg.num_kv_heads, 2) if cfg.num_kv_heads < cfg.num_heads else 4,
        dtype=jnp.float32,
    )
    if cfg.num_experts:
        kw.update(num_experts=4,
                  experts_per_token=min(cfg.experts_per_token, 2),
                  moe_d_ff=128, ep_size=1)
    if cfg.mla:
        kw.update(kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
                  v_head_dim=32)
    if cfg.ssm_state:
        kw.update(ssm_state=16, ssm_head_dim=32)
    if cfg.attn_layer_period:
        # keep the hybrid interleave visible with 2 layers: attn at layer 1
        kw.update(attn_layer_period=2)
    if cfg.enc_dec:
        kw.update(enc_layers=2, enc_seq=16)
    if cfg.sliding_window:
        kw.update(sliding_window=16)
    return cfg.with_(**kw)


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins; no allocation)
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: ShapeConfig,
                dtype=None) -> Dict[str, jax.ShapeDtypeStruct]:
    """Abstract inputs for one step of the given kind.

    For the decode kinds the KV-cache/SSM-state specs are built by the model
    (they depend on layer structure); this returns the *data* inputs only.
    """
    dtype = dtype or cfg.dtype
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train":
        specs: Dict[str, jax.ShapeDtypeStruct] = {}
        if cfg.frontend == "vision":
            # stubbed frontend: mixed text+patch embeddings (see DESIGN.md §6)
            specs["embeds"] = sds((B, S, cfg.d_model), dtype)
            specs["positions"] = sds((3, B, S), i32)  # M-RoPE t/h/w
        elif cfg.frontend == "audio":
            specs["audio_embeds"] = sds((B, cfg.enc_seq, cfg.d_model), dtype)
            specs["tokens"] = sds((B, S), i32)
        else:
            specs["tokens"] = sds((B, S), i32)
        specs["labels"] = sds((B, S), i32)
        return specs
    if shape.kind == "prefill":
        specs = {}
        if cfg.frontend == "vision":
            specs["embeds"] = sds((B, S, cfg.d_model), dtype)
            specs["positions"] = sds((3, B, S), i32)
        elif cfg.frontend == "audio":
            specs["audio_embeds"] = sds((B, cfg.enc_seq, cfg.d_model), dtype)
            specs["tokens"] = sds((B, S), i32)
        else:
            specs["tokens"] = sds((B, S), i32)
        return specs
    if shape.kind == "decode":
        specs = {"token": sds((B, 1), i32), "position": sds((B,), i32)}
        if cfg.frontend == "audio":
            # cross-attention context (encoder output) is part of the cache
            pass
        return specs
    raise ValueError(shape.kind)
