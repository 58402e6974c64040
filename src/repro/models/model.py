"""Unified model API: ``build_model(cfg)`` → a ``Model`` bundle of pure fns.

Every architecture family exposes the same surface:
  specs()                  ParamSpec tree (shapes + logical sharding axes)
  init(rng)                materialized params
  loss_fn(params, batch)   (scalar loss, metrics dict) — teacher-forced LM
  prefill_fn(params, batch)→ (cache, last_logits)
  decode_fn(params, cache, token, position) → (logits, new_cache)
  cache_specs(B, seq_len)  ShapeDtypeStruct tree for serve_step dry-runs
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec as ED
from repro.models import layers as L
from repro.models import transformer as T


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    specs: Any
    loss_fn: Callable
    prefill_fn: Callable
    decode_fn: Callable
    cache_specs: Callable

    def init(self, rng, dtype=None):
        return L.init_params(rng, self.specs, dtype or self.cfg.dtype)

    def abstract_params(self, dtype=None):
        return L.abstract_params(self.specs, dtype or self.cfg.dtype)

    def logical_axes(self):
        return L.logical_axes(self.specs)


# ---------------------------------------------------------------------------
# decoder-only families (dense / moe / ssm / hybrid / vlm)
# ---------------------------------------------------------------------------


def _decoder_embed_inputs(params, batch, cfg):
    """Embed tokens or accept stubbed embeddings; produce positions:
    the batch's M-RoPE ids, or None for token batches, whose positions are
    ``arange(S)`` in every row, known statically (the attention kernel
    takes only those)."""
    if cfg.frontend == "vision":
        h = batch["embeds"]
        mrope_pos = batch["positions"]  # (3, B, S)
        positions = mrope_pos[0]  # temporal axis doubles as causal order
    else:
        h = L.embed_apply(params["embed"], batch["tokens"])
        positions = mrope_pos = None
    return h, positions, mrope_pos


def _build_decoder_model(cfg: ModelConfig) -> Model:
    specs = T.decoder_specs(cfg)

    def loss_fn(params, batch, *, block_k=1024):
        h, positions, mrope_pos = _decoder_embed_inputs(params, batch, cfg)
        h, aux, _ = T.decoder_forward(params, h, cfg, positions=positions,
                                      mrope_pos=mrope_pos, block_k=block_k)
        with jax.named_scope("head"):
            h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
            logits = L.unembed_apply(params["embed"], h, cfg.tie_embeddings)
            ce = L.cross_entropy(logits, batch["labels"])
        loss = ce + cfg.router_aux_weight * aux["balance"]
        return loss, {"ce": ce, "aux": aux["balance"],
                      "held_rows": aux["held_rows"],
                      "held_peak": aux["held_peak"],
                      "held_tiles": aux["held_tiles"]}

    def prefill_fn(params, batch, *, block_k=1024):
        h, positions, mrope_pos = _decoder_embed_inputs(params, batch, cfg)
        h, _, cache = T.decoder_forward(params, h, cfg, positions=positions,
                                        mrope_pos=mrope_pos,
                                        collect_cache=True, block_k=block_k)
        h = L.rmsnorm(h[:, -1:], params["final_norm"], cfg.norm_eps)
        logits = L.unembed_apply(params["embed"], h, cfg.tie_embeddings)
        return cache, logits

    def decode_fn(params, cache, token, position):
        h = L.embed_apply(params["embed"], token)  # (B,1,d)
        h, new_cache = T.decoder_decode_step(params, h, cfg, cache,
                                             position=position,
                                             window=cfg.sliding_window)
        h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
        logits = L.unembed_apply(params["embed"], h, cfg.tie_embeddings)
        return logits, new_cache

    def cache_specs(B, seq_len, dtype=None):
        return T.decoder_cache_specs(cfg, B, seq_len, cfg.sliding_window,
                                     dtype)

    return Model(cfg, specs, loss_fn, prefill_fn, decode_fn, cache_specs)


# ---------------------------------------------------------------------------
# encoder-decoder family (whisper)
# ---------------------------------------------------------------------------


def _build_encdec_model(cfg: ModelConfig) -> Model:
    specs = ED.encdec_specs(cfg)

    def loss_fn(params, batch, *, block_k=512):
        enc_h = ED.encode(params, batch["audio_embeds"], cfg, block_k=block_k)
        logits = ED.decode_train(params, enc_h, batch["tokens"], cfg,
                                 block_k=block_k)
        ce = L.cross_entropy(logits, batch["labels"])
        return ce, {"ce": ce, "aux": jnp.zeros((), jnp.float32)}

    def prefill_fn(params, batch, *, block_k=512):
        """Builds the decode cache: encoder pass + cross K/V + empty self kv."""
        enc_h = ED.encode(params, batch["audio_embeds"], cfg, block_k=block_k)
        xk = jnp.einsum("bsd,ldhk->lbshk", enc_h,
                        params["dec_blocks"]["cross"]["wk"])
        xv = jnp.einsum("bsd,ldhk->lbshk", enc_h,
                        params["dec_blocks"]["cross"]["wv"])
        B = enc_h.shape[0]
        S = batch["tokens"].shape[1]
        self_specs = T.attn_cache_specs(cfg, B, S, cfg.sliding_window,
                                        (cfg.num_layers,), cfg.dtype)
        self_cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                  self_specs)
        cache = {"self": self_cache, "cross": {"k": xk, "v": xv}}
        # teacher-forced warm start is up to the caller; return BOS logits
        logits, cache = ED.decode_step(params, cache, batch["tokens"][:, :1],
                                       jnp.zeros((B,), jnp.int32), cfg,
                                       window=cfg.sliding_window)
        return cache, logits

    def decode_fn(params, cache, token, position):
        return ED.decode_step(params, cache, token, position, cfg,
                              window=cfg.sliding_window)

    def cache_specs(B, seq_len, dtype=None):
        return ED.encdec_cache_specs(cfg, B, seq_len, cfg.sliding_window,
                                     dtype)

    return Model(cfg, specs, loss_fn, prefill_fn, decode_fn, cache_specs)


# ---------------------------------------------------------------------------


def build_model(cfg: ModelConfig) -> Model:
    if cfg.enc_dec:
        return _build_encdec_model(cfg)
    return _build_decoder_model(cfg)
