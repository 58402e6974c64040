"""Strong correctness: prefill+decode must reproduce the teacher-forced
forward pass — next-token logits from the incremental path match the full
pass at every position (per family)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import build_model
from repro.models import layers as L
from repro.models import transformer as T

FAMILIES = ["granite-8b", "mixtral-8x7b", "mamba2-780m", "jamba-v0.1-52b",
            "stablelm-1.6b", "moonlight-16b-a3b"]


@pytest.mark.parametrize("name", FAMILIES)
def test_incremental_decode_matches_full_forward(name, rng):
    cfg = reduced(get_config(name))
    m = build_model(cfg)
    params = m.init(rng)
    B, S = 2, 16
    tokens = jax.random.randint(jax.random.fold_in(rng, 1), (B, S), 0,
                                cfg.vocab_size)

    # full forward logits at each position
    h = L.embed_apply(params["embed"], tokens)
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h, _, _ = T.decoder_forward(params, h, cfg, positions=positions,
                                block_k=8)
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    full_logits = L.unembed_apply(params["embed"], h, cfg.tie_embeddings)

    # incremental: feed tokens one at a time through decode_fn
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         m.cache_specs(B, S))
    step = jax.jit(m.decode_fn)
    for t in range(S):
        logits, cache = step(params, cache, tokens[:, t:t + 1],
                             jnp.full((B,), t, jnp.int32))
        np.testing.assert_allclose(
            np.asarray(logits[:, 0], np.float32),
            np.asarray(full_logits[:, t], np.float32),
            rtol=5e-3, atol=5e-3,
            err_msg=f"{name} diverges at position {t}")


def test_sliding_window_ring_buffer_decode(rng):
    """With window W < S the ring-buffer decode matches full SWA forward."""
    cfg = reduced(get_config("mixtral-8x7b")).with_(sliding_window=8,
                                                    num_experts=0, d_ff=128)
    m = build_model(cfg)
    params = m.init(rng)
    B, S = 1, 24
    tokens = jax.random.randint(jax.random.fold_in(rng, 2), (B, S), 0,
                                cfg.vocab_size)
    h = L.embed_apply(params["embed"], tokens)
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h, _, _ = T.decoder_forward(params, h, cfg, positions=positions,
                                block_k=8)
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    full_logits = L.unembed_apply(params["embed"], h, cfg.tie_embeddings)

    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         m.cache_specs(B, S))
    # ring buffer capacity = window
    assert cache["sub0"]["k"].shape[2] == 8
    step = jax.jit(m.decode_fn)
    for t in range(S):
        logits, cache = step(params, cache, tokens[:, t:t + 1],
                             jnp.full((B,), t, jnp.int32))
        np.testing.assert_allclose(
            np.asarray(logits[:, 0], np.float32),
            np.asarray(full_logits[:, t], np.float32),
            rtol=5e-3, atol=5e-3, err_msg=f"pos {t}")


def test_whisper_decode_consistency(rng):
    cfg = reduced(get_config("whisper-large-v3"))
    m = build_model(cfg)
    params = m.init(rng)
    B, S = 1, 8
    from repro.models import encdec as ED
    audio = jax.random.normal(jax.random.fold_in(rng, 3),
                              (B, cfg.enc_seq, cfg.d_model)) * 0.1
    tokens = jax.random.randint(jax.random.fold_in(rng, 4), (B, S), 0,
                                cfg.vocab_size)
    enc_h = ED.encode(params, audio, cfg, block_k=8)
    full_logits = ED.decode_train(params, enc_h, tokens, cfg, block_k=8)

    # build cache: cross K/V from encoder + empty self cache
    xk = jnp.einsum("bsd,ldhk->lbshk", enc_h,
                    params["dec_blocks"]["cross"]["wk"])
    xv = jnp.einsum("bsd,ldhk->lbshk", enc_h,
                    params["dec_blocks"]["cross"]["wv"])
    self_specs = T.attn_cache_specs(cfg, B, S, 0, (cfg.num_layers,),
                                    cfg.dtype)
    cache = {"self": jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                  self_specs),
             "cross": {"k": xk, "v": xv}}
    for t in range(S):
        logits, cache = ED.decode_step(params, cache, tokens[:, t:t + 1],
                                       jnp.full((B,), t, jnp.int32), cfg)
        np.testing.assert_allclose(
            np.asarray(logits[:, 0], np.float32),
            np.asarray(full_logits[:, t], np.float32),
            rtol=5e-3, atol=5e-3, err_msg=f"pos {t}")
