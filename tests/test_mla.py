"""Multi-head latent attention (DeepSeek-V2/V3, as Moonlight-16B-A3B runs it):
the sub-layer against its written equations, the splash kernel at latent
attention's widths (q·k at 192, values at 128) against the jnp core, and
a whole model on the kernel path against the jnp path."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced
from repro.models import layers as L
from repro.models import transformer as T


def _cfg():
    return reduced(get_config("moonlight-16b-a3b"))


def _rope(x, theta):
    """Rotate-half rotary positions, written out: x (S, D)."""
    S, D = x.shape
    inv = 1.0 / theta ** (np.arange(0, D, 2) / D)
    ang = np.arange(S)[:, None] * inv[None]
    x1, x2 = x[:, :D // 2], x[:, D // 2:]
    return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                           x2 * np.cos(ang) + x1 * np.sin(ang)], -1)


def _rms(x, g, eps):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * g


def test_latent_sublayer_matches_written_equations(rng):
    cfg = _cfg()
    H, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    specs = T.attn_sublayer_specs(cfg, ())
    p = L.init_params(rng, specs)
    p["kv_norm"] = 1.0 + 0.1 * jax.random.normal(jax.random.fold_in(rng, 5),
                                                 (r,))
    S = 16
    h = jax.random.normal(jax.random.fold_in(rng, 1), (1, S, cfg.d_model))
    got, (k_got, v_got) = T.attn_sublayer(p, h, cfg, positions=None,
                                          block_k=8)

    P = {k: np.asarray(v, np.float64) for k, v in p.items()}
    x = _rms(np.asarray(h[0], np.float64), P["norm"], cfg.norm_eps)
    kv = x @ P["wkv_a"]                                   # (S, r + rope)
    c = _rms(kv[:, :r], P["kv_norm"], T.LATENT_NORM_EPS)
    k_pe = _rope(kv[:, r:], cfg.rope_theta)               # one shared head
    out = np.zeros((S, cfg.d_model))
    for head in range(H):
        q = x @ P["wq"][:, head]                          # (S, nope + rope)
        q = np.concatenate([q[:, :nope], _rope(q[:, nope:], cfg.rope_theta)],
                           -1)
        kvb = c @ P["wkv_b"][:, head]                     # (S, nope + dv)
        k = np.concatenate([kvb[:, :nope], k_pe], -1)
        v = kvb[:, nope:]
        s = q @ k.T / np.sqrt(nope + rope)
        s = np.where(np.tril(np.ones((S, S), bool)), s, -np.inf)
        w = np.exp(s - s.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        out += (w @ v) @ P["wo"][head]
        np.testing.assert_allclose(np.asarray(k_got[0, :, head]), k,
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(v_got[0, :, head]), v,
                                   rtol=1e-4, atol=1e-5)
    assert v_got.shape[-1] == dv
    np.testing.assert_allclose(np.asarray(got[0]),
                               np.asarray(h[0]) + out, rtol=1e-4, atol=1e-5)


def test_splash_at_latent_widths_matches_jnp(rng):
    """The kernel takes q·k at 192 and values at 128 as they are (interpret
    mode): forward and the fused backward against the jnp flash core."""
    from repro.kernels import splash
    B, H, S, D, Dv = 1, 2, 256, 192, 128
    q = jax.random.normal(rng, (B, H, S, D))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, H, S, D))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, H, S, Dv))
    w = jax.random.normal(jax.random.fold_in(rng, 3), (B, H, S, Dv))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    t = lambda x: x.transpose(0, 2, 1, 3)
    cores = {
        "kernel": lambda q, k, v: splash.causal_attention(
            q * D ** -0.5, k, v, blocks=(128,) * 4, interpret=True),
        "jnp": lambda q, k, v: t(L.flash_attention_jnp(
            t(q), t(k), t(v), q_positions=pos, k_positions=pos,
            block_k=128)),
    }
    got = {name: jax.value_and_grad(
        lambda q, k, v: jnp.sum(core(q, k, v) * w), (0, 1, 2))(q, k, v)
        for name, core in cores.items()}
    (l_k, g_k), (l_j, g_j) = got["kernel"], got["jnp"]
    assert got["kernel"][1][2].shape == v.shape
    assert abs(float(l_k) - float(l_j)) <= 1e-5 * abs(float(l_j)) + 1e-3
    for a, b, n in zip(g_k, g_j, "qkv"):
        a, b = np.asarray(a), np.asarray(b)
        assert np.abs(a - b).max() <= 2e-5 * np.abs(b).max(), n


def test_latent_model_on_kernel_path_matches_jnp():
    """A tiny Moonlight model whose attention takes the kernel path matches
    the jnp path, loss and gradients (in a subprocess, as the granite
    kernel-path test: interpret-mode Pallas in a large CPU program can
    spoil later compiles in the same process)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    code = textwrap.dedent("""
        import jax, numpy as np
        from repro.configs import get_config, reduced
        from repro.data.synthetic import lm_batch_for
        from repro.models import build_model
        from repro.models import layers as L

        cfg = reduced(get_config("moonlight-16b-a3b"))
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        batch = lm_batch_for(cfg, 1, 128, seed=5)

        def loss_and_grad():
            (l, _), g = jax.value_and_grad(
                lambda p: m.loss_fn(p, batch), has_aux=True)(params)
            return float(l), g

        l_jnp, g_jnp = loss_and_grad()
        L.attention_path = lambda *a, **k: "kernel"
        l_pal, g_pal = loss_and_grad()
        assert L.attention_path_counts()["kernel"] > 0
        assert abs(l_jnp - l_pal) < 1e-4, (l_jnp, l_pal)
        for a, b in zip(jax.tree.leaves(g_jnp), jax.tree.leaves(g_pal)):
            a, b = np.asarray(a), np.asarray(b)
            assert np.abs(a - b).max() <= 1e-4 * max(np.abs(b).max(), 1e-6)
        print("ok")
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0 and "ok" in out.stdout, out.stderr[-3000:]


def test_leading_dense_layer_is_its_own_group():
    """The leading dense layer is a stack of its own outside the scanned
    MoE stack, and so a group of its own in the flat plane."""
    from repro.core.layerview import FlatPartition
    from repro.models import build_model
    cfg = _cfg()
    m = build_model(cfg)
    specs = m.specs
    assert "mlp" in specs["dense"]["sub0"]
    assert "router" not in specs["dense"]["sub0"]["mlp"]
    assert "router" in specs["blocks"]["sub0"]["mlp"]
    assert specs["dense"]["sub0"]["mlp"]["wi_gate"].shape == (
        1, cfg.d_model, cfg.d_ff)
    part = FlatPartition(m.abstract_params())
    assert part.names == ("blocks", "dense", "embed", "final_norm")
