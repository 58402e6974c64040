"""Per-architecture smoke tests (deliverable f): every assigned arch as a
REDUCED same-family variant runs one forward/train step and one decode step
on CPU, asserting output shapes and finiteness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced, list_configs
from repro.data.synthetic import lm_batch_for
from repro.models import build_model

ASSIGNED = [
    "jamba-v0.1-52b", "qwen2-vl-2b", "mamba2-780m", "mixtral-8x7b",
    "granite-8b", "qwen3-moe-30b-a3b", "yi-34b", "stablelm-1.6b",
    "moonlight-16b-a3b", "whisper-large-v3",
]

B, S = 2, 32


@pytest.fixture(scope="module")
def models():
    return {}


def _build(models, name):
    if name not in models:
        cfg = reduced(get_config(name))
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        models[name] = (cfg, m, params)
    return models[name]


@pytest.mark.parametrize("name", ASSIGNED)
def test_reduced_config_limits(name):
    cfg = reduced(get_config(name))
    assert cfg.num_layers <= 2
    assert cfg.d_model <= 512
    assert cfg.num_experts <= 4
    assert cfg.family == get_config(name).family


@pytest.mark.parametrize("name", ASSIGNED)
def test_train_step(models, name):
    cfg, m, params = _build(models, name)
    batch = lm_batch_for(cfg, B, S)
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: m.loss_fn(p, batch), has_aux=True)(params)
    assert np.isfinite(float(loss))
    assert float(loss) > 0
    gsq = sum(float(jnp.sum(g.astype(jnp.float32) ** 2))
              for g in jax.tree.leaves(grads))
    assert np.isfinite(gsq) and gsq > 0


@pytest.mark.parametrize("name", ASSIGNED)
def test_decode_step(models, name):
    cfg, m, params = _build(models, name)
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         m.cache_specs(B, S))
    logits, cache2 = m.decode_fn(params, cache,
                                 jnp.zeros((B, 1), jnp.int32),
                                 jnp.zeros((B,), jnp.int32))
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))
    assert jax.tree.structure(cache) == jax.tree.structure(cache2)


@pytest.mark.parametrize("name", ASSIGNED)
def test_one_sgd_step_reduces_loss_on_repeated_batch(models, name):
    """Overfit sanity: a few SGD steps on one batch reduce its loss."""
    cfg, m, params = _build(models, name)
    batch = lm_batch_for(cfg, B, S, seed=3)

    loss0 = float(m.loss_fn(params, batch)[0])
    p = params
    for _ in range(8):
        g = jax.grad(lambda p: m.loss_fn(p, batch)[0])(p)
        p = jax.tree.map(lambda x, gg: x - 0.1 * gg, p, g)
    loss1 = float(m.loss_fn(p, batch)[0])
    assert loss1 < loss0, (loss0, loss1)


def test_all_assigned_configs_registered():
    for name in ASSIGNED:
        cfg = get_config(name)
        assert cfg.name == name
        assert cfg.source
    assert len(ASSIGNED) == 10
    assert len({get_config(n).family for n in ASSIGNED}) == 6


def test_full_config_specs_match_assignment():
    c = get_config("jamba-v0.1-52b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads,
            c.d_ff, c.vocab_size, c.num_experts, c.experts_per_token) == \
        (32, 4096, 32, 8, 14336, 65536, 16, 2)
    c = get_config("yi-34b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads, c.d_ff,
            c.vocab_size) == (60, 7168, 56, 8, 20480, 64000)
    c = get_config("qwen3-moe-30b-a3b")
    assert (c.num_experts, c.experts_per_token, c.moe_d_ff,
            c.vocab_size) == (128, 8, 768, 151936)
    c = get_config("mamba2-780m")
    assert (c.num_layers, c.d_model, c.ssm_state) == (48, 1536, 128)
    c = get_config("whisper-large-v3")
    assert c.enc_dec and c.enc_layers == 32 and c.num_heads == 20
    c = get_config("mixtral-8x7b")
    assert c.sliding_window == 4096 and c.num_experts == 8
    c = get_config("qwen2-vl-2b")
    assert c.mrope and c.frontend == "vision" and c.num_heads == 12
    c = get_config("stablelm-1.6b")
    assert c.num_kv_heads == 32 and c.rope_fraction == 0.25
    c = get_config("moonlight-16b-a3b")
    assert c.num_experts == 64 and c.experts_per_token == 6
    c = get_config("granite-8b")
    assert (c.num_layers, c.d_model) == (36, 4096)


def test_param_counts_orders_of_magnitude():
    """Sanity: parameter counts land near the advertised sizes."""
    expect = {
        "yi-34b": 34e9, "granite-8b": 8e9, "mixtral-8x7b": 47e9,
        "mamba2-780m": 0.78e9, "stablelm-1.6b": 1.6e9,
        "qwen2-vl-2b": 1.5e9, "jamba-v0.1-52b": 52e9,
        "qwen3-moe-30b-a3b": 30e9, "moonlight-16b-a3b": 16e9,
    }
    for name, n in expect.items():
        got = get_config(name).param_counts()["total"]
        assert 0.5 * n < got < 1.8 * n, (name, got, n)


def test_use_pallas_attention_path_matches_jnp():
    """A model whose attention takes the kernel path (the splash kernel,
    interpret mode here) matches the pure-jnp flash path — loss and grads.
    On the CPU the model's choice (``layers.attention_path``) is the jnp
    path, so the test steers it to the kernel; ``tokens`` batches carry the
    static ``arange`` positions the kernel needs.

    Runs in a subprocess: mixing interpret-mode Pallas into a large jit
    program occasionally corrupts the XLA:CPU ORC-JIT state for *later*
    unrelated compiles in the same process ("Failed to materialize
    symbols"), so this test is isolated like the mesh dry-run tests."""
    import os
    import subprocess
    import sys
    import textwrap
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    code = textwrap.dedent("""
        import jax, numpy as np
        from repro.configs import get_config, reduced
        from repro.data.synthetic import lm_batch_for
        from repro.models import build_model
        from repro.models import layers as L

        cfg = reduced(get_config("granite-8b"))
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        batch = lm_batch_for(cfg, 1, 128, seed=5)

        def loss_and_grad():
            (l, _), g = jax.value_and_grad(
                lambda p: m.loss_fn(p, batch), has_aux=True)(params)
            return float(l), g

        l_jnp, g_jnp = loss_and_grad()
        assert L.attention_path_counts()["kernel"] == 0
        L.attention_path = lambda *a, **k: "kernel"
        l_pal, g_pal = loss_and_grad()
        assert L.attention_path_counts()["kernel"] > 0
        assert abs(l_jnp - l_pal) < 1e-4, (l_jnp, l_pal)
        for a, b in zip(jax.tree.leaves(g_jnp), jax.tree.leaves(g_pal)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=1e-3, atol=1e-4)
        print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0 and "OK" in r.stdout, r.stdout + r.stderr
