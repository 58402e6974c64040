import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref as KREF
from repro.models import layers as L


def _qkv(rng, B, Sq, Sk, Hq, Hkv, D, dtype=jnp.float32):
    q = jax.random.normal(rng, (B, Sq, Hq, D), dtype)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, Sk, Hkv, D), dtype)
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, Sk, Hkv, D), dtype)
    return q, k, v


class TestRMSNorm:
    def test_unit_variance(self, rng):
        x = jax.random.normal(rng, (4, 64)) * 5.0
        y = L.rmsnorm(x, jnp.ones(64))
        rms = jnp.sqrt(jnp.mean(y * y, -1))
        np.testing.assert_allclose(np.asarray(rms), 1.0, rtol=1e-3)

    def test_gamma_scales(self, rng):
        x = jax.random.normal(rng, (4, 64))
        y2 = L.rmsnorm(x, 2 * jnp.ones(64))
        y1 = L.rmsnorm(x, jnp.ones(64))
        np.testing.assert_allclose(np.asarray(y2), np.asarray(2 * y1), rtol=1e-5)


class TestRoPE:
    def test_norm_preserved(self, rng):
        x = jax.random.normal(rng, (2, 16, 4, 32))
        pos = jnp.broadcast_to(jnp.arange(16)[None], (2, 16))
        y = L.apply_rope(x, pos, theta=1e4, fraction=1.0)
        np.testing.assert_allclose(np.linalg.norm(np.asarray(x), axis=-1),
                                   np.linalg.norm(np.asarray(y), axis=-1),
                                   rtol=1e-5)

    def test_relative_property(self, rng):
        """<rope(q,i), rope(k,j)> depends only on i - j."""
        q = jax.random.normal(rng, (1, 1, 1, 32))
        k = jax.random.normal(jax.random.fold_in(rng, 1), (1, 1, 1, 32))

        def dot_at(i, j):
            qi = L.apply_rope(q, jnp.array([[i]]), theta=1e4)
            kj = L.apply_rope(k, jnp.array([[j]]), theta=1e4)
            return float(jnp.sum(qi * kj))

        assert abs(dot_at(5, 3) - dot_at(12, 10)) < 1e-4
        assert abs(dot_at(7, 7) - dot_at(0, 0)) < 1e-4

    def test_partial_fraction_passthrough(self, rng):
        x = jax.random.normal(rng, (1, 8, 2, 32))
        pos = jnp.broadcast_to(jnp.arange(8)[None], (1, 8))
        y = L.apply_rope(x, pos, theta=1e4, fraction=0.25)
        # last 75% of dims untouched
        np.testing.assert_array_equal(np.asarray(x[..., 8:]),
                                      np.asarray(y[..., 8:]))

    def test_theta_zero_identity(self, rng):
        x = jax.random.normal(rng, (1, 8, 2, 32))
        pos = jnp.broadcast_to(jnp.arange(8)[None], (1, 8))
        np.testing.assert_array_equal(
            np.asarray(L.apply_rope(x, pos, theta=0.0)), np.asarray(x))

    def test_mrope_matches_rope_for_equal_axes(self, rng):
        """When t==h==w, M-RoPE must behave like a rotation by that pos."""
        x = jax.random.normal(rng, (2, 8, 2, 32))
        pos = jnp.broadcast_to(jnp.arange(8)[None], (2, 8))
        pos3 = jnp.stack([pos, pos, pos])
        y = L.apply_mrope(x, pos3, theta=1e4)
        # norm preservation is the key invariant
        np.testing.assert_allclose(np.linalg.norm(np.asarray(x), axis=-1),
                                   np.linalg.norm(np.asarray(y), axis=-1),
                                   rtol=1e-5)


class TestFlashAttention:
    @pytest.mark.parametrize("causal,window", [(True, 0), (True, 16),
                                               (False, 0)])
    @pytest.mark.parametrize("Hq,Hkv", [(4, 4), (4, 2), (8, 1)])
    def test_matches_naive(self, rng, causal, window, Hq, Hkv):
        B, S, D = 2, 64, 16
        q, k, v = _qkv(rng, B, S, S, Hq, Hkv, D)
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        out = L.flash_attention_jnp(q, k, v, q_positions=pos, k_positions=pos,
                                    causal=causal, window=window, block_k=16)
        # ref uses (B, H, S, D) layout
        ref = KREF.attention_ref(q.transpose(0, 2, 1, 3),
                                 k.transpose(0, 2, 1, 3),
                                 v.transpose(0, 2, 1, 3),
                                 causal=causal, window=window)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(ref.transpose(0, 2, 1, 3)),
                                   rtol=2e-4, atol=2e-5)

    def test_grads_match_naive(self, rng):
        B, S, Hq, Hkv, D = 1, 32, 2, 1, 8
        q, k, v = _qkv(rng, B, S, S, Hq, Hkv, D)
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

        def f_flash(q, k, v):
            return L.flash_attention_jnp(q, k, v, q_positions=pos,
                                         k_positions=pos, block_k=8).sum()

        def f_ref(q, k, v):
            return KREF.attention_ref(q.transpose(0, 2, 1, 3),
                                      k.transpose(0, 2, 1, 3),
                                      v.transpose(0, 2, 1, 3)).sum()

        g1 = jax.grad(f_flash, (0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, (0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)

    def test_decode_matches_full(self, rng):
        """Decode attention at position t == row t of the full pass."""
        B, S, Hq, Hkv, D = 2, 16, 4, 2, 8
        q, k, v = _qkv(rng, B, S, S, Hq, Hkv, D)
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        full = L.flash_attention_jnp(q, k, v, q_positions=pos,
                                     k_positions=pos, block_k=8)
        t = S - 1
        out = L.decode_attention_jnp(
            q[:, t:t + 1], k, v, q_position=jnp.full((B,), t),
            k_positions=pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(full[:, t:t + 1]),
                                   rtol=2e-4, atol=2e-5)


class TestCrossEntropy:
    def test_uniform_is_logV(self):
        V = 64
        logits = jnp.zeros((4, 8, V))
        labels = jnp.zeros((4, 8), jnp.int32)
        np.testing.assert_allclose(float(L.cross_entropy(logits, labels)),
                                   np.log(V), rtol=1e-5)

    def test_perfect_prediction(self):
        labels = jnp.arange(8)[None]
        logits = jax.nn.one_hot(labels, 8) * 100.0
        assert float(L.cross_entropy(logits, labels)) < 1e-3


class TestParamSpecs:
    def test_init_respects_shape_dtype(self, rng):
        from repro.models.layers import ParamSpec, init_params, logical_axes
        specs = {"a": ParamSpec((4, 8), ("embed", "ffn")),
                 "b": ParamSpec((8,), ("ffn",), init="zeros")}
        p = init_params(rng, specs, jnp.bfloat16)
        assert p["a"].shape == (4, 8) and p["a"].dtype == jnp.bfloat16
        assert float(jnp.abs(p["b"]).max()) == 0.0
        assert logical_axes(specs)["a"] == ("embed", "ffn")

    def test_init_deterministic(self, rng):
        from repro.models.layers import ParamSpec, init_params
        specs = {"a": ParamSpec((4, 8), (None, None))}
        p1 = init_params(rng, specs)
        p2 = init_params(rng, specs)
        np.testing.assert_array_equal(np.asarray(p1["a"]), np.asarray(p2["a"]))

    def test_init_same_in_every_process(self):
        """Seeded weights do not depend on the process: two interpreters
        with different string-hash salts build identical parameters."""
        import os
        import subprocess
        import sys
        code = ("import jax, numpy as np\n"
                "from repro.models.layers import ParamSpec, init_params\n"
                "p = init_params(jax.random.PRNGKey(0), "
                "{'a': ParamSpec((4, 8), (None, None))})\n"
                "print(repr(np.asarray(p['a']).tolist()))\n")
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        outs = []
        for salt in ("1", "2"):
            env = dict(os.environ, PYTHONPATH=src, PYTHONHASHSEED=salt,
                       JAX_PLATFORMS="cpu")
            r = subprocess.run([sys.executable, "-c", code], env=env,
                               capture_output=True, text=True, timeout=300)
            assert r.returncode == 0, r.stderr
            outs.append(r.stdout)
        assert outs[0] == outs[1]
