"""Pallas-kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode — kernel bodies execute in Python on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ref as KREF


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-4, atol=2e-5)


class TestFlashKernel:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,Hq,Hkv,S,D,bq,bk", [
        (1, 2, 2, 64, 16, 16, 16),
        (2, 4, 2, 128, 32, 32, 64),   # GQA, rectangular blocks
        (1, 8, 1, 64, 8, 64, 16),     # MQA
    ])
    def test_sweep(self, rng, dtype, B, Hq, Hkv, S, D, bq, bk):
        q = jax.random.normal(rng, (B, Hq, S, D)).astype(dtype)
        k = jax.random.normal(jax.random.fold_in(rng, 1),
                              (B, Hkv, S, D)).astype(dtype)
        v = jax.random.normal(jax.random.fold_in(rng, 2),
                              (B, Hkv, S, D)).astype(dtype)
        out = ops.flash_attention(q, k, v, block_q=bq, block_k=bk,
                                  interpret=True)
        ref = KREF.attention_ref(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            **_tol(dtype))

    @pytest.mark.parametrize("causal,window", [(True, 16), (False, 0)])
    def test_masking_variants(self, rng, causal, window):
        B, Hq, Hkv, S, D = 1, 2, 1, 64, 16
        q = jax.random.normal(rng, (B, Hq, S, D))
        k = jax.random.normal(jax.random.fold_in(rng, 1), (B, Hkv, S, D))
        v = jax.random.normal(jax.random.fold_in(rng, 2), (B, Hkv, S, D))
        out = ops.flash_attention(q, k, v, causal=causal, window=window,
                                  block_q=16, block_k=16, interpret=True)
        ref = KREF.attention_ref(q, k, v, causal=causal, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)


class TestSSDKernel:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,H,S,P,N,chunk", [
        (1, 2, 32, 8, 4, 8),
        (2, 3, 64, 16, 8, 16),
        (1, 1, 64, 32, 16, 64),
    ])
    def test_sweep(self, rng, dtype, B, H, S, P, N, chunk):
        x = (jax.random.normal(rng, (B, H, S, P)) * 0.5).astype(dtype)
        dt = jax.nn.softplus(
            jax.random.normal(jax.random.fold_in(rng, 1), (B, H, S))
        ).astype(dtype)
        A = -jnp.exp(jax.random.normal(jax.random.fold_in(rng, 2), (H,)) * 0.3)
        Bm = (jax.random.normal(jax.random.fold_in(rng, 3), (B, S, N)) * 0.5
              ).astype(dtype)
        Cm = (jax.random.normal(jax.random.fold_in(rng, 4), (B, S, N)) * 0.5
              ).astype(dtype)
        y = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
        ref = KREF.ssd_ref(x, dt, A, Bm, Cm)
        np.testing.assert_allclose(
            np.asarray(y, np.float32), np.asarray(ref, np.float32),
            **_tol(dtype))


class TestGossipMixKernel:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("shape", [(128,), (7, 33, 5), (1024, 128),
                                       (3, 3)])
    def test_sweep(self, rng, dtype, shape):
        x = jax.random.normal(rng, shape).astype(dtype)
        r = jax.random.normal(jax.random.fold_in(rng, 1), shape).astype(dtype)
        u = (jax.random.normal(jax.random.fold_in(rng, 2), shape) * 0.01
             ).astype(dtype)
        out = ops.gossip_mix(x, r, u, 0.6, 0.4, interpret=True)
        ref = KREF.gossip_mix_ref(x, r, u, 0.6, 0.4)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            **_tol(dtype))

    def test_pure_mix_convexity(self, rng):
        """With upd = 0, output lies between x and x_recv elementwise."""
        x = jnp.ones((64,)) * 2.0
        r = jnp.ones((64,)) * -1.0
        out = ops.gossip_mix(x, r, jnp.zeros(64), 0.75, 0.25, interpret=True)
        np.testing.assert_allclose(np.asarray(out), 0.75 * 2.0 - 0.25,
                                   rtol=1e-6)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("n", [1, 127, 129, 1023, 8 * 128 + 5])
    def test_odd_sizes_exercise_padding(self, rng, dtype, n):
        """Satellite: fused kernel vs the reference mix at sizes that are
        NOT multiples of the (8, 128) tile — the pad/unpad path must be
        exact (padding contributes zeros that are sliced away)."""
        x = jax.random.normal(rng, (n,)).astype(dtype)
        r = jax.random.normal(jax.random.fold_in(rng, 1), (n,)).astype(dtype)
        u = (jax.random.normal(jax.random.fold_in(rng, 2), (n,)) * 0.01
             ).astype(dtype)
        out = ops.gossip_mix(x, r, u, 0.7, 0.3, interpret=True)
        ref = KREF.gossip_mix_ref(x, r, u, 0.7, 0.3)
        assert out.shape == (n,) and out.dtype == dtype
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), **_tol(dtype))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("n", [127, 1024])
    def test_pure_mix_variant_matches_ref(self, rng, dtype, n):
        """upd=None selects the 2-read pure-mix kernel (the lockstep
        gossip path); it must equal the reference with a zero update."""
        x = jax.random.normal(rng, (n,)).astype(dtype)
        r = jax.random.normal(jax.random.fold_in(rng, 1), (n,)).astype(dtype)
        out = ops.gossip_mix(x, r, None, 0.6, 0.4, interpret=True)
        ref = KREF.gossip_mix_ref(x, r, jnp.zeros_like(x), 0.6, 0.4)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), **_tol(dtype))

    def test_traced_alpha_beta(self, rng):
        """α/β arrive as traced scalars from the push-sum weights inside
        the jitted gossip stage — the SMEM prefetch path must accept
        them."""
        x = jax.random.normal(rng, (300,))
        r = jax.random.normal(jax.random.fold_in(rng, 1), (300,))

        @jax.jit
        def f(w, rw):
            new_w = w + rw
            return ops.gossip_mix(x, r, None, w / new_w, rw / new_w,
                                  interpret=True)

        out = f(jnp.float32(0.5), jnp.float32(0.25))
        ref = KREF.gossip_mix_ref(x, r, jnp.zeros_like(x),
                                  2.0 / 3.0, 1.0 / 3.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)


class TestRMSNormKernel:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("shape,tile", [((4, 64), 2), ((2, 7, 128), 8),
                                            ((300, 32), 256)])
    def test_sweep(self, rng, dtype, shape, tile):
        x = (jax.random.normal(rng, shape) * 3).astype(dtype)
        g = (1 + 0.1 * jax.random.normal(jax.random.fold_in(rng, 1),
                                         shape[-1:])).astype(dtype)
        out = ops.rmsnorm(x, g, tile_rows=tile, interpret=True)
        ref = KREF.rmsnorm_ref(x, g)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            **_tol(dtype))

    def test_matches_model_rmsnorm(self, rng):
        from repro.models.layers import rmsnorm as model_rmsnorm
        x = jax.random.normal(rng, (8, 64))
        g = jnp.ones(64)
        np.testing.assert_allclose(
            np.asarray(ops.rmsnorm(x, g, interpret=True)),
            np.asarray(model_rmsnorm(x, g)), rtol=1e-5, atol=1e-6)


class TestFlashBackwardKernels:
    """Pallas dq + dk/dv backward passes vs naive autodiff grads."""

    @pytest.mark.parametrize("Hq,Hkv,causal,window,bq,bk", [
        (2, 2, True, 0, 16, 16),
        (4, 2, True, 16, 32, 16),   # GQA + sliding window
        (4, 1, False, 0, 16, 32),   # MQA bidirectional
    ])
    def test_grads_match_naive(self, rng, Hq, Hkv, causal, window, bq, bk):
        from repro.kernels.flash_attention import flash_attention_trainable
        B, S, D = 1, 64, 16
        q = jax.random.normal(rng, (B, Hq, S, D))
        k = jax.random.normal(jax.random.fold_in(rng, 1), (B, Hkv, S, D))
        v = jax.random.normal(jax.random.fold_in(rng, 2), (B, Hkv, S, D))

        def f(q, k, v):
            return flash_attention_trainable(
                q, k, v, causal=causal, window=window, block_q=bq,
                block_k=bk, interpret=True).sum()

        def g(q, k, v):
            return KREF.attention_ref(q, k, v, causal=causal,
                                      window=window).sum()

        g1 = jax.grad(f, (0, 1, 2))(q, k, v)
        g2 = jax.grad(g, (0, 1, 2))(q, k, v)
        for a, b, n in zip(g1, g2, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=2e-5, err_msg=n)

    def test_fwd_lse_output(self, rng):
        from repro.kernels.flash_attention import flash_attention
        B, H, S, D = 1, 2, 32, 8
        q = jax.random.normal(rng, (B, H, S, D))
        k = jax.random.normal(jax.random.fold_in(rng, 1), (B, H, S, D))
        o, lse = flash_attention(q, k, k, block_q=8, block_k=8,
                                 return_lse=True, interpret=True)
        assert lse.shape == (B, H, S)
        assert np.all(np.isfinite(np.asarray(lse)))


class TestSplashAttention:
    """The model's attention kernel on the TPU (``repro.kernels.splash``,
    which ``layers.causal_attention_kernel`` calls), here in interpret
    mode, against the chunked jnp core and the naive reference; the choice
    between the two paths (``layers.attention_path``); and its counter."""

    S, D, BLOCKS = 256, 64, (128, 128, 128, 128)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("Hq,Hkv", [(2, 2), (4, 2)])   # MHA, GQA
    def test_loss_and_grads_match_jnp_and_ref(self, rng, Hq, Hkv, dtype):
        from repro.kernels import splash
        from repro.models.layers import flash_attention_jnp
        B, S, D = 2, self.S, self.D
        q = jax.random.normal(rng, (B, Hq, S, D)).astype(dtype)
        k = jax.random.normal(jax.random.fold_in(rng, 1),
                              (B, Hkv, S, D)).astype(dtype)
        v = jax.random.normal(jax.random.fold_in(rng, 2),
                              (B, Hkv, S, D)).astype(dtype)
        w = jax.random.normal(jax.random.fold_in(rng, 3), (B, Hq, S, D))
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        t = lambda x: x.transpose(0, 2, 1, 3)

        cores = {
            "kernel": lambda q, k, v: splash.causal_attention(
                q * D ** -0.5, k, v, blocks=self.BLOCKS, interpret=True),
            "jnp": lambda q, k, v: t(flash_attention_jnp(
                t(q), t(k), t(v), q_positions=pos, k_positions=pos,
                block_k=128)),
            "ref": KREF.attention_ref,
        }
        got = {}
        for name, core in cores.items():
            loss = lambda q, k, v: jnp.sum(core(q, k, v).astype(jnp.float32)
                                           * w)
            got[name] = jax.value_and_grad(loss, (0, 1, 2))(q, k, v)
        (l_k, g_k) = got["kernel"]
        for g in g_k:   # dq/dk/dv come back in the inputs' dtype
            assert g.dtype == dtype
        rtol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
        for other in ("jnp", "ref"):
            l_o, g_o = got[other]
            assert abs(float(l_k) - float(l_o)) <= rtol * abs(float(l_o)) + \
                (1.0 if dtype == jnp.bfloat16 else 1e-3), other
            for a, b, n in zip(g_k, g_o, "qkv"):
                a = np.asarray(a, np.float32)
                b = np.asarray(b, np.float32)
                scale = np.abs(b).max()
                tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
                assert np.abs(a - b).max() <= tol * scale, (other, n)

    @pytest.mark.parametrize("case,want", [
        ("tpu", "kernel"),
        ("cpu backend", "jnp"),
        ("not causal", "jnp"),
        ("window", "jnp"),
        ("Sq != Sk", "jnp"),
        ("S not a multiple of the block", "jnp"),
        ("S under 128", "jnp"),
        ("given positions", "jnp"),
        ("auto axis of size 2", "jnp"),
        ("auto axes of size 1", "kernel"),
        ("no mesh, two devices", "jnp"),
    ])
    def test_path_choice(self, monkeypatch, case, want):
        import contextlib
        from jax.sharding import AbstractMesh, AxisType
        from repro.kernels import splash
        from repro.models.layers import attention_path
        monkeypatch.setattr(jax, "default_backend",
                            lambda: "cpu" if case == "cpu backend" else "tpu")
        if case == "no mesh, two devices":
            monkeypatch.setattr(jax, "device_count", lambda: 2)
        S = 2 * max(splash.BLOCKS)
        S = {"S not a multiple of the block": S + 128,
             "S under 128": 96}.get(case, S)
        Sk = S // 2 if case == "Sq != Sk" else S
        kw = dict(causal=case != "not causal",
                  window=64 if case == "window" else 0,
                  arange=case != "given positions")
        mesh = contextlib.nullcontext()
        if case.startswith("auto axes") or case.startswith("auto axis"):
            n = 2 if case == "auto axis of size 2" else 1
            mesh = jax.sharding.use_abstract_mesh(AbstractMesh(
                (1, n), ("data", "model"),
                axis_types=(AxisType.Manual, AxisType.Auto)))
        with mesh:
            got = attention_path((2, S, 4, 64), (2, Sk, 2, 64), **kw)
        assert got == want

    @pytest.mark.parametrize("path", ["jnp", "kernel"])
    def test_counter_reads_traced_calls_by_path(self, monkeypatch, path):
        """Tracing a decoder's loss and gradient counts each trace of its
        scanned block's attention call on the path taken: on the CPU the jnp
        core; where the choice says kernel, the kernel."""
        import dataclasses
        from repro.configs import get_config, reduced
        from repro.models import build_model
        from repro.models import layers as L
        if path == "kernel":
            monkeypatch.setattr(L, "attention_path",
                                lambda *a, **k: "kernel")
        cfg = dataclasses.replace(reduced(get_config("gpt2-medium")),
                                  num_layers=2)
        model = build_model(cfg)
        params = model.abstract_params()
        tok = jax.ShapeDtypeStruct((1, 128), jnp.int32)
        before = L.attention_path_counts()
        jax.eval_shape(jax.value_and_grad(
            lambda p, b: model.loss_fn(p, b)[0]),
            params, {"tokens": tok, "labels": tok})
        after = L.attention_path_counts()
        other = "jnp" if path == "kernel" else "kernel"
        assert after[other] == before[other]
        # the block body traced for the forward, for remat's forward rule,
        # and for the recompute in its backward
        assert after[path] - before[path] == 3
