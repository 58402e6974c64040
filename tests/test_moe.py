import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import moe as M


def _cfg(**kw):
    base = reduced(get_config("mixtral-8x7b"))
    return base.with_(**kw) if kw else base


def _moonlight(**kw):
    """A tiny Moonlight block: 8 routed experts top-2, sigmoid scores with
    the correction bias, gates scaled by 2.446, one shared expert."""
    base = reduced(get_config("moonlight-16b-a3b")).with_(
        num_experts=8, experts_per_token=2, moe_d_ff=32, n_shared_experts=1)
    return base.with_(**kw) if kw else base


def _params(rng, cfg):
    from repro.models.layers import init_params
    return init_params(rng, M.moe_specs(cfg))


def _x(rng, i, shape, scale=1.0):
    return jax.random.normal(jax.random.fold_in(rng, i), shape) * scale


# held shares of the T·k assignments: (ep_size, first held expert, bias on
# it). A bias of -10 sends every token elsewhere, so no row is held.
SHARES = {"none": (8, 3, -10.0), "eighth": (8, 5, 0.0), "half": (2, 4, 0.0),
          "all": (1, 0, 0.0)}
# the layer's own row tile, and 7 rows: the held counts above (0, 8, 60
# and 128 of 128 rows at 2 x 32 tokens) are then no multiple of the tile
TILES = (None, 7)


def _held_case(rng, share, tile, monkeypatch):
    """A tiny Moonlight layer holding the given share of its rows, and
    the row tile set for the test: (cfg, params, first, x)."""
    ep, first, bias = SHARES[share]
    if tile is not None:
        monkeypatch.setattr(M, "ROW_TILE", tile)
    cfg = _moonlight(ep_size=ep)
    p = _params(rng, cfg)
    p["bias"] = jnp.zeros_like(p["bias"]).at[first].set(bias)
    x = _x(rng, 21, (2, 32, cfg.d_model), 0.5)
    return cfg, p, first, x


def _share(p, first, held):
    """The weights chip ``first // held`` holds of an uncut layer's."""
    q = dict(p)
    for k in ("wi_gate", "wi_up", "wo"):
        q[k] = p[k][first:first + held]
    return q


class TestMoE:
    def test_matches_dense_dispatch_with_ample_capacity(self, rng):
        """Drop-free dispatch == the dense-dispatch oracle (softmax)."""
        cfg = _cfg(capacity_factor=8.0)
        p = _params(rng, cfg)
        x = _x(rng, 7, (2, 16, cfg.d_model), 0.5)
        y1, _ = M.moe_apply(p, x, cfg)
        y2 = M.moe_apply_dense(p, x, cfg)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=2e-3, atol=2e-4)

    def test_capacity_drops_reduce_output(self, rng):
        """The dry-run's capacity dispatch: with capacity 0 < c << 1 some
        tokens are dropped, not corrupted."""
        cfg = _cfg(capacity_factor=0.25)
        p = _params(rng, cfg)
        x = _x(rng, 8, (2, 16, cfg.d_model), 0.5)
        try:
            M.GROUPS = 2
            y, _ = M.moe_apply(p, x, cfg)
            yf, _ = M.moe_apply(p, x, cfg.with_(capacity_factor=8.0))
        finally:
            M.GROUPS = 1
        assert np.all(np.isfinite(np.asarray(y)))
        # dropped tokens produce strictly smaller magnitude than full capacity
        assert float(jnp.sum(jnp.abs(y))) < float(jnp.sum(jnp.abs(yf)))

    def test_aux_loss_uniform_router_is_one(self, rng):
        """Balanced routing gives aux ≈ 1 (E · Σ f_e·P_e with f=P=1/E)."""
        cfg = _cfg(capacity_factor=8.0)
        p = _params(rng, cfg)
        p = dict(p)
        p["router"] = jnp.zeros_like(p["router"])  # uniform probs → balanced-ish
        x = _x(rng, 9, (4, 64, cfg.d_model))
        _, aux = M.moe_apply(p, x, cfg)
        # ties in top-k make f slightly lumpy; generous bounds
        assert 0.8 < float(aux["balance"]) < 1.5

    def test_gates_renormalized(self, rng):
        """The chosen gates sum to the routed scaling factor, for softmax
        and for sigmoid scores alike."""
        for cfg in (_cfg(), _moonlight()):
            p = _params(rng, cfg)
            x = _x(rng, 10, (8, cfg.d_model))
            _, _, gates = M.route(x, p, cfg)
            np.testing.assert_allclose(np.asarray(gates.sum(-1)),
                                       cfg.routed_scaling_factor, rtol=1e-5)

    def test_grads_flow_to_router_and_experts(self, rng):
        cfg = _moonlight()
        p = _params(rng, cfg)
        x = _x(rng, 11, (1, 8, cfg.d_model))

        def loss(p):
            y, aux = M.moe_apply(p, x, cfg)
            return jnp.sum(y ** 2) + 0.01 * aux["balance"]

        g = jax.grad(loss)(p)
        for k in ("router", "wi_gate", "wi_up", "wo"):
            assert float(jnp.abs(g[k]).max()) > 0.0, k
        # the correction bias selects and takes no gradient
        assert float(jnp.abs(g["bias"]).max()) == 0.0

    def test_grouped_dispatch_matches_ungrouped(self, rng):
        """The dry-run's grouped dispatch == the drop-free layer when no
        tokens are dropped (per-group capacity drops them otherwise)."""
        cfg = _cfg(capacity_factor=8.0)
        p = _params(rng, cfg)
        x = _x(rng, 12, (2, 32, cfg.d_model), 0.5)
        y_flat, aux_flat = M.moe_apply(p, x, cfg)
        try:
            M.GROUPS = 4
            y_grp, aux_grp = M.moe_apply(p, x, cfg)
        finally:
            M.GROUPS = 1
        np.testing.assert_allclose(np.asarray(y_grp), np.asarray(y_flat),
                                   rtol=2e-3, atol=2e-4)
        np.testing.assert_allclose(float(aux_grp["balance"]),
                                   float(aux_flat["balance"]), rtol=1e-3)

    def test_grouped_dispatch_grads(self, rng):
        cfg = _cfg(capacity_factor=4.0)
        p = _params(rng, cfg)
        x = _x(rng, 13, (1, 16, cfg.d_model))
        try:
            M.GROUPS = 4
            g = jax.grad(lambda p: jnp.sum(M.moe_apply(p, x, cfg)[0] ** 2))(p)
        finally:
            M.GROUPS = 1
        for k, v in g.items():
            assert np.all(np.isfinite(np.asarray(v, np.float32))), k

    def test_capacity_function(self):
        assert M.capacity(64, 4, 2, 1.0) == 32
        assert M.capacity(64, 4, 2, 1.25) == 40
        assert M.capacity(2, 64, 2, 1.0) == 2  # floor at k


class TestHeldExperts:
    """The layer told which experts it holds (``cfg.ep_size``): it routes
    over every expert, computes its own experts' part, and drops no row."""

    def test_shares_add_up_to_the_uncut_layer(self, rng):
        """Four chips of two held experts each: their routed parts, with
        the shared expert counted once, sum to the uncut layer."""
        whole = _moonlight()
        cut = whole.with_(ep_size=4)
        assert cut.experts_held == 2
        p = _params(rng, whole)
        x = _x(rng, 20, (2, 16, whole.d_model), 0.5)
        y_whole, _ = M.moe_apply(p, x, whole)
        shared = M.moe_apply_dense({**p, "wi_gate": p["wi_gate"][:0],
                                    "wi_up": p["wi_up"][:0],
                                    "wo": p["wo"][:0]}, x, whole)
        parts = [M.moe_apply(_share(p, f, 2), x, cut, first=f)[0]
                 for f in range(0, 8, 2)]
        total = sum(parts) - 3 * shared
        np.testing.assert_allclose(np.asarray(total), np.asarray(y_whole),
                                   rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("tile", TILES)
    @pytest.mark.parametrize("share", list(SHARES))
    def test_held_part_matches_dense_oracle(self, rng, monkeypatch, share,
                                            tile):
        cfg, p, first, x = _held_case(rng, share, tile, monkeypatch)
        y, _ = M.moe_apply(p, x, cfg, first=first)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(M.moe_apply_dense(p, x, cfg,
                                                        first=first)),
            rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("tile", TILES)
    @pytest.mark.parametrize("share", list(SHARES))
    def test_held_tiles_follow_the_held_rows(self, rng, monkeypatch, share,
                                             tile):
        """The row work runs over ceil(held rows / tile) tiles per call:
        none with no row held, every tile of the T·k buffer with all."""
        cfg, p, first, x = _held_case(rng, share, tile, monkeypatch)
        _, aux = M.moe_apply(p, x, cfg, first=first)
        m = x.shape[0] * x.shape[1] * cfg.experts_per_token
        t = M.row_tile(m)
        rows, tiles = int(aux["held_rows"]), int(aux["held_tiles"])
        assert tiles == -(-rows // t) <= -(-m // t)
        if share == "none":
            assert rows == tiles == 0
        if share == "all":
            assert rows == m and tiles == -(-m // t)
        if tile is not None and rows:
            assert rows % t, "a held count that is a multiple of the tile"

    def test_no_row_dropped_when_every_token_routes_to_one_expert(self, rng):
        """A bias that sends every token to expert 0: all T rows land on
        it, with no capacity to overflow, and match the dense oracle."""
        cfg = _moonlight(ep_size=4, experts_per_token=1)
        p = _params(rng, cfg)
        p["bias"] = jnp.zeros_like(p["bias"]).at[0].set(10.0)
        x = _x(rng, 22, (2, 32, cfg.d_model), 0.5)
        y, aux = M.moe_apply(p, x, cfg)
        assert float(aux["held_rows"]) == 2 * 32
        assert float(aux["held_peak"]) == 2.0   # all on one of two held
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(M.moe_apply_dense(p, x, cfg)),
                                   rtol=1e-4, atol=1e-5)

    def test_planted_bias_changes_selection_not_gate_values(self, rng):
        cfg = _moonlight()
        p = _params(rng, cfg)
        x = _x(rng, 23, (64, cfg.d_model))
        s, idx0, g0 = M.route(x, p, cfg)
        planted = dict(p, bias=jnp.zeros_like(p["bias"]).at[3].set(5.0))
        s1, idx1, g1 = M.route(x, planted, cfg)
        np.testing.assert_array_equal(np.asarray(s), np.asarray(s1))
        assert np.all(np.any(np.asarray(idx1) == 3, axis=1))
        assert not np.array_equal(np.asarray(idx0), np.asarray(idx1))
        # the gates are the chosen experts' own scores, normalised: the
        # bias is not in them
        chosen = np.take_along_axis(np.asarray(s1), np.asarray(idx1), 1)
        want = chosen / chosen.sum(-1, keepdims=True) * \
            cfg.routed_scaling_factor
        np.testing.assert_allclose(np.asarray(g1), want, rtol=1e-6)

    def test_load_counters(self, rng):
        cfg = _moonlight(ep_size=2)
        p = _params(rng, cfg)
        x = _x(rng, 24, (2, 16, cfg.d_model))
        _, idx, _ = M.route(x.reshape(-1, cfg.d_model), p, cfg)
        _, aux = M.moe_apply(p, x, cfg)
        load = np.bincount(np.asarray(idx).ravel(), minlength=8)[:4]
        assert float(aux["held_rows"]) == load.sum()
        np.testing.assert_allclose(float(aux["held_peak"]),
                                   load.max() / load.mean(), rtol=1e-6)

    @pytest.mark.parametrize("seq_aux", [True, False])
    def test_balance_loss_is_per_sequence_or_per_batch(self, rng, seq_aux):
        cfg = _moonlight(seq_aux=seq_aux)
        p = _params(rng, cfg)
        x = _x(rng, 25, (3, 8, cfg.d_model))
        _, aux = M.moe_apply(p, x, cfg)
        s, idx, _ = M.route(x.reshape(-1, cfg.d_model), p, cfg)
        E, k = cfg.num_experts, cfg.experts_per_token
        f = np.asarray(jax.nn.one_hot(idx, E).sum(1)).reshape(3, 8, E)
        P = np.asarray(s / s.sum(-1, keepdims=True)).reshape(3, 8, E)
        if seq_aux:
            want = np.mean([E / k * np.sum(f[b].mean(0) * P[b].mean(0))
                            for b in range(3)])
        else:
            want = E / k * np.sum(f.reshape(-1, E).mean(0)
                                  * P.reshape(-1, E).mean(0))
        np.testing.assert_allclose(float(aux["balance"]), want, rtol=1e-5)


class TestGroupedProducts:
    """The routed products (XLA's ragged dot) against one dense product
    per group: groups of rows, the rows of no held group last and zero,
    and the pullback."""

    def test_grouped_matmul_matches_per_group_products_with_grads(self, rng):
        x = _x(rng, 30, (256, 64))
        w = _x(rng, 31, (3, 64, 96))
        sizes = jnp.array([40, 0, 100, 116], jnp.int32)
        ends = np.cumsum(np.asarray(sizes))

        def per_group(x, w):
            parts = [x[a:b] @ w[g] for g, (a, b)
                     in enumerate(zip([0, *ends[:-2]], ends[:-1]))]
            return jnp.concatenate(parts + [jnp.zeros((116, 96))])

        out = {}
        for name, f in (("ragged", lambda x, w: M.grouped_matmul(x, w, sizes)),
                        ("groups", per_group)):
            loss = lambda x, w: jnp.sum(f(x, w) ** 2)
            out[name] = jax.value_and_grad(loss, (0, 1))(x, w)
        np.testing.assert_allclose(float(out["ragged"][0]),
                                   float(out["groups"][0]), rtol=1e-5)
        for a, b in zip(out["ragged"][1], out["groups"][1]):
            a, b = np.asarray(a), np.asarray(b)
            assert np.abs(a - b).max() <= 1e-5 * np.abs(b).max()
        y = M.grouped_matmul(x, w, sizes)
        assert float(jnp.abs(y[140:]).max()) == 0.0

    @pytest.mark.parametrize("tile", TILES)
    @pytest.mark.parametrize("share", list(SHARES))
    def test_layer_grads_match_dense_oracle(self, rng, monkeypatch, share,
                                            tile):
        """The held part's gradients, through the row work's pullbacks
        over the held tiles and the grouped products, equal the dense
        oracle's."""
        cfg, p, first, x = _held_case(rng, share, tile, monkeypatch)
        w = _x(rng, 33, x.shape)

        def loss(f):
            return lambda p, x: jnp.sum(f(p, x) * w)

        got = jax.grad(loss(lambda p, x: M.moe_apply(
            p, x, cfg, first=first)[0]), (0, 1))(p, x)
        want = jax.grad(loss(lambda p, x: M.moe_apply_dense(
            p, x, cfg, first=first)), (0, 1))(p, x)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            a, b = np.asarray(a), np.asarray(b)
            assert np.abs(a - b).max() <= 1e-4 * max(np.abs(b).max(), 1e-6)

    def test_rows_past_the_held_groups_never_reach_the_layer(self, rng,
                                                            monkeypatch):
        """A grouped product that leaves junk in the rows past the held
        groups, in its result and in the rows' gradient (as a tiled kernel
        that writes only the groups' tiles may), changes neither the
        layer's output nor any gradient."""
        cfg = _moonlight(ep_size=4)
        p = _params(rng, cfg)
        x = _x(rng, 34, (2, 32, cfg.d_model), 0.5)
        w = _x(rng, 35, x.shape)
        real = jax.lax.ragged_dot

        def junk(a, sizes):
            past = jnp.arange(a.shape[0])[:, None] >= jnp.sum(sizes)
            return jnp.where(past, 1e3 + jnp.arange(a.size, dtype=a.dtype
                                                    ).reshape(a.shape), a)

        @jax.custom_vjp
        def dirty(a, b, sizes):
            return junk(real(a, b, sizes), sizes)

        def dirty_fwd(a, b, sizes):
            return dirty(a, b, sizes), (a, b, sizes)

        def dirty_bwd(res, ct):
            a, b, sizes = res
            _, pull = jax.vjp(lambda a, b: real(a, b, sizes), a, b)
            da, db = pull(ct)
            return junk(da, sizes), db, None

        dirty.defvjp(dirty_fwd, dirty_bwd)

        def run():
            return jax.value_and_grad(lambda p, x: jnp.sum(M.moe_apply(
                p, x, cfg, first=cfg.experts_held)[0] * w), (0, 1))(p, x)

        want = run()
        monkeypatch.setattr(jax.lax, "ragged_dot", dirty)
        got = run()
        assert float(got[0]) == float(want[0])
        for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_rows_past_the_held_groups_never_reach_the_layer_in_small_tiles(
            self, rng, monkeypatch):
        """The same junk, with a row tile of 7: the held rows end inside a
        tile and past it lie tiles the row work never writes."""
        monkeypatch.setattr(M, "ROW_TILE", 7)
        self.test_rows_past_the_held_groups_never_reach_the_layer(
            rng, monkeypatch)

    @pytest.mark.parametrize("tile", TILES)
    def test_nan_past_the_held_groups_never_reaches_the_layer(
            self, rng, monkeypatch, tile):
        """NaN in the rows past the held groups, in the products and in
        their rows' gradients: a zero gate or a zero cotangent does not
        hide it, so the row work must read none of those rows."""
        if tile is not None:
            monkeypatch.setattr(M, "ROW_TILE", tile)
        cfg = _moonlight(ep_size=4)
        p = _params(rng, cfg)
        x = _x(rng, 36, (2, 32, cfg.d_model), 0.5)
        w = _x(rng, 37, x.shape)
        real = jax.lax.ragged_dot

        def nan_past(a, sizes):
            past = jnp.arange(a.shape[0])[:, None] >= jnp.sum(sizes)
            return jnp.where(past, jnp.nan, a)

        @jax.custom_vjp
        def dirty(a, b, sizes):
            return nan_past(real(a, b, sizes), sizes)

        def dirty_fwd(a, b, sizes):
            return dirty(a, b, sizes), (a, b, sizes)

        def dirty_bwd(res, ct):
            a, b, sizes = res
            _, pull = jax.vjp(lambda a, b: real(a, b, sizes), a, b)
            da, db = pull(ct)
            return nan_past(da, sizes), db, None

        dirty.defvjp(dirty_fwd, dirty_bwd)

        def run():
            return jax.value_and_grad(lambda p, x: jnp.sum(M.moe_apply(
                p, x, cfg, first=cfg.experts_held)[0] * w), (0, 1))(p, x)

        want = run()
        monkeypatch.setattr(jax.lax, "ragged_dot", dirty)
        got = run()
        assert float(got[0]) == float(want[0])
        for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
