"""Compile-only guards for the TPU v5e: the Pallas kernels of the main path,
compiled by the chip's compiler for a described (not attached) v5e chip at
GPT-2 Medium plane and head sizes. Nothing runs; a kernel the compiler
refuses (unaligned tiling, too much VMEM) fails here without a chip.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and under pytest-xdist every worker
imports this file."""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

# gpt2-medium gossip-plane buffers: the final-norm group and one layer's
# share of the stacked block group (4·1024² attention + 3·1024·4096 MLP
# + 2·1024 norms)
PLANE_SIZES = {"final_norm": 1024, "block_layer": 16_779_264}
DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip cannot be read back without one:
    keep these compiles out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *args, **static):
    compiled = fn.lower(*args, **static).compile()
    # the Pallas kernel is in the program, compiled, not interpreted
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("group", list(PLANE_SIZES))
def test_gossip_mix_compiles(one_chip, group, dtype):
    n, dt = PLANE_SIZES[group], DTYPES[dtype]
    x = _sds((n,), dt, one_chip)
    ab = _sds((), jnp.float32, one_chip)
    _compile(ops.gossip_mix, x, x, x, ab, ab, interpret=False)


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("group", list(PLANE_SIZES))
def test_quantize_plane_compiles(one_chip, group, dtype):
    n, dt = PLANE_SIZES[group], DTYPES[dtype]
    x = _sds((n,), dt, one_chip)
    _compile(ops.quantize_plane, x, x, interpret=False)


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("group", list(PLANE_SIZES))
def test_dequant_mix_compiles(one_chip, group, dtype):
    from repro.kernels.quantize import quant_layout
    n, dt = PLANE_SIZES[group], DTYPES[dtype]
    rows, _, _ = quant_layout(n)
    x = _sds((n,), dt, one_chip)
    q = _sds((n,), jnp.int8, one_chip)
    s = _sds((rows,), jnp.float32, one_chip)
    ab = _sds((), jnp.float32, one_chip)
    _compile(ops.dequant_mix, x, q, s, x, ab, ab, interpret=False)


def test_flash_attention_compiles(one_chip):
    # gpt2-medium heads: batch 8, 16 heads, seq 1024, head_dim 64
    q = _sds((8, 16, 1024, 64), jnp.bfloat16, one_chip)
    _compile(ops.flash_attention, q, q, q, causal=True, interpret=False)


# each cell's attention core: gpt2-medium (batch 8, 16 heads, seq 1024,
# head_dim 64, f32) and stablelm-1.6b (batch 2, 32 heads, seq 4096, bf16)
ATTENTION = {"gpt2-medium": ((8, 16, 1024, 64), jnp.float32),
             "stablelm-1.6b": ((2, 32, 4096, 64), jnp.bfloat16)}


def _splash_loss(q, k, v):
    from repro.kernels import splash
    return jnp.sum(splash.causal_attention(q, k, v).astype(jnp.float32))


@pytest.mark.parametrize("pass_", ["fwd", "fwd+bwd"])
@pytest.mark.parametrize("cell", list(ATTENTION))
def test_splash_attention_compiles(one_chip, cell, pass_):
    """The model's attention kernel, forward alone and under ``jax.grad``
    (the fused backward), at each cell's shape and dtype."""
    shape, dt = ATTENTION[cell]
    q = _sds(shape, dt, one_chip)
    fn = _splash_loss if pass_ == "fwd" else jax.grad(_splash_loss, (0, 1, 2))
    _compile(jax.jit(fn), q, q, q)


def test_decoder_grad_takes_attention_kernel_on_tpu(one_chip, monkeypatch):
    """``value_and_grad`` of a 2-layer decoder at gpt2-medium width: on the
    TPU the model's own choice (``layers.attention_path``) takes the kernel.
    The backend is the CPU's while tracing here, so the test steers it."""
    import dataclasses
    from repro.configs import get_config
    from repro.models import build_model
    from repro.models import layers as L
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(get_config("gpt2-medium"), num_layers=2)
    model = build_model(cfg)
    params = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                          model.abstract_params())
    tok = _sds((2, 1024), jnp.int32, one_chip)
    before = L.attention_path_counts()
    fn = jax.jit(jax.value_and_grad(
        lambda p, b: model.loss_fn(p, b)[0]))
    _compile(fn, params, {"tokens": tok, "labels": tok})
    after = L.attention_path_counts()
    assert after["kernel"] > before["kernel"]
    assert after["jnp"] == before["jnp"]


@pytest.fixture(scope="module")
def four_chips(topo):
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.array(topo.devices[:4]).reshape(4, 1), ("data", "model"))


@pytest.mark.parametrize("lane", ["mix", "mix-int8", "fused", "fused-int8"])
def test_pallas_gossip_lanes_compile_in_worker_body(four_chips, lane):
    """The gossip lanes call the kernels inside the shard_map body that is
    manual over the workers and leaves 'model' to GSPMD: a compiled Mosaic
    kernel must still lower there (an interpreted one always does)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.layerview import FlatPartition
    from repro.launch.train import (gossip_fused_lane, gossip_plane_lane,
                                    shard_map)
    M, shifts = 4, (1, 2)
    part = FlatPartition({"a": jnp.zeros((1024,)),
                          "b": jnp.zeros((16, 128), jnp.bfloat16)})
    kind, _, wire = lane.partition("-")
    wire = wire or "param"
    if kind == "mix":
        lane_fn = gossip_plane_lane(part, M, "data", shifts, use_pallas=True,
                                    interpret=False, wire=wire)
    else:
        lane_fn = gossip_fused_lane(part, M, "data", shifts, use_pallas=True,
                                    interpret=False, wire=wire)
    n_planes = (1 if kind == "mix" else 2) + (wire == "int8")

    def body(w, shift_idx, *planes):
        planes = [{k: v[0] for k, v in p.items()} for p in planes]
        out = lane_fn(*planes, w[0], shift_idx)
        return jax.tree.map(lambda v: v[None], out)

    wsh = NamedSharding(four_chips, P("data"))
    plane = {n: _sds((M, size), part.group_dtypes[n], wsh)
             for n, size in part.group_sizes.items()}
    hop = jax.jit(shard_map(
        body, mesh=four_chips, in_specs=(P("data"), P()) + (P("data"),) *
        n_planes, out_specs=P("data"), axis_names={"data"}))
    _compile(hop, _sds((M,), jnp.float32, wsh),
             _sds((), jnp.int32, NamedSharding(four_chips, P())),
             *([plane] * n_planes))


def test_splash_attention_compiles_in_worker_body(four_chips):
    """The attention kernel inside the shard_map body that is manual over
    the workers and leaves 'model' (size 1) to GSPMD, as the LayUp lanes
    run the model: ``per_shard`` makes the call manual over 'model'."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.train import shard_map
    shape, dt = ATTENTION["gpt2-medium"]

    def body(q, k, v):
        return jax.grad(_splash_loss, (0, 1, 2))(q, k, v)

    wsh = NamedSharding(four_chips, P("data"))
    q = _sds((4,) + shape[1:], dt, wsh)
    fn = jax.jit(shard_map(body, mesh=four_chips, in_specs=P("data"),
                           out_specs=P("data"), axis_names={"data"}))
    _compile(fn, q, q, q)


def test_latent_moe_layers_take_both_kernels_on_tpu(one_chip, monkeypatch):
    """``value_and_grad`` of Moonlight-16B-A3B's leading dense layer and one
    MoE layer at published widths, with one chip's 8 of the 64 experts and
    a 2048-token row: on the TPU the latent attention core takes the splash
    kernel at q·k 192 and values 128, and the routed products XLA's ragged
    dot kernel. The trace finds each by its instructions' names."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.models import layers as L
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("moonlight-16b-a3b").with_(num_layers=2, ep_size=8,
                                                vocab_size=20480)
    model = build_model(cfg)
    params = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                          model.abstract_params())
    tok = _sds((1, 2048), jnp.int32, one_chip)
    before = L.attention_path_counts()
    fn = jax.jit(jax.value_and_grad(
        lambda p, b: model.loss_fn(p, b)[0]))
    text = _compile(fn, params, {"tokens": tok, "labels": tok}).as_text()
    assert L.attention_path_counts()["kernel"] > before["kernel"]
    for kernel in ("splash_mha_fwd", "splash_mha_dkv", "ragged-dot"):
        assert re.search(rf"%{kernel}[\w.-]* = .*? custom-call\(", text), \
            kernel
