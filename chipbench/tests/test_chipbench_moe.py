"""The MoE family through the harness at a size a CPU test run holds: a tiny
Moonlight-16B-A3B block (a leading dense layer, two MoE layers of 8 routed
experts of which this chip holds 2, top-2, sigmoid scores with the
correction bias, shared experts, latent attention) is built from its
configuration file by ``cells.load_config`` and ``build_step``, driven
three LayUp steps through the window's own call, and compared with the
plain reference (``chipbench/reference/moe.py``) under the Moonlight cell's
limits; the fp8 control is refused by them."""
import dataclasses
import os

import jax
import pytest

from chipbench import cells, check, run as R
from chipbench.reference import moe as ref

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
SEED = 4_000_000_019
CELL = "moonlight-16b-a3b.layup-r2d1.m1"


@pytest.fixture(scope="module")
def readings():
    cfg = cells.load_config("tiny-moe", DATA)
    cdict = cells.config_dict("tiny-moe", DATA)
    job = cells.load_traffic("layup.m1", DATA)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1])
    prog = cells.Program(cfg, job, mesh, cells.build_step(cfg, job, mesh),
                         SEED)
    got = R.checked_steps(prog, job, cdict["vocab_size"], SEED,
                          check.CHECK_STEPS)
    prog.free()
    args = (ref, cdict, job, SEED, check.CHECK_STEPS, jax.devices()[:1])
    want = R.reference(*args)
    ctl = R.reference(*args, p=ref.CONTROLS["bfloat16"])   # fp8 operands
    return cfg, check.gaps(got, want), check.gaps(ctl, want)


def test_program_matches_reference_under_the_cells_limits(readings):
    cfg, numbers, _ = readings
    assert cfg.experts_held == 2 and cfg.first_k_dense_replace == 1
    limits = cells.load_limits(CELL)
    for k in ("loss_gap", "grad_gap", "delta_gap"):
        assert numbers[k] <= (limits[k] if limits[k] is not None else 1e-4), \
            (k, numbers[k])


def test_fp8_control_is_refused(readings):
    _, _, control = readings
    assert not check.verdict(control, cells.load_limits(CELL)), control


def test_reference_sizes_are_program_fields():
    from repro.configs import ModelConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    assert set(ref.SIZES) <= fields
    # the cell's file states each, under the published config.json's name
    d = cells.config_dict("moonlight-16b-a3b")
    assert d["n_routed_experts"] == 64 and d["ep_size"] == 8


def _experts_trace():
    """A step by hand on one chip over a 10 s window: the routed products
    as XLA's ragged-dot custom calls, which carry no scope, the permute
    under the ``experts`` scope, and an attention op."""
    from chipbench import stages as S
    op = S.ScopedOp
    ops = [op(0.0, 1.0, "ragged-dot-metadata.1", "custom-call",
              scope="ragged-dot-metadata"),
           op(1.0, 2.0, "ragged-dot-none.3", "custom-call",
              scope="ragged-dot-none"),
           op(2.0, 2.5, "fusion.7", "fusion:kLoop",
              scope="jit(step)/fwd0/experts/gather:"),
           op(2.5, 4.0, "fusion.8", "fusion:kOutput",
              scope="jit(step)/fwd0/attention/dot_general:")]
    return S.ScopedTrace(ops={0: ops}, window=(0.0, 10.0))


def test_experts_readers_find_the_unscoped_products():
    import types
    tr = _experts_trace()
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run = types.SimpleNamespace(trace=tr, steps=10, chips=1, peak=peak,
                                flops_by_part={"experts": 4e11},
                                bytes_by_part={"experts": 1e8})
    # the products, their bookkeeping and the permute: 2.5 s over 10 steps
    assert R.load_reader("experts_ms_per_step")(run) == pytest.approx(250.0)
    # the kernel is the ragged-dot ops alone: 2 s; bound by its operations
    need = 4e11 / 197e12
    assert need > 1e8 / 819e9
    assert R.load_reader("experts_roofline")(run) == pytest.approx(
        100 * need / 0.2, rel=1e-12)
    run.flops_by_part = {}
    assert R.load_reader("experts_roofline")(run) is None
