"""The per-layer readers that take the program's scopes and kernels: each
stage reader returns what ``chipbench/stages.py``'s ``per_step`` gives on
the tiny LayUp and pipeline-engine traces recorded on a TPU v5e, and a
kernel of ``chipbench/kernels`` is read by its own roofline and left out of
``matmul_roofline`` and ``non_matmul_ms_per_step`` where, and only where,
the trace holds its ops."""
import json
import os
import types

import pytest

from chipbench import kernels
from chipbench import run as R
from chipbench import stages as S

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
SCOPED = os.path.join(DATA, "tiny_scoped_trace.xplane.pb.gz")
PIPELINE = os.path.join(DATA, "tiny_pipeline_trace.xplane.pb.gz")
STAGE_READERS = ("fwd", "bwd", "recompute", "update", "plane", "unscoped",
                 "attention", "head", "mix", "dispatch_idle")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
STEPS = 10


def _run(tr, steps=STEPS, **kw):
    """What ``chipbench/run.py`` hands each per-layer reader."""
    rd = dict(trace=tr, stages=S.per_step(tr, steps), steps=steps,
              window_s=1.0, chips=1, peak=PEAK, flops_per_step=1e12,
              flops_by_part={"dense": 7e11, "attention": 2e11,
                             "head": 1e11},
              bytes_by_part={"attention": 1e8}, compiled_bytes=None,
              job={}, config={})
    rd.update(kw)
    return types.SimpleNamespace(**rd)


@pytest.fixture(scope="module", params=[SCOPED, PIPELINE],
                ids=["scoped", "pipeline"])
def recorded(request):
    return request.param, S.load(request.param)


@pytest.mark.parametrize("stage", STAGE_READERS)
def test_stage_reader_reads_per_step(recorded, stage):
    path, tr = recorded
    name = f"{stage}_ms_per_step"
    got = R.load_reader(name)(_run(tr))
    assert got == S.per_step(tr, STEPS)[name]
    if stage == "mix":
        assert got is None            # one worker: nothing mixes
    elif stage == "dispatch_idle":
        # only the pipeline engine writes the program's host spans
        assert (got is None) == (path == SCOPED)
        assert path == SCOPED or got > 0
    else:
        assert got > 0


def test_stage_readers_exist_in_the_benchmark():
    with open(os.path.join(os.path.dirname(R.HERE), "BENCHMARK.json")) as f:
        names = {m["name"] for m in json.load(f)["per_layer"]}
    assert {f"{s}_ms_per_step" for s in STAGE_READERS} <= names
    assert {f"{k}_roofline" for k in kernels.table()} <= names


def test_nothing_to_read_without_ops():
    tr = S.ScopedTrace(ops={}, window=(0.0, 1.0))
    for stage in STAGE_READERS:
        assert R.load_reader(f"{stage}_ms_per_step")(_run(tr)) is None
    assert R.load_reader("attention_roofline")(_run(tr)) is None


def test_plane_stage_of_a_step_without_plane_reads_nothing():
    op = S.ScopedOp(0.0, 1.0, "fusion.1", "fusion:kLoop",
                    scope="jit(step)/fwd0/jvp()/mul:")
    tr = S.ScopedTrace(ops={0: [op]}, window=(0.0, 2.0))
    assert R.load_reader("plane_ms_per_step")(_run(tr)) is None
    assert R.load_reader("fwd_ms_per_step")(_run(tr)) == 1e3 / STEPS


# a step by hand: a dot, a loop fusion, the kernel's forward and backward
# custom calls, a collective, all on one chip over a 10 s window
def _hand_trace(with_kernel: bool):
    op = S.ScopedOp
    ops = [op(0.0, 2.0, "fusion.1", "fusion:kOutput"),
           op(2.0, 3.0, "fusion.2", "fusion:kLoop"),
           op(6.0, 6.5, "all-reduce.1", "all-reduce")]
    if with_kernel:
        ops += [op(3.0, 4.0, "splash_mha_fwd_residuals.3", "custom-call"),
                op(4.0, 5.5, "splash_mha_dkv_no_residuals.3", "custom-call")]
    else:   # the jnp path: the same core as fusions
        ops += [op(3.0, 5.5, "fusion.3", "fusion:kLoop")]
    ops.sort(key=lambda o: o.start)
    return S.ScopedTrace(ops={0: ops}, window=(0.0, 10.0))


def _read(tr, name, **kw):
    return R.load_reader(name)(_run(tr, **kw))


def test_kernel_read_by_its_own_roofline():
    tr = _hand_trace(with_kernel=True)
    assert set(kernels.traced(tr)) == {"attention"}
    # 2.5 s of kernel over 10 steps; the part's least time per step is its
    # operations at the peak, which take longer than its bytes
    need = 2e11 / 197e12
    assert need > 1e8 / 819e9
    assert _read(tr, "attention_roofline") == pytest.approx(
        100 * need / 0.25, rel=1e-12)
    # the part's operations leave matmul_roofline's numerator: 2 s of dots
    assert _read(tr, "matmul_roofline") == pytest.approx(
        100 * (1e12 - 2e11) / 197e12 / 0.2, rel=1e-12)
    # and the kernel's ops leave non_matmul_ms_per_step: 1 s of fusion
    assert _read(tr, "non_matmul_ms_per_step") == pytest.approx(100.0)


def test_kernel_roofline_bound_by_bytes():
    tr = _hand_trace(with_kernel=True)
    got = _read(tr, "attention_roofline", bytes_by_part={"attention": 1e12})
    assert got == pytest.approx(100 * (1e12 / 819e9) / 0.25, rel=1e-12)


def test_without_kernel_ops_the_part_stays_a_matmul_part():
    tr = _hand_trace(with_kernel=False)
    assert kernels.traced(tr) == {}
    assert _read(tr, "attention_roofline") is None
    assert _read(tr, "matmul_roofline") == pytest.approx(
        100 * 1e12 / 197e12 / 0.2, rel=1e-12)
    assert _read(tr, "non_matmul_ms_per_step") == pytest.approx(350.0)


def test_kernel_ops_on_the_recorded_traces(recorded):
    """The recorded tiny traces predate the kernel: nothing of it is read."""
    _, tr = recorded
    assert kernels.traced(tr) == {}
    assert _read(tr, "attention_roofline") is None


def test_kernel_table_names_parts_of_the_flops():
    from chipbench.flops import dense
    c = {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
         "head_dim": 16, "d_ff": 128, "vocab_size": 256,
         "tie_embeddings": True, "dtype": "float32"}
    job = {"seq_len": 32, "batch_per_worker": 2, "workers": 1,
           "step": {"algo": "layup", "fb_ratio": 2}}
    parts = dense.step_flops_by_part(c, job)
    for k in kernels.table().values():
        assert k["part"] in parts
        assert k["ops"] and " " not in k["ops"]


def test_traced_run_hands_every_reader_its_input(tmp_path):
    """A traced run of the tiny cell through the whole harness on the CPU
    (the look for a chip skipped), with the benchmark's own per-layer
    metrics: every reader gets what it reads, and those that read the
    device's ops find none on the CPU and are left out."""
    from chipbench.tests.test_chipbench_faults import DATA as FDATA
    from chipbench.tests.test_chipbench_faults import SEED, cpu_gate
    with open(os.path.join(os.path.dirname(R.HERE), "BENCHMARK.json")) as f:
        per_layer = [{k: v for k, v in m.items() if k != "workloads"}
                     for m in json.load(f)["per_layer"]]
    with open(os.path.join(FDATA, "BENCHMARK.json")) as f:
        bench = dict(json.load(f), per_layer=per_layer)
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    args = R.parse(["--workload", "tiny.layup.m1", "--seed", str(SEED),
                    "--seconds", "0.2", "--trace", "1"])
    out = R.run(args, base=FDATA, bench_path=str(path), gate=cpu_gate,
                cache=False)
    assert out["correct"], out["checked"]
    device = {m["name"] for m in per_layer if m["source"] == "device_trace"}
    assert not device & set(out["metrics"])
    assert "step_mfu" in out["metrics"]
    assert out["breakdown"] == {"device_ops": [], "idle_gaps": []}
