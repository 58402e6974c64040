"""The per-stage reduction (``chipbench/stages.py``, ``chipbench/xspace.py``)
on small traces recorded on a TPU v5e by ``chipbench/tools/record_trace.py``:
``tiny_trace`` from a program without stage scopes, and the tiny LayUp cell
and the tiny pipeline-engine cell from the program with them; and on
scopes and intervals made by hand."""
import glob
import gzip
import os
import types

import pytest

from chipbench import run as R
from chipbench import stages as S
from chipbench import trace as T
from chipbench import xspace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
UNSCOPED = os.path.join(DATA, "tiny_trace.xplane.pb.gz")
SCOPED = os.path.join(DATA, "tiny_scoped_trace.xplane.pb.gz")
PIPELINE = os.path.join(DATA, "tiny_pipeline_trace.xplane.pb.gz")
READERS = sorted(os.path.basename(p)[:-3] for p in glob.glob(
    os.path.join(os.path.dirname(DATA), "..", "metrics", "*.py")))
# ops XLA makes itself, with no op of the program behind them
XLA_OWN = ("copy", "copy-start", "copy-done", "broadcast", "iota",
           "custom-call")


@pytest.fixture(scope="module")
def old():
    return T.load(UNSCOPED), S.load(UNSCOPED)


@pytest.fixture(scope="module", params=[UNSCOPED, SCOPED, PIPELINE],
                ids=["unscoped", "scoped", "pipeline"])
def any_trace(request):
    return S.load(request.param)


def test_every_xla_op_event_resolves_to_metadata():
    from jax.profiler import ProfileData
    raw = {p.name: p for p in xspace.load(UNSCOPED)}
    with gzip.open(UNSCOPED, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    n = 0
    for plane in data.planes:
        if T._device_index(plane.name) is None:
            continue
        lines = {ln.name: ln for ln in raw[plane.name].lines}
        for line in plane.lines:
            if line.name not in (T.SYNC_LINE, T.ASYNC_LINE):
                continue
            events = lines[line.name].events
            names = raw[plane.name].event_names
            got = [names[mid] for mid, _, _ in events]
            assert got == [ev.name for ev in line.events]
            n += len(got)
    assert n == 12_597 + 858


def test_leaf_ops_without_tf_op_are_xlas_own(old):
    _, tr = old
    a, b = T.window_of(tr)
    bare = [o for o in tr.ops[0] if o.leaf and not o.is_async and not o.scope]
    assert bare
    for o in bare:
        assert (o.category in XLA_OWN
                or o.category.startswith("fusion")
                and o.name.startswith("constant_")), (o.name, o.category)
    within = lambda ops: sum(max(0.0, min(o.end, b) - max(o.start, a))
                             for o in ops)
    assert within(bare) < 0.1 * S.leaf_busy(tr, 0)


# each accepted reader's value on the unscoped recorded trace, as it read
# before the per-stage reduction existed
BEFORE = {
    "device_idle_share": 98.10699065607879,
    "gossip_exposed_ms_per_step": None,
    "gossip_permute_ms_per_step": None,
    "matmul_roofline": 62.123726683032295,
    "non_matmul_ms_per_step": 0.011342699999907849,
    "step_hbm_compiled_gb": 0.123456789,
    "step_mfu": 0.5076142131979695,
}


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_accepted_readers_read_the_same_bit_for_bit(old, name):
    assert set(BEFORE) <= set(READERS)
    got = []
    for tr in old:
        rd = types.SimpleNamespace(
            trace=tr, stages={}, steps=100, window_s=0.1, chips=1,
            peak={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            flops_per_step=1e9, flops_by_part={"attention": 1e8},
            bytes_by_part={"attention": 1e7}, compiled_bytes=123456789,
            job={}, config={})
        got.append(R.load_reader(name)(rd))
    assert got == [BEFORE[name]] * 2


def test_breakdown_reads_the_same(old):
    a, b = old
    assert T.top_ops(a) == T.top_ops(b)
    assert T.top_gaps(a) == T.top_gaps(b)
    assert a.spans == b.spans and a.window == b.window


def test_stages_and_collectives_sum_to_leaf_busy(any_trace):
    tr = any_trace
    for chip in tr.chips():
        parts = S.partition(tr, chip)
        assert set(parts) == set(S.STAGES) | {"collective"}
        assert all(v >= 0 for v in parts.values())
        assert sum(parts.values()) == pytest.approx(S.leaf_busy(tr, chip),
                                                    rel=1e-12)
        assert S.leaf_busy(tr, chip) <= T.busy(tr, chip) * (1 + 1e-12)


def test_scoped_step_fills_every_stage_but_mix():
    tr = S.load(SCOPED)
    parts = S.partition(tr, 0)
    for stage in ("fwd", "bwd", "recompute", "update", "plane"):
        assert parts[stage] > 0, stage
    assert parts["mix"] == 0.0 and parts["collective"] == 0.0
    assert S.cross_cut(tr, 0, "attention") > 0
    assert S.cross_cut(tr, 0, "head") > 0
    assert not tr.program_spans   # the monolithic step dispatches once


def test_pipeline_engine_spans_and_dispatch_idle():
    tr = S.load(PIPELINE)
    names = {n for _, _, n in tr.program_spans}
    assert {"repro.fwd0", "repro.fwd1", "repro.update", "repro.gossip",
            "repro.poll"} <= names
    assert S.dispatch_idle(tr, 0) > 0
    assert S.dispatch_idle(tr, 0) <= T.length(T.idle_gaps(tr, 0))
    parts = S.partition(tr, 0)
    for stage in ("fwd", "bwd", "recompute", "update", "plane"):
        assert parts[stage] > 0, stage


# the recorded scoped traces' per-stage seconds, pinned
PINNED = {
    SCOPED: {
        "recompute": 0.00016826199999805946, "mix": 0.0,
        "update": 0.00024239000000011307, "plane": 0.00013323700000045235,
        "bwd": 0.0004549869999971659, "fwd": 0.000565727999993465,
        "unscoped": 0.00033796299999606927, "collective": 0.0,
        "attention": 0.00014079899999649287,
        "head": 0.00017757700000000515, "dispatch_idle": 0.0,
    },
    PIPELINE: {
        "recompute": 0.00013285599999864062, "mix": 0.0,
        "update": 0.0002272020000002109, "plane": 0.0001772659999997664,
        "bwd": 0.0003626669999982693, "fwd": 0.0004469939999950587,
        "unscoped": 0.00040217599999693016, "collective": 0.0,
        "attention": 0.00011109299999764038,
        "head": 0.00015047399999974786,
        "dispatch_idle": 0.06601171300000455,
    },
}


@pytest.mark.parametrize("path", [SCOPED, PIPELINE],
                         ids=["scoped", "pipeline"])
def test_recorded_stage_seconds_are_pinned(path):
    tr = S.load(path)
    got = S.partition(tr, 0)
    got["attention"] = S.cross_cut(tr, 0, "attention")
    got["head"] = S.cross_cut(tr, 0, "head")
    got["dispatch_idle"] = S.dispatch_idle(tr, 0)
    assert got == PINNED[path]


@pytest.mark.parametrize("scope,stage", [
    ("jit(step)/fwd0/jvp()/while/body/closed_call/mul:", "fwd"),
    ("jit(step)/fwd1/while/body/closed_call/attention/exp:", "fwd"),
    ("jit(step)/fwd0/transpose(jvp())/while/body/closed_call/"
     "transpose(jvp())/dot_general:", "bwd"),
    ("jit(f)/transpose(jvp(fwd0))/mul:", "bwd"),
    ("jit(step)/fwd0/transpose(jvp())/while/body/closed_call/recompute/"
     "jvp(attention)/dot_general:", "recompute"),
    # remat's pullback: the backward of the recomputed forward
    ("jit(step)/fwd0/transpose(jvp())/while/body/closed_call/"
     "transpose(recompute)/jvp(attention)/dot_general:", "bwd"),
    ("jit(step)/update/add:", "update"),
    ("jit(step)/unpack/slice:", "plane"),
    ("jit(step)/pack/concatenate:", "plane"),
    ("jit(step)/clock/reduce_sum:", "plane"),
    ("jit(step)/mix.blocks/add:", "mix"),
    ("jit(step)/clock/mix.weight/mul:", "mix"),
    ("jit(step)/update/mul;jit(step)/fwd0/transpose(jvp())/mul:", "update"),
    ("jit(step)/sin:", "unscoped"),
    ("jit(step)/fwd0x/mul:", "unscoped"),
    ("", "unscoped"),
])
def test_stage_of(scope, stage):
    assert S.stage_of(scope) == stage


def test_partition_and_dispatch_idle_by_hand():
    op = lambda s, e, scope, cat="fusion:kLoop", a=False: S.ScopedOp(
        s, e, "x", cat, is_async=a, scope=scope)
    tr = S.ScopedTrace(ops={0: [
        op(0.0, 2.0, "jit(s)/fwd0/jvp()/mul:"),
        op(1.5, 3.0, "jit(s)/update/add:"),      # overlaps the forward
        op(4.0, 5.0, "", "collective-permute-start"),
        op(6.0, 7.0, "jit(s)/fwd0/transpose(jvp())/mul:"),
    ]}, window=(0.0, 10.0))
    tr.program_spans = [(3.0, 4.5, "repro.update"), (8.0, 9.0, "repro.poll")]
    parts = S.partition(tr, 0)
    assert parts["fwd"] == 2.0 and parts["update"] == 1.0
    assert parts["collective"] == 1.0 and parts["bwd"] == 1.0
    assert sum(parts.values()) == S.leaf_busy(tr, 0) == 5.0
    # idle: 3..4, 5..6, 7..10; inside the spans: 3..4 and 8..9
    assert S.dispatch_idle(tr, 0) == pytest.approx(2.0)
    out = S.per_step(tr, 2)
    assert out["fwd_ms_per_step"] == 1e3 and out["mix_ms_per_step"] is None
    assert out["dispatch_idle_ms_per_step"] == pytest.approx(1e3)
