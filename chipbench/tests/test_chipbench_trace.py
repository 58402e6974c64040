"""The trace reduction, on a small trace recorded on a TPU v5e (the tiny
test cell through the harness with ``--trace 1``, by
``chipbench/tools/record_trace.py``), and on intervals made by hand."""
import os

import pytest

from chipbench import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny_trace.xplane.pb.gz")


@pytest.fixture(scope="module")
def tr():
    return T.load(DATA)


def test_recorded_trace_has_one_chip_and_the_window(tr):
    assert tr.chips() == [0]
    a, b = T.window_of(tr)
    assert tr.window == (a, b) and 0 < b - a < 1.0
    names = {n for _, _, n in tr.spans}
    assert {"chipbench.window", "chipbench.batch", "chipbench.dispatch",
            "chipbench.wait", "chipbench.drain"} <= names


def test_busy_and_idle_gaps_cover_the_window(tr):
    a, b = T.window_of(tr)
    busy = T.busy(tr, 0)
    idle = T.length(T.idle_gaps(tr, 0))
    assert 0 < busy < b - a
    assert busy + idle == pytest.approx(b - a, rel=1e-9, abs=1e-12)


def test_op_classes(tr):
    ops = tr.ops[0]
    cats = {o.category for o in ops}
    assert "fusion:kOutput" in cats and "fusion:kLoop" in cats
    assert any(o.kind == "matmul" for o in ops if o.leaf)
    assert any(o.kind == "other" for o in ops if o.leaf)
    # the scanned layers run as while loops that enclose their body's ops
    loops = [o for o in ops if o.category == "while"]
    assert loops and not any(o.leaf for o in loops)
    # classes of leaves never exceed the compute stream's busy time
    total = sum(T.kind_seconds(tr, 0, k)
                for k in ("matmul", "collective", "other"))
    assert total <= T.busy(tr, 0) * (1 + 1e-9)
    assert T.kind_seconds(tr, 0, "collective") == 0.0


def test_gaps_are_named_by_a_host_span(tr):
    gaps = T.top_gaps(tr)
    assert gaps and len(gaps) <= 10
    assert all(name.startswith("chip0 chipbench.") for name, _ in gaps)
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)


def test_top_ops_are_leaves_with_short_names(tr):
    top = T.top_ops(tr)
    assert 0 < len(top) <= 10
    assert all(" = " not in name and "[" in name for name, _ in top)


@pytest.mark.parametrize("text,name,cat", [
    ("%fusion.4 = f32[256,64]{1,0:T(8,128)} fusion(f32[256,64]{1,0} %p), "
     "kind=kOutput, calls=%fused_computation.4", "fusion.4", "fusion:kOutput"),
    ("%while.1 = (s32[]{:T(128)}, f32[2]{0}) while((s32[], f32[2]) %t), "
     "condition=%c, body=%b", "while.1", "while"),
    ("%collective-permute-start.1 = (f32[8]{0}, f32[8]{0}) "
     "collective-permute-start(f32[8]{0} %x), source_target_pairs={{0,1}}",
     "collective-permute-start.1", "collective-permute-start"),
    ("%convolution.2 = bf16[4,8]{1,0} convolution(bf16[4,2]{1,0} %a, "
     "bf16[2,8]{1,0} %b), dim_labels=bf_io->bf", "convolution.2",
     "convolution"),
])
def test_parse_op(text, name, cat):
    assert T.parse_op(text) == (name, cat)


def test_classify():
    assert T.classify("fusion:kOutput") == "matmul"
    assert T.classify("convolution") == "matmul"
    assert T.classify("collective-permute-done") == "collective"
    assert T.classify("all-reduce") == "collective"
    assert T.classify("fusion:kLoop") == "other"


def _trace(ops, window=(0.0, 10.0)):
    tr = T.Trace(ops={0: [T.Op(s, e, n, c, is_async=a)
                          for s, e, n, c, a in ops]}, window=window)
    for o in tr.ops[0]:
        o.leaf = True
    return tr


def test_exposed_collective_time_by_hand():
    tr = _trace([(0.0, 2.0, "f1", "fusion:kOutput", False),
                 (1.0, 4.0, "cp", "collective-permute-start", True),
                 (3.0, 5.0, "f2", "fusion:kLoop", False)])
    assert T.seconds(tr, 0, T.is_permute) == pytest.approx(3.0)
    # 1..4 overlaps f1 until 2 and f2 from 3: exposed 2..3
    assert T.exposed(tr, 0, T.is_permute) == pytest.approx(1.0)
    assert T.busy(tr, 0) == pytest.approx(4.0)
    assert T.idle_gaps(tr, 0) == [(2.0, 3.0), (5.0, 10.0)]


def test_union_and_subtract_by_hand():
    assert T.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert T.subtract([(0, 10)], [(1, 2), (4, 6)]) == [(0, 1), (2, 4),
                                                         (6, 10)]
    assert T.clip([(-1, 1), (5, 20)], (0, 10)) == [(0, 1), (5, 10)]
