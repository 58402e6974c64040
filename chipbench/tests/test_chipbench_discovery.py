"""``BENCHMARK.json`` and the files it names: every cell's configuration,
traffic and limits exist, every metric has its reader, and the file keeps
to the benchmark's contract."""
import json
import os
import re

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["chipbench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.isfile(os.path.join(ROOT, bench["command"][1]))


def test_every_cell_names_existing_files(bench):
    configs = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert w["config"] in configs
        for kind, name in (("configs", w["config"]),
                           ("traffic", w["traffic"]),
                           ("limits", w["name"])):
            assert os.path.isfile(os.path.join(BENCH_DIR, kind,
                                               f"{name}.json")), (kind, name)
        assert w["chips"] in (1, 4)
        assert len(w["why"]) <= 200


def test_every_cell_is_read_whole(bench):
    """Every key of each cell's files is read: the traffic's job and step
    by the harness, ``make_step`` and the cell's family reference, as
    ``chipbench/run.py`` takes it; the configuration's by the harness and
    that reference."""
    from chipbench import cells
    for w in bench["workloads"]:
        ref = cells.family_module("reference", cells.config_dict(w["config"]))
        ref.check_job(cells.load_traffic(w["traffic"]))
        cells.load_config(w["config"])


def test_every_config_is_used_and_states_its_cuts(bench):
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert c["name"] in used
        with open(os.path.join(ROOT, c["file"])) as f:
            d = json.load(f)
        assert d["name"] == c["name"]
        assert sorted(d.get("reduced", {})) == sorted(c["reduced"])


def test_every_metric_has_a_reader(bench):
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH_DIR, "metrics",
                                           f"{m['name']}.py")), m["name"]
        e2e = {e["name"] for e in bench["end_to_end"]}
        assert m["moves"] in e2e
        cells = {w["name"] for w in bench["workloads"]}
        assert set(m.get("workloads", cells)) <= cells


def test_names_units_and_bounds(bench):
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
            assert (group, e["name"]) not in seen
            seen.add((group, e["name"]))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


def test_limits_hold_their_numbers(bench):
    from chipbench import check
    for w in bench["workloads"]:
        with open(os.path.join(BENCH_DIR, "limits", f"{w['name']}.json")) as f:
            lim = json.load(f)
        assert check.compared(lim), w["name"]
        for k in check.compared(lim):
            assert 0 < lim[k] < 1, (w["name"], k)


def test_a_null_limit_is_reported_and_not_compared():
    from chipbench import check
    numbers = {"loss_gap": 0.5, "grad_gap": 1e-4, "delta_gap": 1e-4}
    limits = {"loss_gap": None, "grad_gap": 1e-3, "delta_gap": 1e-3}
    assert check.compared(limits) == ["grad_gap", "delta_gap"]
    assert check.verdict(numbers, limits)
    assert not check.verdict(dict(numbers, grad_gap=2e-3), limits)
    assert check.report(numbers, limits)[0] == "loss_gap 0.5 not compared"
