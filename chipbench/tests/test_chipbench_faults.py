"""The comparison that decides ``correct``, driven through the whole
harness at a size a test run holds, on the CPU (the look for a chip is
skipped): sound runs come out correct, and each fault a training cell can
have, planted in the timed path, comes out not correct, as does the
control (the reference in the next precision below, in the program's
place). A traffic file's step arguments reach the program as they stand:
an engine cell runs through the same harness, and a key that nothing
reads, or that the reference does not follow, stops the run."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import cells, run as R
from chipbench.calibrate import StaleReadProgram

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
ROOT = os.path.dirname(os.path.dirname(HERE))
SEED = 4_000_000_017   # more than 32 signed bits hold


def cpu_gate(chips):
    return {"platform": "cpu", "kind": "TPU v5 lite", "count": chips}


def run_cell(workload, **kw):
    args = R.parse(["--workload", workload, "--seed", str(SEED),
                    "--seconds", "0.2", "--trace", "0"])
    return R.run(args, base=DATA, bench_path=os.path.join(DATA,
                                                          "BENCHMARK.json"),
                 gate=cpu_gate, cache=False, **kw)


class StuckProgram(cells.Program):
    """A step that returns its state unchanged (it still reports a loss)."""

    def step(self, batch, t):
        import jax
        import jax.numpy as jnp
        keep = self.state
        self.state = jax.tree.map(jnp.copy, keep)
        loss = super().step(batch, t)
        self.state = keep
        return loss


class HalfProgram(cells.Program):
    """Half of each worker's rows left out; the mean is over the rest."""

    def put_batch(self, host_batch):
        M, B = self.M, int(self.job["batch_per_worker"])
        half = {k: v.reshape((M, B) + v.shape[1:])[:, :B // 2]
                   .reshape((M * (B // 2),) + v.shape[1:])
                for k, v in host_batch.items()}
        return super().put_batch(half)


class UnstampedProgram(cells.Program):
    """Version clocks that are never stamped."""

    def step(self, batch, t):
        loss = super().step(batch, t)
        self.state["versions"] = self.state["versions"] * 0.0
        return loss


def half_build(cfg, job, mesh):
    return cells.build_step(cfg, dict(job, batch_per_worker=int(
        job["batch_per_worker"]) // 2), mesh)


@pytest.mark.parametrize("workload", ["tiny.layup.m1", "tiny.ddp.m1",
                                      "tiny-untied.layup.m1",
                                      "tiny.layup-pipeline.m1",
                                      "tiny.layup-streams3.m1"])
def test_sound_run_is_correct(workload):
    out = run_cell(workload)
    assert out["correct"], out["checked"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checked"
    assert set(out["metrics"]) == {"trained_tokens_per_s_per_chip",
                                   "peak_hbm_gb", "setup_s"}


def test_read_plane_left_behind_is_refused():
    out = run_cell("tiny.layup.m1", program=StaleReadProgram)
    assert not out["correct"]
    assert out["checked"]["delta_gap"]["value"] > 0.1


def test_step_the_reference_does_not_follow_stops_the_run():
    with pytest.raises(ValueError, match="does not follow"):
        run_cell("tiny.layup-int8.m1")


@pytest.mark.parametrize("where,key", [("job", "in_flight"),
                                       ("step", "bogus"),
                                       ("config", "matmul_precision")])
def test_key_that_nothing_reads_stops_the_run(tmp_path, where, key):
    import shutil
    base = str(tmp_path / "data")
    shutil.copytree(DATA, base)
    path = os.path.join(base, *{"job": ("traffic", "layup.m1.json"),
                                "step": ("traffic", "layup.m1.json"),
                                "config": ("configs", "tiny.json")}[where])
    with open(path) as f:
        d = json.load(f)
    (d["step"] if where == "step" else d)[key] = 2
    with open(path, "w") as f:
        json.dump(d, f)
    args = R.parse(["--workload", "tiny.layup.m1", "--seed", "1",
                    "--seconds", "0.2", "--trace", "0"])
    with pytest.raises(ValueError, match="nothing reads"):
        R.run(args, base=base, bench_path=os.path.join(base,
                                                       "BENCHMARK.json"),
              gate=cpu_gate, cache=False)


@pytest.mark.parametrize("workload", ["tiny.layup.m1", "tiny.ddp.m1"])
def test_state_left_unchanged_is_refused(workload):
    out = run_cell(workload, program=StuckProgram)
    assert not out["correct"]
    assert out["checked"]["delta_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out_is_refused():
    out = run_cell("tiny.layup.m1", program=HalfProgram,
                   build_step=half_build)
    assert not out["correct"]
    assert out["checked"]["grad_gap"]["value"] > 0.1


@pytest.mark.parametrize("workload", ["tiny.layup.m1",
                                      "tiny-untied.layup.m1"])
def test_control_is_refused(workload, monkeypatch):
    """The reference in the next precision below the configuration's
    (bf16 for f32, fp8 matmuls for bf16), put in the program's place."""
    from chipbench.reference import dense as ref
    import jax

    def control_steps(prog, job, V, seed, n):
        cdict = cells.config_dict(cells.find_cell(
            cells.load_benchmark(os.path.join(DATA, "BENCHMARK.json")),
            workload)["config"], DATA)
        return R.reference(ref, cdict, job, seed, n, jax.devices()[:1],
                           p=ref.CONTROLS[cdict["dtype"]])

    monkeypatch.setattr(R, "checked_steps", control_steps)
    out = run_cell(workload)
    assert not out["correct"], out["checked"]


M2_SCRIPT = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
sys.path.insert(0, {root!r})
sys.path.insert(1, os.path.join({root!r}, "src"))
from chipbench.tests import test_chipbench_faults as F
import repro.launch.train as train
out = {{"sound": F.run_cell("tiny.layup.m2"),
        "ddp": F.run_cell("tiny.ddp.m2"),
        "unstamped": F.run_cell("tiny.layup.m2",
                                program=F.UnstampedProgram)}}
train.gossip_plane_lane = lambda part, M, ax, shifts, **kw: (
    lambda plane, w, shift_idx, alive=None: (plane, w))
out["no_exchange"] = F.run_cell("tiny.layup.m2")
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def m2():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", M2_SCRIPT.format(root=ROOT)],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    lines = [x for x in p.stdout.splitlines() if x.startswith("RESULT ")]
    assert lines, p.stderr[-3000:]
    return json.loads(lines[-1][len("RESULT "):])


def test_two_workers_sound_run_is_correct(m2):
    assert m2["sound"]["correct"], m2["sound"]["checked"]
    assert m2["sound"]["device"]["count"] == 2
    assert m2["sound"]["checked"]["clock_gap"]["value"] == 0.0


def test_ddp_over_two_workers_is_correct(m2):
    assert m2["ddp"]["correct"], m2["ddp"]["checked"]
    assert "clock_gap" not in m2["ddp"]["checked"]


def test_exchange_between_chips_left_out_is_refused(m2):
    assert not m2["no_exchange"]["correct"]
    assert m2["no_exchange"]["checked"]["delta_gap"]["value"] > 0.1


def test_clocks_left_unstamped_are_refused(m2):
    assert not m2["unstamped"]["correct"]
    assert m2["unstamped"]["checked"]["clock_gap"]["value"] > 2.0
