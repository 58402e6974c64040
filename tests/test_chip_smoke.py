"""The chip smoke script's CPU-side contract: it refuses to run without a
TPU (no ok line), and the compile cache it turns on lands where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``<repo>/.jax_cache``."""
import importlib.util
import os
import pathlib

import pytest

from _subproc import run_sub as _run

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_device_gate_refuses_cpu(chip_smoke, capsys):
    import jax
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit) as e:
        chip_smoke.device_gate()
    assert e.value.code not in (0, None)
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "no TPU" in captured.err


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_without_tpu(chip_smoke, capsys, argv):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main(argv)
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_default_cache_dir_is_fixed_in_the_repo():
    from repro.launch import cache
    assert cache.DEFAULT_CACHE_DIR == ROOT / ".jax_cache"


_COMPILE_ONE = """
import jax, jax.numpy as jnp
from repro.launch import cache
{setup}
path = cache.enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(3)).block_until_ready()
print("CACHE", path)
"""


def test_compile_cache_follows_env_var(tmp_path):
    env_dir = tmp_path / "from_env"
    before = (sorted(os.listdir(ROOT / ".jax_cache"))
              if (ROOT / ".jax_cache").exists() else None)
    setup = (f"import os; os.environ['JAX_COMPILATION_CACHE_DIR'] = "
             f"{str(env_dir)!r}")
    # the variable must be set before jax reads its configuration
    code = setup + "\n" + _COMPILE_ONE.format(setup="")
    out = _run(code, timeout=300)
    assert f"CACHE {env_dir}" in out
    assert any(env_dir.iterdir())
    after = (sorted(os.listdir(ROOT / ".jax_cache"))
             if (ROOT / ".jax_cache").exists() else None)
    assert after == before


def test_compile_cache_defaults_to_fixed_dir(tmp_path):
    fixed = tmp_path / "fixed"
    setup = f"cache.DEFAULT_CACHE_DIR = {str(fixed)!r}"
    code = ("import os; os.environ.pop('JAX_COMPILATION_CACHE_DIR', None)\n"
            + _COMPILE_ONE.format(setup=setup))
    out = _run(code, timeout=300)
    assert f"CACHE {fixed}" in out
    assert any(fixed.iterdir())


def test_one_chip_phases_rehearse_on_cpu(chip_smoke, monkeypatch):
    """The one-chip phases' logic, run on the CPU at the reduced config's
    widths with the Pallas kernels interpreted: catches a smoke script
    that drifted from the APIs it drives before it costs a chip run."""
    from repro.configs import get_config, reduced
    monkeypatch.setattr(chip_smoke, "INTERPRET", True)
    cfg = reduced(get_config(chip_smoke.ARCH))
    chip_smoke.phase_kernels(cfg, 0, seq=128, batch=2)
    chip_smoke.phase_main(cfg, 0, seq=64, batch=8, steps=3)


def test_four_chip_phases_rehearse_on_cpu():
    """The --chips 4 phases on four host devices at the reduced widths.
    Nothing on the path imports the dry-run module, which rewrites
    XLA_FLAGS for 512 fake devices when imported."""
    out = _run(f"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, {str(ROOT)!r})
import chip_smoke
from repro.configs import get_config, reduced
chip_smoke.INTERPRET = True
cfg = reduced(get_config(chip_smoke.ARCH))
chip_smoke.phase_m4_step(cfg, 0, seq=64, batch=4, steps=3)
chip_smoke.phase_ring_hop(cfg, 0)
chip_smoke.phase_sim_parity(cfg, 0, steps=3)
assert "repro.launch.dryrun" not in sys.modules
print("REHEARSED")
""", timeout=900)
    assert "REHEARSED" in out
