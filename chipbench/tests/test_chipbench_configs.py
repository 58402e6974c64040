"""A configuration file states the sizes its family's reference reads
(``SIZES`` of ``chipbench/reference/<family>.py``), each a field of the
program's ``ModelConfig``, and nothing else but its records. A new family
is its reference and flops module, found by name: the tiny second family
here is added with no edit to any file of the benchmark."""
import dataclasses
import json
import os
import shutil
import sys

import pytest

import chipbench.flops
import chipbench.reference
from chipbench import cells

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")

# the sizes a configuration file stated before each family declared its
# own, and that the dense reference reads
DENSE_SIZES = ("num_layers", "d_model", "num_heads", "num_kv_heads",
               "head_dim", "d_ff", "vocab_size", "tie_embeddings",
               "rope_fraction", "rope_theta", "norm_eps")

TINY_MOE_REFERENCE = '''
from chipbench.reference.dense import SIZES as DENSE, check_job, train
SIZES = DENSE + ("num_experts", "experts_per_token")
'''
TINY_MOE_FLOPS = '''
from chipbench.flops.dense import (step_bytes_by_part, step_flops,
                                   step_flops_by_part,
                                   trained_tokens_per_step)
'''
# a reference that reads a size the program does not have
TINY_BAD_REFERENCE = '''
from chipbench.reference.dense import SIZES as DENSE
SIZES = DENSE + ("num_routed_experts",)
'''


@pytest.mark.parametrize("name", ["gpt2-medium", "stablelm-1.6b"])
def test_real_configs_load_as_before(name):
    """The same ``ModelConfig`` as the rule that read a fixed list of dense
    sizes gave."""
    import jax.numpy as jnp
    from repro.configs import get_config
    with open(os.path.join(cells.HERE, "configs", f"{name}.json")) as f:
        d = json.load(f)
    want = get_config(d["registry"]).with_(
        dtype=jnp.dtype(d["dtype"]), **{k: d[k] for k in DENSE_SIZES})
    assert cells.load_config(name) == want
    from chipbench.reference import dense
    assert dense.SIZES == DENSE_SIZES


@pytest.fixture
def families(tmp_path, monkeypatch):
    """The tiny families' modules as files of their own, found where the
    harness looks for a family by name, and a copy of the test data."""
    code = tmp_path / "families"
    for pkg, files in (("reference", {"tinymoe": TINY_MOE_REFERENCE,
                                      "tinybad": TINY_BAD_REFERENCE}),
                       ("flops", {"tinymoe": TINY_MOE_FLOPS})):
        (code / pkg).mkdir(parents=True)
        for fam, src in files.items():
            (code / pkg / f"{fam}.py").write_text(src)
    monkeypatch.setattr(chipbench.reference, "__path__",
                        list(chipbench.reference.__path__)
                        + [str(code / "reference")])
    monkeypatch.setattr(chipbench.flops, "__path__",
                        list(chipbench.flops.__path__)
                        + [str(code / "flops")])
    base = tmp_path / "data"
    shutil.copytree(DATA, base)
    yield str(base)
    for kind in ("reference", "flops"):
        for fam in ("tinymoe", "tinybad"):
            sys.modules.pop(f"chipbench.{kind}.{fam}", None)


def _write_config(base, name, **changes):
    with open(os.path.join(base, "configs", "tiny.json")) as f:
        d = json.load(f)
    d.update(name=name, **changes)
    for k in [k for k, v in changes.items() if v is None]:
        del d[k]
    with open(os.path.join(base, "configs", f"{name}.json"), "w") as f:
        json.dump(d, f)


def test_second_family_takes_its_sizes(families):
    _write_config(families, "tiny-moe", family="tinymoe", num_experts=4,
                  experts_per_token=2)
    cdict = cells.config_dict("tiny-moe", families)
    cfg = cells.load_config("tiny-moe", families)
    assert (cfg.num_experts, cfg.experts_per_token) == (4, 2)
    assert (cfg.num_layers, cfg.d_model, cfg.vocab_size) == (2, 64, 256)
    ref = cells.family_module("reference", cdict)
    assert "num_experts" in ref.SIZES
    flops = cells.family_module("flops", cdict)
    assert flops.__file__.startswith(os.path.dirname(families))
    job = cells.load_traffic("layup.m1", families)
    assert sum(flops.step_flops_by_part(cdict, job).values()) == \
        flops.step_flops(cdict, job)


@pytest.mark.parametrize("family,changes,match", [
    # a size of the program that the dense reference does not read
    ("dense", {"num_experts": 4}, r"nothing reads \['num_experts'\].*"
     "the dense reference does not read it"),
    # a key that is no size of the program
    ("dense", {"matmul_precision": "highest"},
     r"nothing reads \['matmul_precision'\].*the program has no such size"),
    # a size the family's reference reads, left unstated
    ("tinymoe", {"num_experts": 4}, r"does not state \['experts_per_token'\]"),
    ("dense", {"d_ff": None}, r"does not state \['d_ff'\]"),
    # a reference that reads a size the program does not have
    ("tinybad", {"num_routed_experts": 4},
     r"the tinybad reference reads \['num_routed_experts'\], which the "
     "program has no size for"),
])
def test_config_is_refused(families, family, changes, match):
    _write_config(families, "tiny-x", family=family, **changes)
    with pytest.raises(ValueError, match=match):
        cells.config_dict("tiny-x", families)
    with pytest.raises(ValueError, match=match):
        cells.load_config("tiny-x", families)


def test_every_declared_size_is_a_model_config_field():
    from repro.configs import ModelConfig
    from chipbench.reference import dense
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    assert set(dense.SIZES) <= fields
    assert not set(dense.SIZES) & set(cells.RECORD_KEYS)
