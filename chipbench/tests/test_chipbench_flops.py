"""Operation and parameter counts of ``chipbench/flops/dense.py`` against
counts made by hand for gpt2-medium."""
import json
import os

import pytest

from chipbench.flops import dense

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")
TRAFFIC = os.path.join(os.path.dirname(HERE), "traffic")


def _load(d, name):
    with open(os.path.join(d, f"{name}.json")) as f:
        return json.load(f)


@pytest.fixture
def gpt2():
    return _load(CONFIGS, "gpt2-medium")


def test_gpt2_medium_params(gpt2):
    # 50257*1024 tied embedding + 24 * (2 norms + 4*1024^2 + 3*1024*4096)
    # + the final norm
    assert dense.params(gpt2) == 454_166_528


def test_gpt2_medium_forward_flops_per_token(gpt2):
    f = dense.forward_flops_per_token(gpt2, 1024)
    assert f["dense"] == 24 * 2 * (4 * 1024 * 1024 + 3 * 1024 * 4096)
    # QK^T and PV over the causal half: 2 products * 2 flops * 1024 dims
    # * 512 keys on average, per layer
    assert f["attention"] == 24 * 2 * 2 * 1024 * 512
    assert f["head"] == 2 * 1024 * 50257


@pytest.mark.parametrize("traffic,trained,fwd_tokens,bwd_tokens", [
    ("layup-r2d1.m1.b8s1024", 4096, 8192, 4096),
    ("layup-r2d1.m4.b8s1024", 4 * 4096, 4 * 8192, 4 * 4096),
    ("ddp.m1.b8s1024", 8192, 8192, 8192),
])
def test_gpt2_medium_step_flops(gpt2, traffic, trained, fwd_tokens,
                                bwd_tokens):
    job = _load(TRAFFIC, traffic)
    per_token = sum(dense.forward_flops_per_token(gpt2, 1024).values())
    assert dense.trained_tokens_per_step(job) == trained
    want = per_token * fwd_tokens + 2 * per_token * bwd_tokens
    assert dense.step_flops(gpt2, job) == pytest.approx(want, rel=1e-12)


def test_gpt2_medium_layup_step_is_about_15_7_tflop(gpt2):
    job = _load(TRAFFIC, "layup-r2d1.m1.b8s1024")
    assert dense.step_flops(gpt2, job) == pytest.approx(15.70e12, rel=2e-3)


def test_untied_head_adds_its_matrix():
    c = _load(CONFIGS, "stablelm-1.6b")
    tied = dict(c, tie_embeddings=True)
    assert dense.params(c) - dense.params(tied) == 2048 * 100352


@pytest.mark.parametrize("config,traffic,share", [
    ("gpt2-medium", "layup-r2d1.m1.b8s1024", 0.0525),
    ("gpt2-medium", "layup-r2d1.m4.b8s1024", 0.0525),
    ("gpt2-medium", "ddp.m1.b8s1024", 0.0525),
    ("stablelm-1.6b", "layup-r2d1.m1.b2s4096", 0.0890),
])
def test_step_flops_by_part_sum_to_step_flops(config, traffic, share):
    c, job = _load(CONFIGS, config), _load(TRAFFIC, traffic)
    parts = dense.step_flops_by_part(c, job)
    assert set(parts) == {"dense", "attention", "head"}
    assert sum(parts.values()) == pytest.approx(dense.step_flops(c, job),
                                                rel=1e-15)
    assert parts["attention"] / sum(parts.values()) == pytest.approx(
        share, abs=5e-4)


@pytest.mark.parametrize("config,traffic,want", [
    # q, k, v read and o written over the 8192 forward tokens, q, k, v, o
    # and do read and dq, dk, dv written over the 4096 backward tokens;
    # 1024 wide each, f32, 24 layers
    ("gpt2-medium", "layup-r2d1.m1.b8s1024",
     24 * 4 * 1024 * (8192 * 4 + 4096 * 8)),
    ("gpt2-medium", "ddp.m1.b8s1024", 24 * 4 * 1024 * 8192 * 12),
    # 2048 wide, bf16, 6 layers
    ("stablelm-1.6b", "layup-r2d1.m1.b2s4096",
     6 * 2 * 2048 * (8192 * 4 + 4096 * 8)),
])
def test_attention_bytes_by_hand(config, traffic, want):
    c, job = _load(CONFIGS, config), _load(TRAFFIC, traffic)
    assert dense.step_bytes_by_part(c, job) == {"attention": want}
