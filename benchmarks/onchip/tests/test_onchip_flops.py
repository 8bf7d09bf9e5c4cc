"""Model FLOPs per token and stats bytes per step against hand
arithmetic, through the architecture module each configuration names, and
the configuration files against the program's schema."""
import pytest

import flops
import harness
import traffic
from weights import padded_vocab


def _model(name):
    return harness.config(name)["model"]


def _arch(name):
    return harness.arch_module(harness.config(name))


def test_flops_per_token_of_both_configurations():
    q, dense = _model("qwen2-0.5b"), _arch("qwen2-0.5b")
    # per layer: q,o 896x896, k,v 896x128, mlp 3 x 896x4864; head 896x151936
    qwen_params = 24 * (2 * 896 * 896 + 2 * 896 * 128 + 3 * 896 * 4864) \
        + 896 * 151936
    assert dense.matmul_params(q) == qwen_params == 493961216
    assert dense.flops_per_token(q, 1024) == \
        6 * qwen_params + 12 * 24 * 1024 * 896
    assert dense.flops_per_token(q, 1024) == pytest.approx(3.228e9, rel=1e-3)
    p, dense = _model("phi4-mini-3.8b-stage4"), _arch("phi4-mini-3.8b-stage4")
    phi_params = 4 * (2 * 3072 * 3072 + 2 * 3072 * 1024 + 3 * 3072 * 8192) \
        + 3072 * 25008
    assert dense.matmul_params(p) == phi_params == 479477760
    assert dense.flops_per_token(p, 1024) == pytest.approx(3.028e9, rel=1e-3)


def test_stats_bytes_of_the_health_mix_match_the_sites():
    q, dense = _model("qwen2-0.5b"), _arch("qwen2-0.5b")
    act = 4 * 1024 * 896 * 2                  # bf16 [4, 1024, 896]
    logits = 4 * 1024 * 152064 * 4            # f32 over the padded vocab
    want = 4 * 24 * act + act + logits + 4 + 4
    assert flops.stats_bytes_per_step(dense, q, traffic.load("health")) \
        == want
    assert flops.stats_bytes_per_step(dense, q, traffic.load("idle")) == 0


def test_configuration_files_are_the_program_s_configs():
    from repro.configs.base import ModelConfig
    for name, params in (("qwen2-0.5b", 494031872),
                         ("phi4-mini-3.8b-stage4", None)):
        model = _model(name)
        mcfg = ModelConfig(name=name, **model)
        assert padded_vocab(model) == mcfg.padded_vocab
        if params:
            assert mcfg.param_counts()["total"] == params
