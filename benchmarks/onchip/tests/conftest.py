"""Shared set-up of the benchmark's CPU tests: the benchmark's modules on
the path, and a smoke-width copy of a cell (tiny widths, two layers,
batch 2 x seq 16) that runs through `run_training` in seconds."""
import sys
from pathlib import Path

import pytest

BENCH_DIR = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH_DIR), str(BENCH_DIR.parents[1] / "src")]

TINY = {"family": "dense", "num_layers": 2, "d_model": 64, "num_heads": 4,
        "num_kv_heads": 2, "d_ff": 128, "vocab_size": 500, "head_dim": 16,
        "qkv_bias": True, "norm": "rmsnorm", "norm_eps": 1e-5,
        "rope_theta": 10000.0, "rope_kind": "rope", "tie_embeddings": True,
        "act": "swiglu", "dtype": "bfloat16"}

# limits for the smoke width, between its sound readings (loss_gap 3e-5,
# grad_gap 2e-3, change_gap 1e-3..8e-3, hist_moves 0, rms_gap and mean_gap
# 8e-4..3.5e-3, extreme_gap 4e-3..0.033) and the control's and faults'
# (change_gap 0.13 and up, grad_gap 0.09 and up; half_tensor rms_gap
# 0.047 and up, mean_gap 0.038 and up, extreme_gap 0.12 and up)
SMOKE_LIMITS = {"loss_gap": 2e-3, "grad_gap": 0.03, "change_gap": 0.05,
                "rms_gap": 0.02, "mean_gap": 0.02, "extreme_gap": 0.08,
                "scalar_gap": 0.02,
                "hist_moves": 4}


def smoke_run(mix_name, seed=7, control=False, seconds=0.2, seq_len=16):
    import time
    import harness
    import traffic
    cfg = harness.config("qwen2-0.5b")
    cfg["model"] = dict(TINY)
    mix = traffic.load(mix_name)
    mix.update(batch=2, seq_len=seq_len)
    return harness.run_cell(cfg, mix, SMOKE_LIMITS, cell=f"smoke.{mix_name}",
                            seed=seed, seconds=seconds, trace=False,
                            t_start=time.perf_counter(), control=control)


@pytest.fixture
def smoke():
    return smoke_run


@pytest.fixture
def tiny_model():
    return dict(TINY)
