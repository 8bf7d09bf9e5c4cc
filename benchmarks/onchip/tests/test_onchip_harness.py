"""Each traffic mix through `run_training` at smoke width: the events of
every step land in the maps, the run is `correct`, the seed decides the
weights and the batches, and without a TPU the command prints nothing."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import harness
from weights import make_batch


@pytest.mark.parametrize("mix", ["health", "idle"])
def test_mix_runs_through_run_training(smoke, mix):
    out = smoke(mix)
    checks = out["checks"]
    assert out["correct"], checks
    assert out["attempted"] >= 1 and out["failed"] == 0
    if mix == "idle":
        assert set(checks) == {"loss_gap", "grad_gap", "change_gap"}
    else:
        # every count and histogram total of every step, exactly
        assert checks["count_diff"]["value"] == 0
        assert checks["window_count_diff"]["value"] == 0


def test_the_seed_decides_weights_and_batches(tiny_model):
    arch = harness.arch_module({"arch": "dense", "model": tiny_model})
    a, b, c = (arch.make_params(s, tiny_model) for s in (5, 5, 2**31 + 5))
    leaves = [np.asarray(x) for x in
              (a["embed"]["embedding"], b["embed"]["embedding"],
               c["embed"]["embedding"])]
    np.testing.assert_array_equal(leaves[0], leaves[1])
    assert not np.array_equal(leaves[0], leaves[2])
    x, y, z = (make_batch(s, 0, 2, 16, 500)["tokens"]
               for s in (5, 5, 2**31 + 5))
    np.testing.assert_array_equal(x, y)
    assert not np.array_equal(x, z)
    assert not np.array_equal(make_batch(5, 0, 2, 16, 500)["tokens"],
                              make_batch(5, 1, 2, 16, 500)["tokens"])


def test_one_seed_repeats_the_run(smoke):
    a, b = smoke("idle", seed=11), smoke("idle", seed=11)
    assert a["checks"] == b["checks"]
    c = smoke("idle", seed=12)
    assert c["checks"]["loss_gap"] != a["checks"]["loss_gap"]


def test_without_a_tpu_the_command_exits_non_zero_and_prints_nothing():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(harness.BENCH_DIR / "run.py"), "--workload",
         "qwen2-0.5b.health", "--seed", "3000000019", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=harness.ROOT, timeout=300)
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
