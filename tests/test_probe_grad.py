"""A probed train step differentiates through the Pallas stats kernel's
call site. Collection sits inside value_and_grad (probed_scan in the layer
stack); JAX cannot differentiate a pallas_call, so the collector stops the
gradient before the stats. Interpret mode runs the kernel body on the CPU
and fails the same way the chip's compile would."""
import jax
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import ShapeConfig, TrainConfig
from repro.core import maps as M
from repro.core.runtime import BpftimeRuntime
from repro.data.pipeline import SyntheticDataset
from repro.kernels import ops
from repro.train.train_step import init_train_state, make_train_step

CFG = registry.smoke("qwen2-0.5b")
TCFG = TrainConfig(remat=True, warmup=2, total_steps=4)
STEPS = 2

PROG = """
    mov r9, r1
    ldxdw r6, [r1+ctx:layer]
    stxdw [r10-8], r6
    lddw r1, map:pg_hits
    mov r2, r10
    add r2, -8
    mov r3, 1
    call map_fetch_add
    ldxdw r2, [r9+ctx:rms]
    lddw r1, map:pg_hist
    call hist_add
    mov r0, 0
    exit
"""


def _run(impl: str, mode: str):
    rt = BpftimeRuntime()
    pid = rt.load_asm("pg_watch", PROG, [
        M.MapSpec("pg_hits", M.MapKind.ARRAY, max_entries=8),
        M.MapSpec("pg_hist", M.MapKind.LOG2HIST)])
    rt.attach(pid, "uprobe:block")
    data = SyntheticDataset(CFG, ShapeConfig("pg", 16, 2, "train"), TCFG)
    state = init_train_state(jax.random.PRNGKey(0), CFG, TCFG, rt)
    prev = ops.default_impl()
    ops.set_default_impl(impl)
    try:
        step = jax.jit(make_train_step(CFG, TCFG, rt, probe_mode=mode))
        metrics = []
        for _ in range(STEPS):
            state, m = step(state, data.next())
            metrics.append({k: np.asarray(v) for k, v in m.items()})
    finally:
        ops.set_default_impl(prev)
    return state, metrics


@pytest.fixture(scope="module")
def reference():
    return _run("ref", "scan")


@pytest.mark.parametrize("mode", ["fused", "vectorized", "scan"])
def test_probed_grad_step_through_pallas_kernel(reference, mode):
    want_state, want_metrics = reference
    state, metrics = _run("pallas_interpret", mode)
    # stats only observe: loss, gradients and the update are the unprobed
    # step's, bit for bit
    for got, want in zip(metrics, want_metrics):
        for k in ("loss", "grad_norm"):
            np.testing.assert_array_equal(got[k], want[k], err_msg=k)
    for a, b in zip(jax.tree.leaves(state["params"]),
                    jax.tree.leaves(want_state["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    hits = np.asarray(state["maps"]["pg_hits"]["values"])
    np.testing.assert_array_equal(
        hits, np.asarray(want_state["maps"]["pg_hits"]["values"]))
    assert hits[:CFG.num_layers].tolist() == [STEPS] * CFG.num_layers
    assert int(np.asarray(state["maps"]["pg_hist"]["bins"]).sum()) \
        == CFG.num_layers * STEPS
