"""The main path compiles for a described TPU v5e, with no chip attached.

The TPU compiler is installed with jax; it refuses here what the chip
would refuse (tiling, memory spaces, 64-bit types inside a kernel). The
topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file. Keep every such compile in this one file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.configs.base import TrainConfig
from repro.core import maps as M
from repro.core.runtime import BpftimeRuntime
from repro.kernels import ops
from repro.kernels import tensor_stats as ts
from repro.train.train_step import abstract_train_state, make_train_step

COUNT_AND_HIST = """
    mov r9, r1
    ldxdw r6, [r1+ctx:layer]
    stxdw [r10-8], r6
    lddw r1, map:tc_hits
    mov r2, r10
    add r2, -8
    mov r3, 1
    call map_fetch_add
    ldxdw r2, [r9+ctx:rms]
    lddw r1, map:tc_hist
    call hist_add
    mov r0, 0
    exit
"""


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("shape,dtype", [
    ((4, 1024, 896), jnp.bfloat16),       # qwen2-0.5b block activation
    ((4, 1024, 4864), jnp.bfloat16),      # qwen2-0.5b ffn hidden
    ((3, 37, 129), jnp.float32),          # ragged: not whole 8x1024 tiles
], ids=["bf16-d_model", "bf16-d_ff", "f32-ragged"])
def test_tensor_stats_kernel_compiles_for_v5e(one_chip, shape, dtype):
    compiled = jax.jit(ts.tensor_stats_pallas).lower(
        _sds(shape, dtype, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_probed_train_step_compiles_for_v5e(one_chip):
    """The smoke-width probed step, with the Pallas stats kernel inside
    value_and_grad and the i64 probe stage on the fused lane."""
    cfg = registry.smoke("qwen2-0.5b")
    tcfg = TrainConfig(remat=True, warmup=10, total_steps=5)
    rt = BpftimeRuntime()
    pid = rt.load_asm("tc_watch", COUNT_AND_HIST, [
        M.MapSpec("tc_hits", M.MapKind.ARRAY, max_entries=64),
        M.MapSpec("tc_hist", M.MapKind.LOG2HIST)])
    rt.attach(pid, "uprobe:block")
    state = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                         abstract_train_state(cfg, tcfg, rt))
    batch = {k: _sds((2, 16), jnp.int32, one_chip)
             for k in ("tokens", "labels")}
    prev = ops.default_impl()
    ops.set_default_impl("pallas")
    try:
        compiled = jax.jit(make_train_step(cfg, tcfg, rt, probe_mode="fused")) \
            .lower(state, batch).compile()
    finally:
        ops.set_default_impl(prev)
    assert "tpu_custom_call" in compiled.as_text()
