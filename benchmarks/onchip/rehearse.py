"""Compile a cell's probed train step for a described TPU v5e, without the
chip, and print the compiler's memory analysis.

    JAX_PLATFORMS=cpu python3 benchmarks/onchip/rehearse.py <cell> ...

The step is the one `run_training` builds for the cell (configuration,
traffic mix, probe lane), given the shapes of the state and of one batch
on one chip of a described `v5e:2x2` topology, with the Pallas stats
kernel. What the chip's compiler refuses, this refuses; it runs nothing
and measures no time.
"""
from __future__ import annotations

import os
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH_DIR), str(BENCH_DIR.parents[1] / "src")]


def rehearse(cell_name: str) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.configs.base import ModelConfig, TrainConfig
    from repro.kernels import ops
    from repro.train.train_step import abstract_train_state, make_train_step

    import harness
    import traffic as T
    cell = harness.workload(harness.benchmark(), cell_name)
    cfg = harness.config(cell["config"])
    mix = T.load(cell["traffic"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    train = cfg["train"]
    mcfg = ModelConfig(name=cell_name, **cfg["model"])
    tcfg = TrainConfig(**{k: train[k] for k in harness.TRAIN_FIELDS})
    rt = T.build_runtime(mix)
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        abstract_train_state(mcfg, tcfg, rt))
    batch = {k: jax.ShapeDtypeStruct((mix["batch"], mix["seq_len"]),
                                     jnp.int32, sharding=chip)
             for k in ("tokens", "labels")}
    prev = ops.default_impl()
    ops.set_default_impl("pallas")
    try:
        t = time.perf_counter()
        compiled = jax.jit(make_train_step(mcfg, tcfg, rt,
                                           probe_mode=mix["probe_mode"])) \
            .lower(state, batch).compile()
        seconds = time.perf_counter() - t
    finally:
        ops.set_default_impl(prev)
    mem = compiled.memory_analysis()
    return {"cell": cell_name, "compile_s": seconds,
            "kernel": "tpu_custom_call" in compiled.as_text(),
            **{k: getattr(mem, k) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "alias_size_in_bytes",
                "generated_code_size_in_bytes")}}


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    for name in sys.argv[1:]:
        print(rehearse(name), flush=True)
