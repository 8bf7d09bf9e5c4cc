"""Bring-up smoke run of the probed training path on one TPU.

    python chip_smoke.py

One process, no children. It drives `repro.launch.train.run_training` for
a few steps at the full published width of qwen2-0.5b (24 layers, d 896,
vocab 151936; batch 4 x seq 1024, AdamW, fused probe lane, random weights
from a fixed seed) with the per-layer health probe of examples/train_e2e.py
attached at `uprobe:block`: an ARRAY of hits per layer and a LOG2HIST of
the block input's rms. It checks

  * the on-chip stats kernel against the jnp reference (kernels/ref.py) on
    a bf16 activation-sized tensor and a ragged f32 one, NaN/Inf exactly;
  * every layer hit once per step, and steps x layers histogram samples;
  * every loss finite;
  * the Pallas kernel (`tpu_custom_call`) inside the compiled step.

It prints the train step's compile seconds, steady step seconds (host clock between step
ends, each after `block_until_ready`), the device's `peak_bytes_in_use`
and the kernel implementation. Without a TPU it exits 1 and prints no
result. The last line of a passing run is one JSON object naming the
device. Any failed check raises, so the exit code is non-zero.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                     # noqa: E402
import jax.numpy as jnp        # noqa: E402
import numpy as np             # noqa: E402

ARCH = "qwen2-0.5b"
STEPS = 5
BATCH, SEQ = 4, 1024
SEED = 0

# examples/train_e2e.py's layer-health probe
PROG = """
    mov r9, r1                   ; save ctx across helper calls
    ldxdw r6, [r1+ctx:layer]
    stxdw [r10-8], r6
    lddw r1, map:layer_hits
    mov r2, r10
    add r2, -8
    mov r3, 1
    call map_fetch_add
    ldxdw r2, [r9+ctx:rms]
    lddw r1, map:act_hist
    call hist_add
    mov r0, 0
    exit
"""

# f32 sums of ~4M terms in two different orders: the count and extrema are
# exact, the moments agree to a relative 1e-4 of the rms
MOMENT_RTOL = 1e-4

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")
    print(f"check ok: {what}")


def check_kernel(ops, key, shape, dtype) -> None:
    """On-chip ops.tensor_stats against the reference, on device data with
    NaN and +-Inf planted at fixed positions."""
    x = jax.random.normal(key, shape, jnp.float32) * 3.0
    flat = x.reshape(-1)
    n = flat.size
    flat = flat.at[jnp.array([1, n // 3, n - 1])].set(jnp.nan)
    flat = flat.at[jnp.array([2, n // 2])].set(jnp.inf)
    flat = flat.at[n // 5].set(-jnp.inf)
    x = flat.reshape(shape).astype(dtype)
    got = {k: np.asarray(v) for k, v in ops.tensor_stats(x).items()}
    want = {k: np.asarray(v) for k, v in ops.tensor_stats(x, impl="ref").items()}
    name = f"tensor_stats {dtype.__name__}{list(shape)}"
    print(f"{name}: kernel {got}")
    print(f"{name}: ref    {want}")
    check(int(got["nan_cnt"]) == int(want["nan_cnt"]) == 3
          and int(got["inf_cnt"]) == int(want["inf_cnt"]) == 3,
          f"{name} NaN/Inf counts equal the reference")
    check(all(got[k] == want[k] for k in ("min", "max", "absmax")),
          f"{name} min/max/absmax equal the reference")
    tol = MOMENT_RTOL * float(want["rms"])
    check(abs(float(got["mean"]) - float(want["mean"])) <= tol
          and abs(float(got["rms"]) - float(want["rms"])) <= tol,
          f"{name} mean/rms within {MOMENT_RTOL} x rms of the reference")


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1

    from repro.jaxenv import use_compile_cache
    print(f"compile cache: {use_compile_cache()}")
    from repro.configs import registry
    from repro.configs.base import ShapeConfig, TrainConfig
    from repro.core import maps as M
    from repro.core.runtime import BpftimeRuntime
    from repro.data.pipeline import SyntheticDataset
    from repro.kernels import ops
    from repro.launch.train import run_training
    from repro.train.train_step import make_train_step

    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    impl = ops.default_impl()
    print(f"kernel implementation: {impl}")
    check(impl == "pallas", "the chip runs the Pallas stats kernel")

    # ---- phase 1: the stats kernel against its reference
    k1, k2 = jax.random.split(jax.random.PRNGKey(SEED))
    check_kernel(ops, k1, (4, 1024, 896), jnp.bfloat16)
    check_kernel(ops, k2, (3, 37, 129), jnp.float32)

    # ---- phase 2: the probed train step through the normal entry point
    cfg = registry.get(ARCH)
    rt = BpftimeRuntime()
    pid = rt.load_asm("watch", PROG, [
        M.MapSpec("layer_hits", M.MapKind.ARRAY, max_entries=64),
        M.MapSpec("act_hist", M.MapKind.LOG2HIST)])
    rt.attach(pid, "uprobe:block")

    compile_s = {"train_step": 0.0, "other": 0.0}

    def on_compile_event(event, duration, fun_name="", **_):
        if event in COMPILE_EVENTS:
            compile_s["train_step" if "train_step" in fun_name
                      else "other"] += duration

    jax.monitoring.register_event_duration_secs_listener(on_compile_event)
    cache_hits = []
    jax.monitoring.register_event_listener(
        lambda event, **_: cache_hits.append(event) if event == CACHE_HIT
        else None)
    ends = []

    def on_step(step, state, metrics):
        jax.block_until_ready(state)
        ends.append(time.perf_counter())

    t0 = time.perf_counter()
    state, hist = run_training(
        ARCH, steps=STEPS, smoke=False, runtime=rt, probe_mode="fused",
        seq_len=SEQ, batch=BATCH, log_every=1, on_step=on_step)
    steady = np.diff(ends)
    print(f"params: {cfg.param_counts()['total']}")
    print(f"run_training seconds (init + compile + {STEPS} steps): "
          f"{ends[-1] - t0}")
    print(f"train step compile seconds (trace + lower + backend): "
          f"{compile_s['train_step']} (persistent cache hits in the run: "
          f"{len(cache_hits)})")
    print(f"other compile seconds (init, eager ops): {compile_s['other']}")
    print(f"first step seconds (with compile): {ends[0] - t0}")
    print(f"steady step seconds: {steady.tolist()} "
          f"median {float(np.median(steady))}")
    print(f"peak_bytes_in_use: {dev.memory_stats()['peak_bytes_in_use']}")

    losses = [h["loss"] for h in hist]
    print(f"losses: {losses}")
    check(len(losses) == STEPS and all(np.isfinite(losses)),
          f"{STEPS} finite losses")
    hits = np.asarray(state["maps"]["layer_hits"]["values"])
    bins = np.asarray(state["maps"]["act_hist"]["bins"])
    print(f"layer_hits: {hits.tolist()}")
    print(f"act_hist: {bins.tolist()}")
    L = cfg.num_layers
    check(bool((hits[:L] == STEPS).all()) and int(hits[L:].sum()) == 0,
          f"layer_hits[:{L}] == {STEPS}, no other slot hit")
    check(int(bins.sum()) == L * STEPS,
          f"LOG2HIST holds {L} x {STEPS} samples")

    # ---- phase 3: the kernel is inside the compiled step. The same step
    # program, rebuilt as run_training builds it, comes back from the
    # persistent compile cache when the cache holds run_training's entry.
    tcfg = TrainConfig(microbatch=0, remat=True, warmup=10, total_steps=STEPS)
    batch = SyntheticDataset(cfg, ShapeConfig("driver", SEQ, BATCH, "train"),
                             tcfg).next()
    compile_s.update(train_step=0.0, other=0.0)
    cache_hits.clear()
    t1 = time.perf_counter()
    compiled = jax.jit(make_train_step(cfg, tcfg, rt, probe_mode="fused")) \
        .lower(state, batch).compile()
    print(f"step recompile seconds: {time.perf_counter() - t1} "
          f"(of it compiling {compile_s['train_step']}, "
          f"persistent cache hits {len(cache_hits)})")
    check("tpu_custom_call" in compiled.as_text(),
          "the compiled step contains tpu_custom_call")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
