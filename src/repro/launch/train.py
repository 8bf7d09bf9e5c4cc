"""End-to-end training driver: one device, smoke or published widths
(`--no-smoke`); the production mesh is only lowered, by dryrun.py.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --steps 50 [--no-smoke --batch 4 --seq 1024] [--shm /dev/shm/bpftime]

Probes reach a running job through the shm control plane (`--shm`, then
`python -m repro.core.daemon <shm> --attach obj.json --target
uprobe:block`).

Integration points exercised here (the paper's workflow, §3.2):
  * probes attach/detach between steps WITHOUT restarting training —
    attach_epoch changes re-jit the step, state carries over;
  * a shm control plane lets an external daemon inject programs live;
  * per-step syscalls (data fetch / checkpoint / step begin+end) run their
    eBPF hooks; filter programs can veto batches or checkpoints.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
from jax.profiler import StepTraceAnnotation, TraceAnnotation


def run_training(arch: str, *, steps: int = 20, smoke: bool = True,
                 runtime=None, shm_dir: str | None = None,
                 worker_id: str | None = None,
                 worker_group: str | None = None,
                 ckpt_dir: str | None = None, save_every: int = 0,
                 probe_mode: str = "scan", seq_len: int = 64,
                 batch: int = 8, microbatch: int = 0, log_every: int = 10,
                 on_step=None, max_data_skips: int = 1000,
                 cache_dir: str | None = None):
    from repro.configs import registry
    from repro.configs.base import ShapeConfig, TrainConfig
    from repro.data.pipeline import SyntheticDataset
    from repro.train.train_step import init_train_state, make_train_step
    from repro.ckpt import checkpoint as CK

    cfg = registry.smoke(arch) if smoke else registry.get(arch)
    tcfg = TrainConfig(microbatch=microbatch, remat=True, warmup=10,
                       total_steps=steps)
    shape = ShapeConfig("driver", seq_len, batch, "train")
    if runtime is not None and cache_dir:
        # explicit cache dir wins over the <shm>/cache default setup_shm
        # would otherwise join
        runtime.enable_artifact_cache(cache_dir)
    if runtime is not None and shm_dir:
        # worker_id=None keeps the single-process layout; with an id, this
        # trainer joins <shm_dir>/workers/<wid>/ so a fleet daemon can
        # aggregate several trainers into one global map view; worker_group
        # additionally names the node aggregator that folds this trainer in
        # a hierarchical fleet (DESIGN.md §15)
        runtime.setup_shm(shm_dir, worker_id=worker_id, group=worker_group)

    data = SyntheticDataset(cfg, shape, tcfg, runtime=runtime)
    state = init_train_state(jax.random.PRNGKey(0), cfg, tcfg, runtime)

    jit_cache: dict[int, object] = {}

    def build_step():
        return jax.jit(
            make_train_step(cfg, tcfg, runtime, probe_mode=probe_mode))

    def _call_sig(batch_np):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a)),
            (state, batch_np))

    # trace facts the layout fingerprint can't see from the runtime alone:
    # model + batch geometry + schedule length all shape the compiled graph
    aot_key = ("train_step", arch, bool(smoke), seq_len, batch, microbatch,
               probe_mode, steps)

    def get_step_fn(batch_np):
        epoch = runtime.attach_epoch if runtime else 0
        if epoch not in jit_cache:
            # a background-promoted table link pre-compiles the new epoch's
            # step (core/promote.py) — never block the loop on a re-jit
            # that promotion already paid for
            promoted = runtime.take_promoted_step() if runtime else None
            if promoted is None and runtime is not None \
                    and runtime.artifact_cache is not None:
                # fleet cold-join fast path: reuse another worker's AOT
                # executable (or compile-and-store for the next joiner)
                compiled, _hit = runtime.aot_step(
                    build_step, _call_sig(batch_np), extra_key=aot_key)
                jit_cache[epoch] = compiled
            else:
                jit_cache[epoch] = promoted or build_step()
        return jit_cache[epoch]

    def arm_promotion(batch_np):
        """Hand the promotion engine the loop's step builder + the exact
        call signature, so table-lane links injected later (poll_control)
        converge to the fused lane without a foreground compile."""
        if runtime is None or runtime.live is None \
                or runtime._promoter is not None:
            return
        sig = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a)),
            (state, batch_np))
        runtime.enable_promotion(build_step, sig)

    history = []
    t0 = time.time()
    skips = 0          # consecutive vetoed/faulted batches: bounded spin
    n_iter = 0
    s = int(state["step"])
    # host spans (jax.profiler annotations, recorded only while a trace is
    # running): `train.step` holds one iteration, its children the host
    # work between the step's end and the next dispatch. `d2h` counts the
    # times the iteration blocks on a device->host read: the step's
    # metrics, its step counter and the map leaves publish will read are
    # copied in one batch started at dispatch and awaited once, in
    # `train.wait`; publish adds one read only for leaves that batch
    # did not start
    while s < steps:
        n_iter += 1
        with StepTraceAnnotation("train.step",
                                 step_num=n_iter) as step_span:
            if runtime is not None:
                with TraceAnnotation("train.control") as span:
                    applied = runtime.poll_control()   # daemon injection
                    # push any live-table change onto the running compiled
                    # step (no-op unless a live attach/detach happened
                    # since last sync)
                    state["maps"] = runtime.sync_live_table(state["maps"])
                    span.set_metadata(applied=len(applied))
                with TraceAnnotation("train.hook"):
                    runtime.syscalls.invoke("sys_step_begin", [s],
                                            impl=lambda: None)
            with TraceAnnotation("train.data") as span:
                batch_np = data.next()
                span.set_metadata(vetoed=int(batch_np is None))
            if batch_np is None:                 # vetoed/faulted batch
                step_span.set_metadata(d2h=0)
                skips += 1
                if max_data_skips and skips >= max_data_skips:
                    raise RuntimeError(
                        f"data pipeline yielded no batch {skips} times in "
                        f"a row — a filter is vetoing every fetch")
                continue
            skips = 0
            arm_promotion(batch_np)              # no-op after the first
            with TraceAnnotation("train.dispatch") as span:
                n_built = len(jit_cache)
                step_fn = get_step_fn(batch_np)  # re-jits on attach change
                state, metrics = step_fn(state, batch_np)
                reads = (metrics, state["step"],
                         runtime.start_publish(state["maps"])
                         if runtime is not None else [])
                for leaf in jax.tree.leaves(reads[:2]):
                    leaf.copy_to_host_async()
                span.set_metadata(built=len(jit_cache) - n_built)
            with TraceAnnotation("train.wait") as span:
                host_metrics, host_step, _ = jax.device_get(reads)
                history.append({k: float(v)
                                for k, v in host_metrics.items()})
                s = int(host_step)
                span.set_metadata(arrays=len(jax.tree.leaves(reads)))
            d2h = 1
            if runtime is not None:
                with TraceAnnotation("train.publish"):
                    d2h += runtime.publish(state["maps"])
                with TraceAnnotation("train.hook"):
                    runtime.syscalls.invoke(
                        "sys_step_end", [s, int(1e6 * (time.time() - t0))],
                        impl=lambda: None)
            step_span.set_metadata(d2h=d2h)
            if ckpt_dir and save_every and s % save_every == 0:
                with TraceAnnotation("train.ckpt"):
                    CK.save(ckpt_dir, s, state, runtime=runtime,
                            blocking=True)
            if on_step is not None:
                with TraceAnnotation("train.on_step"):
                    on_step(s, state, metrics)
        if log_every and s % log_every == 0:
            print(f"step {s}: loss={history[-1]['loss']:.4f} "
                  f"gnorm={history[-1]['grad_norm']:.3f} "
                  f"({(time.time() - t0) / max(s, 1):.2f}s/step)")
    return state, history


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced widths (default); --no-smoke runs the "
                         "published config")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--shm")
    ap.add_argument("--worker-id",
                    help="join the fleet layout as <shm>/workers/<id>/ "
                         "(multi-trainer aggregation, DESIGN.md §10)")
    ap.add_argument("--worker-group",
                    help="aggregation group: the node aggregator (`node "
                         "run <group>`) that folds this trainer in a "
                         "hierarchical fleet (DESIGN.md §15)")
    ap.add_argument("--ckpt")
    ap.add_argument("--save-every", type=int, default=0)
    ap.add_argument("--cache",
                    help="AOT artifact cache directory (defaults to "
                         "<shm>/cache when --shm is given)")
    args = ap.parse_args(argv)

    from repro.jaxenv import use_compile_cache
    use_compile_cache()
    from repro.core.runtime import BpftimeRuntime
    rt = BpftimeRuntime() if (args.shm or args.cache) else None
    state, hist = run_training(
        args.arch, steps=args.steps, smoke=args.smoke, runtime=rt,
        shm_dir=args.shm, worker_id=args.worker_id,
        worker_group=args.worker_group, ckpt_dir=args.ckpt,
        save_every=args.save_every, batch=args.batch, seq_len=args.seq,
        cache_dir=args.cache)
    print(f"final loss {hist[-1]['loss']:.4f} after {len(hist)} steps")


if __name__ == "__main__":
    main()
