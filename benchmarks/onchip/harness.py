"""One run of one cell: the probed train step driven through the program's
own entry point, `repro.launch.train.run_training`.

The run registers the cell's configuration in the program's registry,
builds the probe runtime from the traffic mix, and feeds the seed to the
weights and the batches by wrapping `init_train_state` and
`SyntheticDataset`, which `run_training` imports when it is called. The
first `WARMUP_STEPS` steps are set-up: the first compiles, the first
three are the ones the reference follows. The window then runs for the
given seconds and ends when the step hook raises `WindowClosed`; the
hook keeps the last state it was given.

Afterwards the device's peak memory is read, the program's state is
freed, and the plain reference runs the same three steps so that
`check.compare` can decide `correct`.

What belongs to the architecture (the weights' tree, the reference's
model, FLOPs and probed bytes) comes from the module the configuration
names, resolved by `arch_module` before anything compiles (`arch/`).
"""
from __future__ import annotations

import contextlib
import functools
import gc
import importlib.util
import json
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

import check
import flops
import reference as R
import traffic as T
import xplane
from weights import make_batch

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
ARCH_DIR = BENCH_DIR / "arch"
# the contract of an architecture module (arch/__init__.py)
ARCH_FUNCTIONS = ("check_config", "make_params", "change_norms",
                  "train_steps", "layer_sites", "flops_per_token",
                  "site_bytes")
WARMUP_STEPS = 5
CHECK_STEPS = 3
TRACE_STEPS = 10
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# TrainConfig fields that run_training sets and the configuration states
TRAIN_FIELDS = ("optimizer", "lr", "warmup", "total_steps", "weight_decay",
                "clip_norm", "param_dtype", "compute_dtype", "remat",
                "microbatch")


class WindowClosed(Exception):
    """Raised by the step hook to end `run_training` when the window is over."""


class HarnessError(RuntimeError):
    """The program did not run as the cell states (seed, config, steps)."""


def read_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def benchmark() -> dict:
    return read_json(ROOT / "BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return read_json(BENCH_DIR / "configs" / f"{name}.json")


def limits(cell: str) -> dict:
    return read_json(BENCH_DIR / "limits" / f"{cell}.json")


def metric_reader(name: str):
    """`read(ctx)` of `metrics/<name>.py`."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@functools.lru_cache(maxsize=None)
def _load_arch(path: Path):
    spec = importlib.util.spec_from_file_location(f"arch_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def arch_module(cfg: dict):
    """The architecture module `arch/<cfg["arch"]>.py`, with every function
    of the contract, that computes the configuration's model."""
    name = cfg.get("arch")
    path = ARCH_DIR / f"{name}.py"
    if not isinstance(name, str) or not path.is_file():
        raise HarnessError(f"the configuration names no architecture module "
                           f"in {ARCH_DIR}: \"arch\" is {name!r}")
    mod = _load_arch(path)
    missing = [f for f in ARCH_FUNCTIONS if not callable(getattr(mod, f, None))]
    if missing:
        raise HarnessError(f"architecture module {path} lacks "
                           f"{', '.join(missing)}")
    mod.check_config(cfg["model"])
    return mod


class _CompileCounter:
    """Backend compiles seen while `armed`, across every run of the process."""
    registered = None

    def __init__(self):
        self.n = 0
        self.armed = False

    @classmethod
    def get(cls) -> "_CompileCounter":
        if cls.registered is None:
            import jax
            cls.registered = cls()
            jax.monitoring.register_event_duration_secs_listener(
                cls.registered._on_event)
        return cls.registered

    def _on_event(self, event, duration, **_):
        if self.armed and event == COMPILE_EVENT:
            self.n += 1


@contextlib.contextmanager
def _patched(module, name, value):
    old = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, old)


def _timed(spans: list, name: str, fn):
    """`fn` with its host time appended to `spans` and a trace span."""
    import jax

    def wrapper(*a, **k):
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            out = fn(*a, **k)
        spans.append((name, t, time.perf_counter()))
        return out
    return wrapper


def run_cell(cfg: dict, traffic: dict, lim: dict, *, cell: str, seed: int,
             seconds: float, trace: bool, t_start: float,
             per_layer: tuple = (), control: bool = False) -> dict:
    """Run one cell and return the result line's content (without
    `device`). `control` starts from bfloat16 parameters, the program's
    `param_dtype="bfloat16"` path, for the control of `correct`."""
    import jax
    import jax.numpy as jnp
    from repro.configs import registry
    from repro.configs.base import ModelConfig
    from repro.data import pipeline
    from repro.launch.train import run_training
    from repro.optim import make_optimizer
    from repro.train import train_step as TS

    arch = arch_module(cfg)
    model, train = cfg["model"], cfg["train"]
    B, S = traffic["batch"], traffic["seq_len"]
    # the work a step does, counted before anything compiles
    work = {"flops_per_token": arch.flops_per_token(model, S),
            "stats_bytes_per_step": flops.stats_bytes_per_step(arch, model,
                                                               traffic)}
    arch_id = f"onchip:{cell}"
    registry.ARCHS[arch_id] = mcfg = ModelConfig(name=arch_id, **model)
    param_dtype = "bfloat16" if control else train["param_dtype"]
    calls = {"init": 0, "data": 0, "fetched": []}
    spans: list = []
    compiles = _CompileCounter.get()

    orig_init = TS.init_train_state

    def seeded_init(key, cfg_, tcfg, runtime=None):
        calls["init"] += 1
        stated = {k: getattr(tcfg, k) for k in TRAIN_FIELDS}
        want = {k: train[k] for k in stated}
        if cfg_ != mcfg or stated != want:
            raise HarnessError(f"run_training built {cfg_} / {stated}; the "
                               f"cell states {mcfg} / {want}")
        shape = jax.eval_shape(lambda: orig_init(key, cfg_, tcfg, runtime))
        params = arch.make_params(seed, model, param_dtype)
        opt_init, _ = make_optimizer(tcfg.optimizer)
        state = {"params": params, "opt": jax.jit(opt_init)(params),
                 "step": jnp.zeros((), jnp.int32),
                 "maps": runtime.init_device_maps() if runtime else {}}
        got = jax.tree.map(lambda a: (a.shape, a.dtype if not control
                                      else None), state)
        exp = jax.tree.map(lambda a: (a.shape, a.dtype if not control
                                      else None), shape)
        if got != exp:
            raise HarnessError(f"the seeded state does not have the shape "
                               f"of the program's: {got} != {exp}")
        return state

    class SeededDataset(pipeline.SyntheticDataset):
        def __init__(self, cfg_, shape, tcfg, seed_=0, runtime=None, **kw):
            super().__init__(cfg_, shape, tcfg, seed, runtime, **kw)
            calls["data"] += 1
            self.next = _timed(spans, "bench.data", self.next)

        def _make(self, step):
            calls["fetched"].append(step)
            return make_batch(seed, step, self.shape.global_batch,
                              self.shape.seq_len, self.cfg.vocab_size)

    gc_pauses: list = []
    gc_start: list = []

    def on_gc(phase, info):
        # collections inside the window, to tell a pause of the host apart
        if not compiles.armed:
            return
        if phase == "start":
            gc_start[:] = [time.perf_counter()]
        elif gc_start:
            gc_pauses.append((gc_start[0], time.perf_counter(),
                              info["generation"]))

    rt = T.build_runtime(traffic)
    for name in ("publish", "poll_control", "sync_live_table"):
        setattr(rt, name, _timed(spans, f"bench.{name}", getattr(rt, name)))
    b1 = train["b1"]
    rec = {"loss": [], "vetoed": [], "ends": [], "steps": 0}
    trace_dir = tempfile.mkdtemp(prefix="onchip-trace-") if trace else None

    def on_step(step, state, metrics):
        now = time.perf_counter()
        rec["steps"] = step
        rec["vetoed"].append(int(metrics["vetoed"]))
        rec["loss"].append(float(metrics["loss"]))
        # the norms are reduced on the device in one call each, so the
        # check holds no second parameter tree beside the state
        if step == 1:
            # AdamW's first moment after one step is (1 - b1) g1
            rec["grad1"] = {k: v / (1 - b1) for k, v in
                            R.leaf_norms(state["opt"]["m"]).items()}
        if step == CHECK_STEPS:
            rec["change"] = R.named(arch.change_norms(
                state["params"], seed, model, param_dtype))
            rec["maps_check"] = _host_maps(state["maps"])
        if step < WARMUP_STEPS:
            return
        if step == WARMUP_STEPS:
            jax.block_until_ready(state)
            rec["ends"].append(time.perf_counter())
            rec["first_window_step"] = step + 1
            compiles.n, compiles.armed = 0, True
            spans.clear()
            if trace:
                # a span made before the trace starts is never recorded
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0        # spans, not every call
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                rec["window_span"] = jax.profiler.TraceAnnotation(
                    "bench.window")
                rec["window_span"].__enter__()
            return
        rec["ends"].append(now)
        n = step - WARMUP_STEPS
        if trace and n == TRACE_STEPS:
            jax.block_until_ready(state)
            rec["trace_steps"] = n
            rec["trace_end"] = time.perf_counter()
            rec.pop("window_span").__exit__(None, None, None)
            jax.profiler.stop_trace()
        if now - rec["ends"][0] >= seconds and (not trace
                                               or n >= TRACE_STEPS):
            jax.block_until_ready(state)
            rec["ends"][-1] = time.perf_counter()
            compiles.armed = False
            rec["state"] = state
            raise WindowClosed

    shm_dir = tempfile.mkdtemp(prefix="onchip-shm-") \
        if traffic.get("shm") else None
    gc.callbacks.append(on_gc)
    try:
        with _patched(TS, "init_train_state", seeded_init), \
                _patched(pipeline, "SyntheticDataset", SeededDataset):
            try:
                run_training(arch_id, steps=train["total_steps"], smoke=False,
                             runtime=rt, shm_dir=shm_dir,
                             probe_mode=traffic["probe_mode"], seq_len=S,
                             batch=B, microbatch=train["microbatch"],
                             log_every=0, on_step=on_step)
                raise HarnessError("run_training returned before the window "
                                   "closed")
            except WindowClosed:
                pass
        if calls["init"] != 1 or calls["data"] != 1:
            raise HarnessError(f"run_training made {calls['init']} states "
                               f"and {calls['data']} datasets, not one each "
                               f"from the seed")
        if calls["fetched"] != list(range(len(calls["fetched"]))) or \
                len(calls["fetched"]) != rec["steps"]:
            raise HarnessError(f"batches fetched out of order or skipped: "
                               f"{calls['fetched'][:8]}... for "
                               f"{rec['steps']} steps")

        devices = jax.local_devices()[:1]
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        ends = np.asarray(rec["ends"])
        steps_in_window = len(ends) - 1
        window_s = float(ends[-1] - ends[0])
        first = rec["first_window_step"] - 1
        window_vetoed = sum(rec["vetoed"][first:])
        window_nonfinite = sum(not np.isfinite(x) for x in rec["loss"][first:])
        out = {
            "attempted": steps_in_window,
            "failed": window_vetoed + window_nonfinite,
            "window_compiles": compiles.n,
            "end_to_end": {
                "setup_s": float(ends[0] - t_start),
                "tokens_per_s": steps_in_window * B * S / window_s,
                "step_ms_p90": float(np.percentile(np.diff(ends) * 1e3, 90)),
                "peak_hbm_gb": peak / 1e9,
            },
            "memory_peak_bytes": int(peak),
            "host": _host_pauses(ends, spans, gc_pauses),
        }
        final_maps = _host_maps(rec["state"]["maps"])
        total_steps = rec["steps"]
        del rec["state"]
        gc.collect()
        out["bytes_in_use_after_window"] = max(
            (d.memory_stats() or {}).get("bytes_in_use", 0) for d in devices)

        if trace:
            out.update(_reduce_trace(trace_dir, rec, spans, model, traffic,
                                     work, per_layer,
                                     steps=rec["trace_steps"],
                                     chips=len(devices)))

        batches = [make_batch(seed, s, B, S, model["vocab_size"])
                   for s in range(CHECK_STEPS)]
        ref = arch.train_steps(seed, model, train, batches)
        # a reference that leaves out a site its module declares is the
        # module's fault, and is named so, not read as the program's
        stated = set(ref["sites"][0])
        declared = set(arch.layer_sites(model)) | {
            (s, R.POINT, 0) for s in R.SCALAR_SITES}
        if stated != declared:
            raise HarnessError(f"the reference states the sites "
                               f"{sorted(stated ^ declared)} that its "
                               f"layer_sites does not, or the reverse")
        from repro.core import events as E
        prog = {"loss": rec["loss"][:CHECK_STEPS],
                "vetoed": rec["vetoed"][:CHECK_STEPS],
                "grad1": rec["grad1"], "change": rec["change"],
                "maps": rec.get("maps_check", {}),
                "final_maps": final_maps, "total_steps": total_steps,
                "site_name": E.SITES.name_of}
        out["checks"] = check.compare(prog, ref, traffic, lim)
        out["correct"] = all(c["value"] <= c["limit"]
                             for c in out["checks"].values())
        return out
    finally:
        compiles.armed = False
        gc.callbacks.remove(on_gc)
        for d in (shm_dir, trace_dir):
            if d:
                shutil.rmtree(d, ignore_errors=True)


def _host_pauses(ends, spans, gc_pauses) -> dict:
    """Where the host was in the window's slowest step: its length beside
    the median's, the benchmark's spans inside it, and the garbage
    collections of the window."""
    steps = np.diff(ends)
    i = int(np.argmax(steps))
    lo, hi = ends[i], ends[i + 1]
    inside: dict = {}
    for name, t0, t1 in spans:
        if lo <= t0 < hi:
            inside[name] = inside.get(name, 0.0) + (t1 - t0) * 1e3
    return {"slowest_step_ms": float(steps[i] * 1e3),
            "median_step_ms": float(np.median(steps) * 1e3),
            "slowest_step_spans_ms": inside,
            "gc_count": len(gc_pauses),
            "gc_max_ms": max(((b - a) * 1e3 for a, b, _ in gc_pauses),
                             default=0.0),
            "gc_in_slowest_step_ms": sum((b - a) * 1e3 for a, b, _ in
                                         gc_pauses if lo <= a < hi)}


def _host_maps(maps: dict) -> dict:
    import jax
    return {k: jax.tree.map(np.asarray, v) for k, v in maps.items()
            if not k.startswith("__")}


def _reduce_trace(trace_dir, rec, spans, model, traffic, work, per_layer, *,
                  steps, chips) -> dict:
    """Device busy time, the breakdown and the per-layer metrics of the
    traced steps."""
    from peaks import peaks
    import jax
    profile = xplane.load(trace_dir)
    windows = [s for s in xplane.host_spans(profile) if s[0] == "bench.window"]
    if not windows:
        raise HarnessError("the trace holds no bench.window span")
    _, lo, hi = windows[0]
    ops = xplane.device_ops(profile)
    if not ops:
        raise HarnessError("the trace holds no device operations")
    busy = [xplane.busy_ns([(o[1], o[2]) for o in dev], lo, hi)
            for dev in ops.values()]
    window_ops = [o for dev in ops.values() for o in dev
                  if xplane.overlap(o[1], o[2], lo, hi) > 0]
    host = [s for s in xplane.host_spans(profile) if s[0] != "bench.window"]
    window_s = (hi - lo) / 1e9
    ctx = {
        "model": model, "traffic": traffic, "chips": chips, "steps": steps,
        "window_s": window_s, "busy_s": float(np.mean(busy)) / 1e9,
        "tokens_per_step": traffic["batch"] * traffic["seq_len"],
        **work,
        "peaks": peaks(jax.devices()[0].device_kind),
        "ops": window_ops, "host_spans": host,
        "spans": [s for s in spans if s[1] >= rec["ends"][0]],
    }
    per = {}
    for m in per_layer:
        value = metric_reader(m["name"])(ctx)
        if value is not None:
            per[m["name"]] = {"value": value, "unit": m["unit"]}
    return {"per_layer": per, "busy_s": ctx["busy_s"], "window_s": window_s,
            "breakdown": _breakdown(window_ops, host, ops, lo, hi)}


def _breakdown(window_ops, host, ops, lo, hi) -> dict:
    """The ten device operations that took most time in the window, and
    the ten longest idle gaps of the first device, each named by the
    benchmark span the host was in for most of it (`loop`: none)."""
    by_name: dict = {}
    for text, s, e, _ in window_ops:
        name = xplane.op_name(text)
        by_name[name] = by_name.get(name, 0.0) + \
            xplane.overlap(s, e, lo, hi) / 1e9
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    dev0 = next(iter(ops.values()))
    gaps = xplane.gaps([(o[1], o[2]) for o in dev0], lo, hi)
    named = []
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        best = max(host, key=lambda s: xplane.overlap(s[1], s[2], g0, g1),
                   default=None)
        name = best[0] if best and xplane.overlap(best[1], best[2], g0, g1) \
            > 0 else "loop"
        named.append([name, (g1 - g0) / 1e9])
    return {"device_ops": [[n, v] for n, v in top], "idle_gaps": named}
