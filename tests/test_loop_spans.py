"""The training loop's host spans and the step's device name scopes: what
a profiler trace of `run_training` holds, and the `op_name` path of the
collection's and the probe stage's operations in the compiled step."""
import glob
import os

import jax
import pytest

from repro.configs import registry
from repro.configs.base import ShapeConfig, TrainConfig
from repro.core import maps as M
from repro.core.runtime import BpftimeRuntime
from repro.data.pipeline import SyntheticDataset
from repro.launch.train import run_training
from repro.train.train_step import init_train_state, make_train_step

COUNT_BY_LAYER = """
    ldxdw r6, [r1+ctx:layer]
    stxdw [r10-8], r6
    lddw r1, map:ls_hits
    mov r2, r10
    add r2, -8
    mov r3, 1
    call map_fetch_add
    mov r0, 0
    exit
"""
HITS = M.MapSpec("ls_hits", M.MapKind.ARRAY, max_entries=64)
STEPS = 3
# one iteration's spans below train.step, each once (train.hook twice)
LOOP_SPANS = {"train.control": 1, "train.hook": 2, "train.data": 1,
              "train.dispatch": 1, "train.wait": 1, "train.publish": 1,
              "train.on_step": 1}


def _runtime():
    rt = BpftimeRuntime()
    pid = rt.load_asm("ls_hits", COUNT_BY_LAYER, [HITS], "uprobe")
    rt.attach(pid, "uprobe:block", mode="fused", promote=False)
    return rt


def _host_spans(trace_dir):
    """[(name, start_ns, end_ns, args)] of the loop's and publish's spans."""
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    profile = jax.profiler.ProfileData.from_file(files[0])
    out = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)) for ev in line.events
                        if ev.name.startswith(("train.", "publish."))]
    return sorted(out, key=lambda s: s[1])


def _traced_run(tmp_path, runtime):
    trace_dir = str(tmp_path / "trace")
    reads = []

    def on_step(s, state, metrics):
        reads.append(len(metrics))

    jax.profiler.start_trace(trace_dir)
    try:
        run_training("qwen2-0.5b", steps=STEPS, smoke=True, runtime=runtime,
                     shm_dir=str(tmp_path / "shm") if runtime else None,
                     probe_mode="fused", seq_len=16, batch=2, log_every=0,
                     on_step=on_step)
    finally:
        jax.profiler.stop_trace()
    return _host_spans(trace_dir), reads


def _inside(span, outer):
    return outer[1] <= span[1] and span[2] <= outer[2]


def test_each_loop_span_once_per_step_inside_train_step(tmp_path):
    rt = _runtime()
    spans, reads = _traced_run(tmp_path, rt)
    steps = [s for s in spans if s[0] == "train.step"]
    assert [s[3]["step_num"] for s in steps] == [1, 2, 3]
    n_leaves = len(jax.tree.leaves(rt.init_device_maps()))
    for step, n_metrics in zip(steps, reads):
        inner = [s for s in spans if s is not step and _inside(s, step)]
        counts = {}
        for s in inner:
            counts[s[0]] = counts.get(s[0], 0) + 1
        assert counts == {**LOOP_SPANS, "publish.fetch": 1,
                          "publish.write": 1}
        publish = next(s for s in inner if s[0] == "train.publish")
        fetch, write = (next(s for s in inner if s[0] == name)
                        for name in ("publish.fetch", "publish.write"))
        assert _inside(fetch, publish) and _inside(write, publish)
        assert fetch[3]["leaves"] == n_leaves
        assert fetch[3]["bytes"] > 0
        # the while test, the hook's step, the metrics, the step after
        # them and every map leaf publish converts
        assert step[3]["d2h"] == 1 + 1 + n_metrics + 1 + n_leaves
        control = next(s for s in inner if s[0] == "train.control")
        data = next(s for s in inner if s[0] == "train.data")
        assert control[3]["applied"] == 0 and data[3]["vetoed"] == 0
    built = [s[3]["built"] for s in spans if s[0] == "train.dispatch"]
    assert built == [1, 0, 0]            # the first iteration builds


def test_no_runtime_no_publish_spans(tmp_path):
    spans, reads = _traced_run(tmp_path, None)
    names = {s[0] for s in spans}
    assert names == {"train.step", "train.data", "train.dispatch",
                     "train.wait", "train.on_step"}
    steps = [s for s in spans if s[0] == "train.step"]
    assert [s[3]["d2h"] for s in steps] == [1 + n + 1 for n in reads]


@pytest.mark.parametrize("scope", ["probe.collect", "probe.stage.vector"])
def test_compiled_step_carries_the_scopes(scope):
    cfg = registry.smoke("qwen2-0.5b")
    tcfg = TrainConfig(remat=True, warmup=1, total_steps=4)
    rt = _runtime()
    state = init_train_state(jax.random.PRNGKey(0), cfg, tcfg, rt)
    batch = SyntheticDataset(cfg, ShapeConfig("t", 16, 2, "train"),
                             tcfg).next()
    step = jax.jit(make_train_step(cfg, tcfg, rt, probe_mode="fused"))
    hlo = step.lower(state, batch).compile().as_text()
    assert f"/{scope}/" in hlo
