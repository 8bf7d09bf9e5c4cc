"""The training loop's host spans and the step's device name scopes: what
a profiler trace of `run_training` holds, the `op_name` path of the
collection's and the probe stage's operations in the compiled step, and
the one batched device->host read a step that the spans count."""
import glob
import os

import jax
import numpy as np
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


def _with_program(rt):
    pid = rt.load_asm("ls_hits", COUNT_BY_LAYER, [HITS], "uprobe")
    rt.attach(pid, "uprobe:block", mode="fused", promote=False)
    return rt


def _runtime():
    return _with_program(BpftimeRuntime())


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
        # every leaf publish reads had its host copy started at dispatch
        assert fetch[3]["prefetched"] == n_leaves
        assert fetch[3]["bytes"] > 0
        # the metrics, the step counter and the map leaves arrive in one
        # batch, awaited once
        wait = next(s for s in inner if s[0] == "train.wait")
        assert wait[3]["arrays"] == n_metrics + 1 + n_leaves
        assert step[3]["d2h"] == 1
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
    assert [s[3]["d2h"] for s in steps] == [1] * STEPS
    waits = [s for s in spans if s[0] == "train.wait"]
    assert [s[3]["arrays"] for s in waits] == [n + 1 for n in reads]


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


class SerialPublish(BpftimeRuntime):
    """The runtime with publish's reads as they were before the batched
    read: no copy started at dispatch, then one blocking copy per leaf."""

    def start_publish(self, map_states):
        return []

    def publish(self, map_states):
        host_states = jax.tree.map(np.asarray, map_states)
        self.syscalls.invoke(
            "sys_shm_publish", [len(host_states)],
            impl=lambda: self.shm.publish_device(host_states))
        return len(jax.tree.leaves(host_states))


def _recorded_run(tmp_path, rt):
    """(published, snapshots, hooks, history, metrics) of a smoke run: the
    bytes of every publish in the order written, the shm snapshot after
    each step, the step hooks' step arguments, the loop's history, and the
    step metrics read one by one in `on_step`."""
    rt.setup_shm(str(tmp_path / "shm"))
    published, snapshots, hooks, metrics = [], [], [], []
    write = rt.shm.publish_device

    def publish_device(states):
        published.append([(name, field, np.asarray(a).tobytes())
                          for name, st in states.items()
                          for field, a in st.items()])
        write(states)

    invoke = rt.syscalls.invoke

    def hook(name, args, **kw):
        if name in ("sys_step_begin", "sys_step_end"):
            hooks.append((name, args[0]))
        return invoke(name, args, **kw)

    def on_step(s, state, step_metrics):
        snapshots.append({spec.name: {
            field: a.tobytes()
            for field, a in rt.shm.snapshot_device(spec.name).items()}
            for spec in rt.map_specs})
        metrics.append({k: float(np.asarray(v))
                        for k, v in step_metrics.items()})

    rt.shm.publish_device = publish_device
    rt.syscalls.invoke = hook
    _, history = run_training(
        "qwen2-0.5b", steps=STEPS, smoke=True, runtime=rt,
        probe_mode="fused", seq_len=16, batch=2, log_every=0,
        on_step=on_step)
    return published, snapshots, hooks, history, metrics


def test_batched_read_publishes_what_the_serial_read_did(tmp_path):
    batched = _recorded_run(tmp_path / "batched", _runtime())
    serial = _recorded_run(tmp_path / "serial", _with_program(SerialPublish()))
    published, snapshots, hooks, history, metrics = batched
    assert len(published) == STEPS and published == serial[0]
    assert snapshots == serial[1]
    assert any(b != a for a, b in zip(snapshots, snapshots[1:]))
    assert hooks == serial[2] == [(name, s) for s in range(STEPS)
                                  for name, s in (("sys_step_begin", s),
                                                  ("sys_step_end", s + 1))]
    assert history == serial[3] == metrics == serial[4]


def test_replaced_leaf_is_published_with_its_new_values(tmp_path):
    """A live-table change swaps the table's leaves after their copies
    started: publish reads the new leaves, and counts only the old ones
    as started."""
    rt = BpftimeRuntime()
    rt.create_map(HITS)
    rt.enable_live_attach(max_programs=4, max_insns=64,
                          arm=("uprobe:block",))
    rt.setup_shm(str(tmp_path / "shm"))
    maps = rt.init_device_maps()
    n_table = len(jax.tree.leaves(maps["__live_table__"]))
    assert len(rt.start_publish(maps)) == len(jax.tree.leaves(maps))
    pid = rt.load_asm("ls_hits", COUNT_BY_LAYER, [HITS], "uprobe")
    rt.attach(pid, "uprobe:block", mode="table", promote=False)
    table = {k: v.copy() for k, v in rt.live.host.items()}
    new = rt.sync_live_table(maps)
    assert new["__live_table__"] is not maps["__live_table__"]
    written = []
    write = rt.shm.publish_device
    rt.shm.publish_device = lambda st: (written.append(st), write(st))

    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        reads = rt.publish(new)
    finally:
        jax.profiler.stop_trace()
    fetch = next(s for s in _host_spans(trace_dir)
                 if s[0] == "publish.fetch")
    n_leaves = len(jax.tree.leaves(new))
    assert fetch[3]["leaves"] == n_leaves
    assert fetch[3]["prefetched"] == n_leaves - n_table < n_leaves
    assert reads == 1
    (host,) = written
    assert table["active"].any()
    for k, v in table.items():
        np.testing.assert_array_equal(host["__live_table__"][k], v)
    np.testing.assert_array_equal(host[HITS.name]["values"],
                                  np.asarray(new[HITS.name]["values"]))
