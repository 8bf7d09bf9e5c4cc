"""The program's spans and scopes read from a trace: finding the run's
trace, its host spans with their counters, device ops by scope with self
time, the five readers of them, the idle gaps named by span, and the
protobuf reader of `op_name`."""
import os
from types import SimpleNamespace as NS

import pytest

import harness
import peaks
import program_trace
import xplane

COLLECT = "jit(train_step)/jvp(train_step)/probe.collect/convert"
# op text -> op_name, as `program_trace.op_names` reads them from a trace
NAMES = {"%convert.1 = f32[] op()": COLLECT,
         "tensor_stats_pallas.3": COLLECT,
         "%while.9 = f32[] op()": "jit(train_step)/while",
         "%fusion.4 = f32[] op()":
             "jit(train_step)/while/body/probe.stage.vector/add",
         "%fusion.5 = f32[] op()":
             "jit(train_step)/probe.stage.combined_scan/while/body/select",
         "%fusion.6 = f32[] op()": "jit(train_step)/dot"}
PROGRAM_READERS = ["loop_host_ms", "host_syncs_per_step", "publish_fetch_ms",
                   "collect_ms", "probe_stage_ms"]


def _ev(name, start, dur, stats=()):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=list(stats))


def _op(name, start, dur):
    return _ev(f"%{name} = f32[] op()", start, dur)


def _traced_profile(program=True):
    """Two whole steps inside the window and a third cut by its end; each
    step's spans below `train.step`, and device ops under the program's
    scopes (`NAMES`), one of them inside a container op. `program=False`:
    the benchmark's window alone, as a program without spans traces."""
    spans = []
    for t, n in ((100, 1), (500, 2), (900, 3)):
        spans += [
            _ev("train.step", t, 400, [("_r", 1), ("step_num", n),
                                       ("d2h", 16)]),
            _ev("train.wait", t, 150),
            _ev("train.publish", t + 150, 100),
            _ev("publish.fetch", t + 150 + 10, 60, [("leaves", 9),
                                                    ("bytes", 2048)]),
            _ev("publish.write", t + 150 + 70, 20),
            _ev("train.on_step", t + 250, 10),
            _ev("train.dispatch", t + 300, 90, [("built", 0)])]
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.window", 100, 1000), _ev("bench.publish", 250, 100)] +
        (spans if program else []))])
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        _op("convert.1", 100, 40),
        _ev("tensor_stats_pallas.3", 140, 60),
        _op("while.9", 200, 50),
        _op("fusion.4", 205, 20),
        _op("fusion.5", 225, 20),
        _op("fusion.6", 400, 100),
        _op("fusion.7", 520, 380)])])       # no op_name in the trace
    return NS(planes=[host, dev])


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """Puts a run's trace where the harness keeps it, read as `profile`
    with `names`; returns the ctx the harness hands the readers."""
    monkeypatch.setattr(program_trace.tempfile, "tempdir", str(tmp_path))
    program_trace._cache.clear()

    def put(profile, names, window_s=1e-6):
        run = tmp_path / "onchip-trace-ab12" / "plugins" / "profile" / "r"
        run.mkdir(parents=True, exist_ok=True)
        (run / "h.xplane.pb").write_bytes(b"")
        monkeypatch.setattr(program_trace, "load_profile",
                            lambda path: profile)
        monkeypatch.setattr(program_trace, "op_names", lambda path: names)
        ops = xplane.device_ops(profile)
        return {
            "window_s": window_s, "busy_s": 0.65e-6, "steps": 2, "chips": 1,
            "tokens_per_step": 4096, "flops_per_token": 3e9,
            "stats_bytes_per_step": 81900, "spans": [],
            "ops": [o for dev in ops.values() for o in dev],
            "peaks": peaks.peaks("TPU v5 lite")}
    yield put
    program_trace._cache.clear()


def test_program_spans_carry_their_counters():
    spans = program_trace.program_spans(_traced_profile())
    assert [s[0] for s in spans[:3]] == ["train.step", "train.wait",
                                         "train.publish"]
    assert spans[0][1:3] == (100, 500)
    assert spans[0][3] == {"_r": 1, "step_num": 1, "d2h": 16}
    steps = program_trace.window_steps(spans, 100, 1100)
    assert [s[3]["step_num"] for s, _ in steps] == [1, 2]   # 3 is cut
    assert len(steps[0][1]) == 6


def test_self_time_counts_a_container_once():
    ops = [("while", 0, 100, {}), ("a", 10, 30, {}), ("b", 40, 50, {}),
           ("c", 45, 48, {}), ("d", 120, 130, {})]
    # b holds c; the while holds a and b
    assert program_trace.self_ns(ops) == [100 - 20 - 10, 20, 10 - 3, 3, 10]


def test_scope_paths_unwrap_transformations():
    assert program_trace.scope_parts(
        "a/transpose(jvp(probe.stage.table))/b") == \
        ["a", "probe.stage.table", "b"]
    assert program_trace.in_scope("jit(f)/jvp(probe.collect)/x",
                                  "probe.collect")
    assert program_trace.in_scope("jit(f)/probe.stage.vector/add",
                                  "probe.stage.")
    assert not program_trace.in_scope("jit(f)/probe.collector/add",
                                      "probe.collect")
    assert not program_trace.in_scope("", "probe.stage.")


def test_program_metric_readers_on_a_synthetic_trace(traced):
    ctx = traced(_traced_profile(), NAMES)
    # 400 ns steps less 150 of wait and 10 of on_step, in ms
    assert harness.metric_reader("loop_host_ms")(ctx) == \
        pytest.approx(240e-6)
    assert harness.metric_reader("host_syncs_per_step")(ctx) == 16
    assert harness.metric_reader("publish_fetch_ms")(ctx) == \
        pytest.approx(60e-6)
    # the convert, not the stats kernel under the same scope: 40 ns / 2
    assert harness.metric_reader("collect_ms")(ctx) == pytest.approx(20e-6)
    # both lanes, the while's own time not counted: (20 + 20) / 2
    assert harness.metric_reader("probe_stage_ms")(ctx) == \
        pytest.approx(20e-6)
    # the kernel still reads as before
    assert harness.metric_reader("stats_kernel_ms")(ctx) == \
        pytest.approx(30e-6)


@pytest.mark.parametrize("name", PROGRAM_READERS)
def test_program_readers_without_spans_or_scopes_return_nothing(name,
                                                                traced):
    ctx = traced(_traced_profile(program=False),
                 {"%fusion.6 = f32[] op()": "jit(f)/dot"})
    assert harness.metric_reader(name)(ctx) is None


@pytest.mark.parametrize("name", PROGRAM_READERS)
def test_program_readers_without_the_runs_trace_return_nothing(name,
                                                               traced):
    # another run's window, or no trace directory at all
    ctx = traced(_traced_profile(), NAMES, window_s=2e-6)
    assert harness.metric_reader(name)(ctx) is None
    program_trace._cache.clear()
    for d, _, files in os.walk(program_trace.tempfile.tempdir):
        for f in files:
            os.remove(os.path.join(d, f))
    ctx["window_s"] = 1e-6
    assert harness.metric_reader(name)(ctx) is None


def test_a_real_cpu_trace_is_found_and_read(tmp_path, monkeypatch):
    import jax
    monkeypatch.setattr(program_trace.tempfile, "tempdir", str(tmp_path))
    program_trace._cache.clear()
    trace_dir = tmp_path / "onchip-trace-cpu"
    jax.profiler.start_trace(str(trace_dir))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for n in (1, 2):
                with jax.profiler.StepTraceAnnotation("train.step",
                                                      step_num=n) as step:
                    with jax.profiler.TraceAnnotation("train.wait"):
                        jax.numpy.ones(4).block_until_ready()
                    step.set_metadata(d2h=3)
    finally:
        jax.profiler.stop_trace()
    window = [s for s in xplane.host_spans(xplane.load(str(trace_dir)))
              if s[0] == "bench.window"][0]
    ctx = {"window_s": (window[2] - window[1]) / 1e9}
    assert harness.metric_reader("host_syncs_per_step")(ctx) == 3
    assert harness.metric_reader("loop_host_ms")(ctx) >= 0
    assert [s[0][3]["step_num"] for s in program_trace.steps(ctx)] == [1, 2]
    program_trace._cache.clear()


def test_gap_spans_name_each_gap_by_its_innermost_span():
    trace = program_trace.reduce(_traced_profile(), NAMES)
    gaps = program_trace.gap_spans(trace)
    # device idle, longest first: 900..1100, 250..400, 500..520
    assert [g["gap_ms"] for g in gaps] == [200e-6, 150e-6, 20e-6]
    # 900..1100: step 3's wait, then its publish
    assert gaps[0]["span"] == "train.wait"
    assert gaps[0]["covered_pct"] == pytest.approx(100.0)
    # 250..400: step 1's publish, and inside it most in the fetch; its
    # publish and on_step cover 250..360
    assert gaps[1]["span"] == "publish.fetch"
    assert gaps[1]["covered_pct"] == pytest.approx(100 * 110 / 150)
    assert gaps[2]["span"] == "train.wait"
    none = program_trace.gap_spans(
        program_trace.reduce(_traced_profile(program=False), NAMES))
    assert [g["span"] for g in none] == ["loop"] * 3
    assert [g["covered_pct"] for g in none] == [0.0] * 3


def _pb(field, value):
    """One protobuf field: an int as a varint, bytes or str as bytes."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    if isinstance(value, int):
        return varint(field << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(field << 3 | 2) + varint(len(value)) + value


def _entry(key, message):
    return _pb(1, key) + _pb(2, message)


def test_op_names_read_from_the_event_metadata(tmp_path):
    stat_meta = _pb(5, _entry(7, _pb(1, 7) + _pb(2, "tf_op"))) + \
        _pb(5, _entry(8, _pb(1, 8) + _pb(2, "jit(f)/probe.stage.vector/x"))) \
        + _pb(5, _entry(9, _pb(1, 9) + _pb(2, "flops")))
    fusion = _pb(1, 1) + _pb(2, "%fusion.1 = f32[] fusion()") + \
        _pb(5, _pb(1, 9) + _pb(3, 12)) + \
        _pb(5, _pb(1, 7) + _pb(5, "jit(f)/jvp(probe.collect)/convert:"))
    ref = _pb(1, 2) + _pb(2, "%add.2 = f32[] add()") + \
        _pb(5, _pb(1, 7) + _pb(7, 8))
    bare = _pb(1, 3) + _pb(2, "%copy.3 = f32[] copy()")
    device = _pb(1, 5) + _pb(2, "/device:TPU:0") + \
        _pb(3, _pb(2, "XLA Ops")) + _pb(4, _entry(1, fusion)) + \
        _pb(4, _entry(2, ref)) + _pb(4, _entry(3, bare)) + stat_meta
    # a host plane's metadata is not a device op's
    host = _pb(2, "/host:CPU") + _pb(4, _entry(1, _pb(
        2, "%fusion.1 = f32[] fusion()") + _pb(5, _pb(1, 7) + _pb(
            5, "host")))) + stat_meta
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb(1, device) + _pb(1, host))
    names = program_trace.op_names(str(path))
    assert names == {"%fusion.1 = f32[] fusion()":
                     "jit(f)/jvp(probe.collect)/convert",
                     "%add.2 = f32[] add()": "jit(f)/probe.stage.vector/x"}
