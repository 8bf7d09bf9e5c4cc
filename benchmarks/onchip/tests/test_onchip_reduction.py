"""The reduction from a trace to per-layer metrics, the peaks table, and
finding a mix, a configuration and a metric by name."""
import json
from types import SimpleNamespace as NS

import pytest

import harness
import peaks
import traffic
import xplane


def _ev(name, start, dur, stats=()):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=list(stats))


def _profile():
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", lines=[], events=[_ev("jit_step", 0, 900)]),
        NS(name="XLA Ops", events=[
            _ev("fusion.1", 100, 200),
            _ev("tensor_stats_pallas.3", 250, 100,
                [("hlo_op", "tensor_stats_pallas.3")]),
            _ev("convolution.2", 500, 300),
            _ev("tensor_stats_pallas", 850, 50)])])
    sc = NS(name="/device:TPU:0 SparseCore 0", lines=[
        NS(name="XLA Ops", events=[_ev("sc_op", 0, 1000)])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.window", 100, 1000), _ev("bench.publish", 400, 100),
        _ev("PjitFunction(step)", 90, 5)])])
    return NS(planes=[NS(name="/host:metadata", lines=[]), host, dev, sc])


def test_device_ops_and_host_spans_from_planes():
    p = _profile()
    ops = xplane.device_ops(p)
    assert list(ops) == ["/device:TPU:0"]            # no SparseCore plane
    assert [o[0] for o in ops["/device:TPU:0"]] == [
        "fusion.1", "tensor_stats_pallas.3", "convolution.2",
        "tensor_stats_pallas"]
    assert ops["/device:TPU:0"][1][3] == {"hlo_op": "tensor_stats_pallas.3"}
    assert xplane.host_spans(p) == [("bench.window", 100, 1100),
                                    ("bench.publish", 400, 500)]


def test_busy_union_and_gaps_clip_to_the_window():
    iv = [(100, 300), (250, 350), (500, 800), (850, 900), (1050, 1200)]
    assert xplane.busy_ns(iv, 100, 1100) == 250 + 300 + 50 + 50
    assert xplane.gaps(iv, 100, 1100) == [(350, 500), (800, 850),
                                          (900, 1050)]
    assert xplane.gaps([], 0, 10) == [(0, 10)]


def _ctx(ops, **kw):
    ctx = {"window_s": 1e-6, "busy_s": 0.65e-6, "steps": 2, "chips": 1,
           "tokens_per_step": 4096, "flops_per_token": 3e9,
           "stats_bytes_per_step": 81900, "ops": ops, "spans": [],
           "peaks": peaks.peaks("TPU v5 lite")}
    ctx.update(kw)
    return ctx


def test_metric_readers_on_a_synthetic_trace():
    ops = xplane.device_ops(_profile())["/device:TPU:0"]
    ctx = _ctx(ops)
    assert harness.metric_reader("device_idle_share")(ctx) == \
        pytest.approx(35.0)
    # kernel by name only: 100 + 50 ns over 2 steps
    assert harness.metric_reader("stats_kernel_ms")(ctx) == \
        pytest.approx(75e-6)
    # 81900 B at 819 GB/s = 100 ns against 75 ns a step
    assert harness.metric_reader("stats_roofline")(ctx) == \
        pytest.approx(100 * 100 / 75)
    tokens_per_s = 2 * 4096 / 1e-6
    assert harness.metric_reader("mfu")(ctx) == \
        pytest.approx(100 * 3e9 * tokens_per_s / 197e12)
    spans = [("bench.publish", 0.0, 0.002), ("bench.data", 0.0, 1.0),
             ("bench.publish", 1.0, 1.004)]
    assert harness.metric_reader("publish_ms")(_ctx(ops, spans=spans)) == \
        pytest.approx(3.0)


def test_readers_find_nothing_and_return_nothing():
    ops = [("fusion.1", 0, 10, {}), ("custom-call.7", 10, 20, {})]
    ctx = _ctx(ops)
    assert harness.metric_reader("stats_kernel_ms")(ctx) is None
    assert harness.metric_reader("stats_roofline")(ctx) is None
    assert harness.metric_reader("publish_ms")(ctx) is None


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="TPU v9"):
        peaks.peaks("TPU v9")


def test_a_traffic_file_is_found_by_name(tmp_path, monkeypatch):
    mix = {"batch": 2, "seq_len": 8, "probe_mode": "fused", "maps": [],
           "programs": []}
    (tmp_path / "dummy.json").write_text(json.dumps(mix))
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", tmp_path)
    assert traffic.load("dummy") == mix
    with pytest.raises(FileNotFoundError):
        traffic.load("absent")


def test_every_cell_finds_its_files_by_name():
    bench = harness.benchmark()
    for cell in bench["workloads"]:
        cfg = harness.config(cell["config"])
        assert set(cfg["model"]) >= {"num_layers", "d_model", "vocab_size"}
        assert traffic.load(cell["traffic"])["batch"] > 0
        assert set(harness.limits(cell["name"])) >= {
            "loss_gap", "grad_gap", "change_gap"}
    for m in bench["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
