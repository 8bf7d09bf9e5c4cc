"""Reduction of a JAX profiler trace (`.xplane.pb`) to intervals.

The device planes (`/device:TPU:<n>`) carry one event per XLA operation
on their "XLA Ops" line; the host plane carries the benchmark's own spans
(`jax.profiler.TraceAnnotation` names starting with `bench.`). Both are
on the profile's one clock, in nanoseconds.
"""
from __future__ import annotations

import glob
import os

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def load(trace_dir: str):
    import jax
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return jax.profiler.ProfileData.from_file(max(files, key=os.path.getmtime))


def op_name(text: str) -> str:
    """The HLO instruction name of a device op event, whose name in a TPU
    trace is the instruction's whole text (`%fusion.12 = bf16[...] ...`)."""
    return text.split(" = ", 1)[0].lstrip("%")


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except (TypeError, ValueError):
        return {}


def device_ops(profile) -> dict:
    """{device plane name: [(op name, start_ns, end_ns, stats)]}."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith("/device:TPU:") or \
                not plane.name[len("/device:TPU:"):].isdigit():
            continue
        ops = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                         _stats(ev)) for ev in line.events]
        out[plane.name] = sorted(ops, key=lambda o: o[1])
    return out


def host_spans(profile) -> list:
    """[(name, start_ns, end_ns)] of the benchmark's own host spans."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events if ev.name.startswith(SPAN_PREFIX)]
    return sorted(out, key=lambda s: s[1])


def merged(intervals, lo: float, hi: float) -> list:
    """The union of [start, end) intervals, clipped to [lo, hi], as sorted
    disjoint intervals."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in merged(intervals, lo, hi))


def gaps(intervals, lo: float, hi: float) -> list:
    """[(start, end)] of [lo, hi] not covered by any interval."""
    out, t = [], lo
    for s, e in merged(intervals, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def overlap(a0: float, a1: float, b0: float, b1: float) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))
