"""The program's own spans and scopes in the trace of a `--trace 1` run.

A metric reader gets from the harness the window's device ops and the
benchmark's `bench.*` spans, not the profile. The program's host spans
(`train.*` around the training loop's host work, `publish.*` inside the
runtime's map publish, their counters as arguments) and each device op's
`op_name` (the `jax.named_scope` path it was traced under: `probe.collect`,
`probe.stage.<lane>`) are read here from the same `.xplane.pb`, which the
harness keeps in its `onchip-trace-*` directory until the metrics are read.
A trace whose `bench.window` differs from the reader's window is not the one
being reduced, and reads as no trace.

    python3 benchmarks/onchip/program_trace.py --workload <cell> --seed <n> \
        --seconds <s>

runs the cell traced, as run.py does, and prints after its result line one
more JSON line, `gap_spans`: the ten longest idle gaps of the first device,
each with the innermost program span that overlaps it most and the share
of it, in %, that the spans below `train.step` cover.
"""
from __future__ import annotations

import glob
import json
import os
import sys
import tempfile

import xplane

PREFIXES = ("train.", "publish.")
STEP_SPAN = "train.step"
TRACE_PREFIX = "onchip-trace-"
_cache: dict = {}


def trace_of(ctx) -> dict | None:
    """The reduced trace (`reduce`) of the run being read, or None."""
    files = glob.glob(os.path.join(tempfile.gettempdir(), TRACE_PREFIX + "*",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        return None
    path = max(files, key=os.path.getmtime)
    if path not in _cache:
        _cache.clear()
        _cache[path] = reduce(load_profile(path), op_names(path))
    trace = _cache[path]
    if trace is None or \
            abs((trace["window_ns"][1] - trace["window_ns"][0]) / 1e9 -
                ctx["window_s"]) > 1e-9:
        return None
    return trace


def last() -> dict | None:
    """The trace the last `trace_of` reduced."""
    return next(iter(_cache.values()), None)


def load_profile(path: str):
    import jax
    return jax.profiler.ProfileData.from_file(path)


def reduce(profile, names: dict) -> dict | None:
    """{window_ns, program_spans, ops, scoped_ops} of a profile; `names`
    maps a device op's text to its `op_name` (`op_names`). None without a
    `bench.window` span."""
    windows = [s for s in xplane.host_spans(profile)
               if s[0] == "bench.window"]
    if not windows:
        return None
    _, lo, hi = windows[0]
    ops = xplane.device_ops(profile)
    return {"window_ns": (lo, hi), "program_spans": program_spans(profile),
            "ops": ops, "scoped_ops": scoped_ops(ops, lo, hi, names)}


def program_spans(profile) -> list:
    """[(name, start_ns, end_ns, args)] of the program's host spans
    (`train.*`, `publish.*`); `args` holds the span's counters."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                     xplane._stats(ev)) for ev in line.events
                    if ev.name.startswith(PREFIXES)]
    return sorted(out, key=lambda s: s[1])


def window_steps(spans, lo: float, hi: float) -> list:
    """[(step span, [spans inside it])] of the `train.step` spans that lie
    wholly inside [lo, hi]."""
    out = []
    for step in spans:
        if step[0] == STEP_SPAN and lo <= step[1] and step[2] <= hi:
            out.append((step, [s for s in spans if s is not step and
                               step[1] <= s[1] and s[2] <= step[2]]))
    return out


def steps(ctx) -> list:
    """`window_steps` of the run being read; [] without its trace."""
    trace = trace_of(ctx)
    if trace is None:
        return []
    return window_steps(trace["program_spans"], *trace["window_ns"])


def scoped(ctx, scope: str) -> list:
    """[(self ns, op text)] of the window's device ops under `scope`
    (`in_scope`); [] without the run's trace."""
    trace = trace_of(ctx)
    if trace is None:
        return []
    return [(ns, text) for path, ns, text in trace["scoped_ops"]
            if in_scope(path, scope)]


def scoped_ops(ops, lo, hi, names) -> list:
    """[(scope path, self ns, op text)] of every device op in the window;
    an op that `names` lacks has the path ""."""
    out = []
    for dev in ops.values():
        own = self_ns(dev)
        out += [(names.get(o[0], ""), own[i], o[0])
                for i, o in enumerate(dev)
                if xplane.overlap(o[1], o[2], lo, hi) > 0]
    return out


def self_ns(ops) -> list:
    """Each op's own time, aligned with `ops` (one device's line): its
    duration less the time of the ops nested inside it, so that a
    container (`while`, `conditional`, `call`) and the ops it runs count
    once."""
    own = [e - s for _, s, e, _ in ops]
    stack: list = []
    for i in sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2])):
        s, e = ops[i][1], ops[i][2]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            outer = stack[-1]
            own[outer] -= min(e, ops[outer][2]) - s
        stack.append(i)
    return own


def scope_parts(path: str) -> list:
    """The scopes of an `op_name` path, each out of the transformations
    JAX wraps it in (`jvp(probe.collect)`, `transpose(jvp(...))`)."""
    out = []
    for part in path.split("/"):
        while part.endswith(")") and "(" in part:
            part = part[part.index("(") + 1:-1]
        out.append(part)
    return out


def in_scope(path: str, scope: str) -> bool:
    """Whether the scope path holds `scope`, or a scope under it when
    `scope` ends in "." (`probe.stage.` takes every lane)."""
    parts = scope_parts(path)
    if scope.endswith("."):
        return any(p.startswith(scope) for p in parts)
    return scope in parts


def gap_spans(trace: dict) -> list:
    """The ten longest idle gaps of the first device, each with the
    innermost program span that overlaps it most (`loop`: none) and the
    share of it, in %, that the spans below `train.step` cover."""
    lo, hi = trace["window_ns"]
    below = [s for s in trace["program_spans"] if s[0] != STEP_SPAN]
    dev0 = next(iter(trace["ops"].values()))
    gaps = xplane.gaps([(o[1], o[2]) for o in dev0], lo, hi)
    out = []
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        def over(s):
            return xplane.overlap(s[1], s[2], g0, g1)
        best, chosen = None, set()
        while True:
            # descend into the span that overlaps most, then into its own
            # spans, until none inside it overlaps the gap
            inner = [s for s in below if over(s) > 0 and id(s) not in chosen
                     and (best is None or best[1] <= s[1] and
                          s[2] <= best[2])]
            if not inner:
                break
            best = max(inner, key=over)
            chosen.add(id(best))
        covered = xplane.busy_ns([(s[1], s[2]) for s in below], g0, g1)
        out.append({"gap_ms": (g1 - g0) / 1e6,
                    "span": best[0] if best else "loop",
                    "covered_pct": 100.0 * covered / (g1 - g0)})
    return out


def _fields(buf: bytes, lo: int, hi: int):
    """(field number, value) of the protobuf message in buf[lo:hi]; a
    length-delimited value as its (start, end) in buf."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        else:                                   # fixed 64 or 32 bits
            value, i = None, i + (8 if wire == 1 else 4)
        yield key >> 3, value


def _varint(buf: bytes, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def op_names(path: str) -> dict:
    """{HLO text of a device op: its `op_name`} from the `tf_op` stat of the
    device planes' event metadata in the `.xplane.pb` at `path`. The
    profiler's Python API gives an event's own stats, not its metadata's,
    so the XSpace message is read here: planes (field 1) with their name
    (2), event metadata (4: id -> {name 2, stats 5}) and stat metadata
    (5: id -> {name 2}); a stat holds its metadata id (1) and a string (5)
    or a reference to a stat metadata's name (7)."""
    with open(path, "rb") as f:
        buf = f.read()

    def text(span):
        return buf[span[0]:span[1]].decode("utf-8", "replace")

    def entry_value(span):
        return next((v for k, v in _fields(buf, *span) if k == 2), None)

    out = {}
    for field, plane in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for k, v in _fields(buf, *plane):
            if k == 2:
                name = text(v)
            elif k == 4:
                events.append(entry_value(v))
            elif k == 5:
                meta = entry_value(v)
                ids = dict(_fields(buf, *meta))
                stat_names[ids.get(1)] = text(ids[2]) if 2 in ids else ""
        if not name.startswith("/device:"):
            continue
        tf_op = {i for i, n in stat_names.items() if n == "tf_op"}
        for ev in events:
            op, path_ = None, None
            for k, v in _fields(buf, *ev):
                if k == 2:
                    op = text(v)
                elif k == 5:
                    stat = dict(_fields(buf, *v))
                    if stat.get(1) in tf_op:
                        path_ = text(stat[5]) if 5 in stat else \
                            stat_names.get(stat.get(7), "")
            if op and path_:
                out[op] = path_.rstrip(":")
    return out


def main(argv=None) -> int:
    import run
    # the metric readers import this file as `program_trace`, which holds
    # the cache when it runs as a script
    import program_trace
    rc = run.main([*(sys.argv[1:] if argv is None else argv),
                   "--trace", "1"])
    trace = program_trace.last()
    if rc == 0 and trace is not None:
        print(json.dumps({"gap_spans": gap_spans(trace)}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
