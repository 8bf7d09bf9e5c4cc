"""Probe sites + event collection — the binary-rewriting analogue.

Model/framework code is annotated with zero-cost markers:

    x = probe_site("attn.out", x)            # free-standing site
    @traceable("mlp")                        # uprobe (entry) + uretprobe (exit)
    def mlp(params, x): ...

With no probe attached, a site is a Python `if` that immediately returns —
the "5-byte nop". When the runtime attaches a program to a site, the next
trace of the step function "patches" the site: the tensor is reduced to a
16-lane stat row (Pallas fused-stats kernel on the heavy path) and appended
to the step's event tape. One probe-execution stage per step then runs the
attached eBPF programs over the tape (see runtime.py) — events never cross
the device/host boundary (the paper's inline-execution property).

Event row layout (i64 lanes; stats in saturating Q47.16 fixed point):
    0 site_id   1 kind    2 layer     3 step
    4 numel     5 mean    6 rms       7 min
    8 max       9 absmax  10 nan_cnt  11 inf_cnt
    12..15 user/spare (zero)
"""
from __future__ import annotations

import functools
import threading
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from repro.kernels import ops

EVENT_WIDTH = 16
KIND_ENTRY = 0    # uprobe
KIND_EXIT = 1     # uretprobe
KIND_TRACEPOINT = 2

FX_SHIFT = 16
FX_ONE = 1 << FX_SHIFT
_FX_MAX = (1 << 62) - 1

I64 = jnp.int64
# name scope of the collection's device work (stats, row building and
# stacking): the `op_name` of every such XLA operation carries it
COLLECT_SCOPE = "probe.collect"


def to_fx(x):
    """f32 -> saturating Q47.16 fixed-point i64 (NaN -> 0)."""
    x = jnp.asarray(x, jnp.float32)
    v = jnp.where(jnp.isnan(x), 0.0, x) * float(FX_ONE)
    v = jnp.clip(v, -float(_FX_MAX), float(_FX_MAX))
    return v.astype(I64)


def from_fx(v):
    return jnp.asarray(v, jnp.float32) / float(FX_ONE)


# --------------------------------------------------------------------------
# site registry (stable name -> id, registration order)
# --------------------------------------------------------------------------

class SiteRegistry:
    def __init__(self):
        self._ids: dict[str, int] = {}
        self._names: list[str] = []
        self._lock = threading.Lock()

    def get_or_create(self, name: str) -> int:
        with self._lock:
            if name not in self._ids:
                self._ids[name] = len(self._names)
                self._names.append(name)
            return self._ids[name]

    def name_of(self, site_id: int) -> str:
        return self._names[site_id]

    def known(self) -> dict[str, int]:
        return dict(self._ids)


SITES = SiteRegistry()


# --------------------------------------------------------------------------
# collector (trace-time ambient; push/pop frames for scan/remat bodies)
# --------------------------------------------------------------------------

@dataclass
class _Frame:
    rows: list = field(default_factory=list)


class Collector:
    """Active during step-function tracing when >=1 device probe is attached.
    `wanted` is the set of (site_id, kind) pairs with attached programs —
    unattached sites stay nops even while a collector is active."""

    _tls = threading.local()

    def __init__(self, wanted: set[tuple[int, int]], stats_fn=None):
        self.wanted = wanted
        self.frames: list[_Frame] = [_Frame()]
        self.layer_ctx = jnp.asarray(0, I64)
        self.stats_fn = stats_fn  # tensor -> dict of stats (see ops.tensor_stats)

    # ---- ambient management
    @classmethod
    def active(cls) -> "Collector | None":
        return getattr(cls._tls, "collector", None)

    def __enter__(self):
        if Collector.active() is not None:
            raise RuntimeError("nested Collector activation")
        Collector._tls.collector = self
        return self

    def __exit__(self, *exc):
        Collector._tls.collector = None
        return False

    # ---- frames
    class _FrameCtx:
        def __init__(self, col):
            self.col = col

        def __enter__(self):
            self.frame = _Frame()
            self.col.frames.append(self.frame)
            return self.frame

        def __exit__(self, *exc):
            assert self.col.frames.pop() is self.frame
            return False

    def frame(self):
        return Collector._FrameCtx(self)

    # ---- emission
    def wants(self, site_id: int, kind: int) -> bool:
        return (site_id, kind) in self.wanted

    def emit_row(self, row):
        assert row.shape == (EVENT_WIDTH,)
        self.frames[-1].rows.append(row)

    def emit_many(self, rows):
        """rows: i64[N, W] (e.g. reshaped scan ys)."""
        assert rows.ndim == 2 and rows.shape[1] == EVENT_WIDTH
        self.frames[-1].rows.append(rows)

    def emit_tensor_event(self, site_id: int, kind: int, tensor):
        with jax.named_scope(COLLECT_SCOPE):
            # stats only observe the tensor; without the stop the enclosing
            # value_and_grad would try to differentiate the Pallas kernel
            st = self._stats(jax.lax.stop_gradient(tensor))
            row = jnp.stack([
                jnp.asarray(site_id, I64),
                jnp.asarray(kind, I64),
                jnp.asarray(self.layer_ctx, I64),
                jnp.asarray(0, I64),                       # step, filled later
                jnp.asarray(tensor.size, I64),
                to_fx(st["mean"]), to_fx(st["rms"]),
                to_fx(st["min"]), to_fx(st["max"]), to_fx(st["absmax"]),
                st["nan_cnt"].astype(I64), st["inf_cnt"].astype(I64),
                jnp.asarray(0, I64), jnp.asarray(0, I64),
                jnp.asarray(0, I64), jnp.asarray(0, I64),
            ])
            self.emit_row(row)

    def _stats(self, tensor):
        if self.stats_fn is not None:
            return self.stats_fn(tensor)
        return ops.tensor_stats(tensor)

    def stacked_rows(self, frame: _Frame):
        parts = []
        for r in frame.rows:
            parts.append(r[None, :] if r.ndim == 1 else r)
        if not parts:
            return jnp.zeros((0, EVENT_WIDTH), I64)
        with jax.named_scope(COLLECT_SCOPE):
            return jnp.concatenate(parts, axis=0)

    def take_all_rows(self):
        assert len(self.frames) == 1, "unbalanced frames"
        rows = self.stacked_rows(self.frames[0])
        self.frames[0].rows.clear()
        return rows


# --------------------------------------------------------------------------
# site markers used by model/framework code
# --------------------------------------------------------------------------

def probe_site(name: str, tensor, kind: int = KIND_TRACEPOINT):
    """Zero-cost marker. Returns `tensor` unchanged (identity in the graph)."""
    col = Collector.active()
    if col is None:
        return tensor
    sid = SITES.get_or_create(name)
    if col.wants(sid, kind):
        col.emit_tensor_event(sid, kind, tensor)
    return tensor


def _first_array_leaf(tree):
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "shape") and getattr(leaf, "size", 0) > 0:
            return leaf
    return None


def traceable(name: str):
    """uprobe/uretprobe pair on a function: entry summarizes the first array
    argument leaf, exit summarizes the first output leaf."""
    sid = SITES.get_or_create(name)

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            col = Collector.active()
            if col is not None and col.wants(sid, KIND_ENTRY):
                leaf = _first_array_leaf((args, kwargs))
                if leaf is not None:
                    col.emit_tensor_event(sid, KIND_ENTRY, leaf)
            out = fn(*args, **kwargs)
            if col is not None and col.wants(sid, KIND_EXIT):
                leaf = _first_array_leaf(out)
                if leaf is not None:
                    col.emit_tensor_event(sid, KIND_EXIT, leaf)
            return out
        return wrapper
    return deco


# --------------------------------------------------------------------------
# scan/remat-aware collection
# --------------------------------------------------------------------------

def probed_scan(body, carry, xs, *, length=None, remat=False,
                remat_policy=None, layer_ids=True):
    """lax.scan that routes probe emissions from inside the body out as
    stacked ys (events survive the scan boundary). The row-collection wrapper
    sits INSIDE the remat boundary so emissions are explicit outputs (no
    leaked tracers, stats are primal outputs and not recomputed).

    body: (carry, x) -> (carry, y)
    """
    col = Collector.active()
    if col is None:
        f = jax.checkpoint(body, policy=remat_policy) if remat else body
        return jax.lax.scan(f, carry, xs, length=length)

    n = length
    if n is None:
        n = jax.tree.leaves(xs)[0].shape[0]
    xs2 = (xs, jnp.arange(n, dtype=I64)) if layer_ids else (xs, None)

    def with_rows(c, x2):
        x, lid = x2
        old = col.layer_ctx
        if lid is not None:
            col.layer_ctx = lid
        with col.frame() as fr:
            c2, y = body(c, x)
        rows = col.stacked_rows(fr)
        col.layer_ctx = old
        return c2, (y, rows)

    f = jax.checkpoint(with_rows, policy=remat_policy) if remat else with_rows
    c_out, (ys, rows) = jax.lax.scan(f, carry, xs2, length=n)
    with jax.named_scope(COLLECT_SCOPE):
        col.emit_many(rows.reshape(-1, EVENT_WIDTH))
    return c_out, ys
