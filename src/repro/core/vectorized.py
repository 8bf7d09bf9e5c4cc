"""Vectorized + fused probe execution — the TPU-native beyond-paper
optimization.

The paper JITs each probe invocation to straight-line native code; on a
vector machine the equivalent is executing probe programs over a whole
event batch as tensor ops. For DAG programs whose map side effects are
commutative (fetch-add family), the sequential lax.scan over events
(jit.run_over_events) collapses to:

  1. a SHADOW pass: vmap the T1 if-converted dataflow over event rows with
     side-effect helpers replaced by recorders -> per-call-site batched
     (pred, args) tensors. Event validity is folded into the entry-block
     predicate, so recorded preds already carry it;
  2. an APPLY pass: one scatter-add / segment-sum / histogram-add /
     batched-ringbuf op per call site over the whole batch.

`run_fused_vector` goes one step further (the fused pipeline, DESIGN.md §2):
ALL vector-safe programs across ALL (site, kind) attachments share ONE
shadow vmap pass over the tape — each program's validity mask is its entry
predicate — and side effects apply once per call site. The probe stage then
costs O(events + call_sites) instead of O(programs x events x total_state).

Cost drops from O(B) sequential program bodies to O(call_sites) vector ops.
Semantic deltas vs scan mode (checked by is_vector_safe / documented):
  * fetch-add return values must be dead (we verify this statically);
  * HASH-map fetch_add is batched via sort-by-key + segment_sum + a
    per-unique-key probe/insert pass (maps.j_hash_fetch_add_batch) —
    end states are bit-identical to the sequential twin;
  * ringbuf rows keep batch order; override takes the first valid lane;
  * trace_printk is counted, not stored.
End map states are bit-identical to scan mode for safe programs (tested).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref as KREF

from . import isa, jit as J, maps as M
from .isa import BPF_JMP, BPF_JMP32, OP_MASK
from .verifier import CallAnn, VerifiedProgram

I64 = jnp.int64

_PURE = {"ktime_get_ns", "get_smp_processor_id", "get_current_pid_tgid",
         "log2"}
_EFFECT = {"map_fetch_add", "percpu_fetch_add", "hist_add", "ringbuf_output",
           "override_return", "trace_printk"}


def _r0_dead_after(vprog: VerifiedProgram, call_pc: int) -> bool:
    """Conservative: r0 (the fetch-add result) must be overwritten before any
    read, scanning forward in instruction order (over-approximates across
    branches; good enough for probe programs)."""
    for pc in range(call_pc + 1, len(vprog.insns)):
        ins = vprog.insns[pc]
        cls = ins.cls
        if cls in (isa.BPF_ALU, isa.BPF_ALU64):
            op = ins.op & OP_MASK
            reads_dst = op != isa.BPF_MOV
            if ins.dst == 0 and not reads_dst:
                return True                      # overwritten
            if (ins.dst == 0 and reads_dst) or \
               (ins.op & isa.SRC_MASK and ins.src == 0):
                return False
        elif cls == isa.BPF_LDX:
            if ins.src == 0:
                return False
            if ins.dst == 0:
                return True
        elif cls in (isa.BPF_STX,):
            if ins.src == 0 or ins.dst == 0:
                return False
        elif cls in (BPF_JMP, BPF_JMP32):
            op = ins.op & OP_MASK
            if op == isa.BPF_CALL:
                return True                      # call clobbers r0
            if op == isa.BPF_EXIT:
                return False                     # r0 is the return value
            if ins.dst == 0 or (ins.op & isa.SRC_MASK and ins.src == 0):
                return False
        elif ins.is_lddw() and ins.dst == 0:
            return True
    return True


def is_vector_safe(vprog: VerifiedProgram) -> bool:
    """True iff the program can run on the batched (shadow+apply) path.
    ARRAY *and* HASH fetch_add are both batchable (hash via the sorted
    segment-scatter in maps.j_hash_fetch_add_batch); the remaining
    requirements are an acyclic CFG, dead fetch-add results, and at most
    ONE ringbuf_output site per ring — effects apply per call SITE, so a
    second site emitting to the same ring would land its whole batch
    after the first site's instead of interleaving per event (found by
    the fuzz harness, pinned in tests/corpus/ringbuf_two_sites.json)."""
    if vprog.tier != "dag":
        return False
    rb_fds: set[int] = set()
    for pc, ann in vprog.anns.items():
        if not isinstance(ann, CallAnn):
            continue
        if ann.name in _PURE:
            continue
        if ann.name not in _EFFECT:
            return False
        if ann.name in ("map_fetch_add", "percpu_fetch_add"):
            if not _r0_dead_after(vprog, pc):
                return False
        if ann.name == "ringbuf_output":
            fd = ann.statics[0]
            if fd in rb_fds:
                return False
            rb_fds.add(fd)
    return True


# --------------------------------------------------------------------------
# shadow pass: record (pred, args) per call site instead of executing
# --------------------------------------------------------------------------

def _make_shadow_cb(meta: list):
    """Build the helper callback for the shadow pass. Effectful helpers
    append a (pred, *dynamic_args) record; `meta` collects the matching
    static info (program, helper name, statics) — vmap traces the program
    once, so meta sees exactly one append per call site."""

    def shadow_cb(vp, ann, m, ms, aux_l, pred):
        zero = jnp.int64(0)
        name = ann.name
        if name == "ktime_get_ns":
            return aux_l["time_ns"], ms, aux_l
        if name == "get_smp_processor_id":
            return aux_l["cpu"], ms, aux_l
        if name == "get_current_pid_tgid":
            return aux_l["pid"], ms, aux_l
        if name == "log2":
            return M.jnp_log2_bin(m.regs[1]).astype(I64), ms, aux_l
        # effectful: record (pred, dynamic args); statics into meta
        if name == "map_fetch_add":
            rec = (pred, J._stack_load(m.stack, ann.statics[1], 8), m.regs[3])
        elif name == "percpu_fetch_add":
            rec = (pred, J._stack_load(m.stack, ann.statics[1], 8), m.regs[3])
        elif name == "hist_add":
            rec = (pred, m.regs[2])
        elif name == "ringbuf_output":
            fd, doff, size, _ = ann.statics
            w = vp.map_specs[fd].rec_width
            lanes = [J._stack_load(m.stack, doff + 8 * i, 8)
                     for i in range(size // 8)]
            lanes += [zero] * (w - len(lanes))
            rec = (pred, jnp.stack(lanes))
        elif name == "override_return":
            rec = (pred, m.regs[1])
        elif name == "trace_printk":
            rec = (pred,)
        else:  # pragma: no cover - guarded by is_vector_safe
            raise AssertionError(name)
        ms["__recs__"].append(rec)
        meta.append((vp, name, ann.statics))
        return zero, ms, aux_l

    return shadow_cb


# --------------------------------------------------------------------------
# apply pass: one batched op per call site
# --------------------------------------------------------------------------

def _apply_site(vp, name, statics, rec, maps_state, aux):
    """Apply one call site's batched side effect. rec[0] is the per-lane
    predicate with event validity already folded in (entry_pred)."""
    ok = rec[0]
    if name == "map_fetch_add":
        fd = statics[0]
        sp = vp.map_specs[fd]
        st = maps_state[sp.name]
        keys, delta = rec[1], rec[2]
        if sp.kind == M.MapKind.HASH:
            new = M.j_hash_fetch_add_batch(st, keys, delta, ok)
            maps_state = {**maps_state, sp.name: new}
        else:
            n = sp.max_entries
            inb = ok & (keys >= 0) & (keys < n)
            idx = jnp.clip(keys, 0, n - 1).astype(jnp.int32)
            vals = st["values"].at[idx].add(
                jnp.where(inb, delta, jnp.int64(0)))
            maps_state = {**maps_state, sp.name: {"values": vals}}
    elif name == "percpu_fetch_add":
        fd = statics[0]
        sp = vp.map_specs[fd]
        st = maps_state[sp.name]
        keys, delta = rec[1], rec[2]
        n = sp.max_entries
        inb = ok & (keys >= 0) & (keys < n)
        idx = jnp.clip(keys, 0, n - 1).astype(jnp.int32)
        sh = jnp.clip(aux["cpu"], 0, sp.num_shards - 1).astype(jnp.int32)
        vals = st["values"].at[sh, idx].add(
            jnp.where(inb, delta, jnp.int64(0)))
        maps_state = {**maps_state, sp.name: {"values": vals}}
    elif name == "hist_add":
        fd = statics[0]
        sp = vp.map_specs[fd]
        st = maps_state[sp.name]
        v = rec[1]
        # bin = min(63, bit_length(v)) for v > 0: binary search over the
        # sorted powers of two (exact, O(B log 63) — no [B, 63] matrix)
        pow2 = jnp.asarray(M._POW2)
        bl = jnp.searchsorted(pow2, v, side="right").astype(jnp.int32)
        bins_idx = jnp.where(v <= 0, 0, jnp.minimum(63, bl))
        bins = st["bins"].at[bins_idx].add(
            jnp.where(ok, jnp.int64(1), jnp.int64(0)))
        maps_state = {**maps_state, sp.name: {"bins": bins}}
    elif name == "ringbuf_output":
        fd = statics[0]
        sp = vp.map_specs[fd]
        st = maps_state[sp.name]
        head0 = st["head"][0]
        d, h = KREF.ringbuf_emit_batch(st["data"], st["head"], rec[1], ok)
        # dropped accounting, batch form: the i-th valid record lands at
        # monotonic position head0 + rank(i); it laps (overwrites an unread
        # record) when that position >= capacity.
        cap = sp.max_entries
        rank = jnp.cumsum(ok.astype(jnp.int64)) - 1
        lapped = jnp.sum((ok & (head0 + rank >= cap)).astype(jnp.int64))
        dropped = st["dropped"].at[0].add(lapped)
        maps_state = {**maps_state,
                      sp.name: {"data": d, "head": h, "dropped": dropped}}
    elif name == "override_return":
        any_ok = jnp.any(ok)
        first = jnp.argmax(ok.astype(jnp.int32))
        aux = {**aux,
               "override_set": jnp.where(any_ok, jnp.int64(1),
                                         aux["override_set"]),
               "override_val": jnp.where(any_ok, rec[1][first],
                                         aux["override_val"])}
    elif name == "trace_printk":
        aux = {**aux, "printk_n": aux["printk_n"] +
               jnp.sum(ok.astype(I64))}
    return maps_state, aux


# --------------------------------------------------------------------------
# drivers
# --------------------------------------------------------------------------

def run_vectorized(vprog: VerifiedProgram, event_rows, valid, maps_state,
                   aux):
    """Single-program batched execution (seed 'vectorized' mode).
    event_rows: i64[B, 16]; valid: bool[B] folded into the entry pred."""
    meta: list[tuple] = []
    t1 = J.compile_t1(vprog, helper_cb=_make_shadow_cb(meta))

    def shadow(row, ok):
        ms = {"__recs__": []}
        t1(row, ms, aux, entry_pred=ok)
        return tuple(ms["__recs__"])

    recs = jax.vmap(shadow)(event_rows, valid)
    # meta collected len(recs) times? no: vmap traces once -> one append/site
    assert len(meta) == len(recs)
    for (vp, name, statics), rec in zip(meta, recs):
        maps_state, aux = _apply_site(vp, name, statics, rec, maps_state,
                                      aux)
    return maps_state, aux


def run_fused_vector(entries, event_rows, maps_state, aux):
    """The fused pipeline's vector lane: ONE vmap pass over the event tape
    executing every vector-safe program of every attachment, then one
    batched apply per call site.

    entries: [(site_id, kind, vprog)] in attachment order — apply order
    matches the seed scan mode's sorted-attachment iteration, so per-map
    streams (ringbuf record order, override first-lane) are preserved."""
    meta: list[tuple] = []
    cb = _make_shadow_cb(meta)
    t1s = [(sid, kind, J.compile_t1(vp, helper_cb=cb))
           for sid, kind, vp in entries]

    def shadow(row):
        ms = {"__recs__": []}
        for sid, kind, t1 in t1s:
            pred = (row[0] == jnp.int64(sid)) & (row[1] == jnp.int64(kind))
            t1(row, ms, aux, entry_pred=pred)
        return tuple(ms["__recs__"])

    recs = jax.vmap(shadow)(event_rows)
    assert len(meta) == len(recs)
    for (vp, name, statics), rec in zip(meta, recs):
        maps_state, aux = _apply_site(vp, name, statics, rec, maps_state,
                                      aux)
    return maps_state, aux
