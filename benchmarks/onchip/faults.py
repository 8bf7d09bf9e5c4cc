"""Faults planted in the program under test, to show that `correct` comes
out false when the timed path is broken. Each is a context manager that
patches the program's modules for the runs made inside it; the
benchmark's own runs never enter one.

  unchanged_state  the step returns the parameters and the optimizer
                   state it was given
  half_batch       the loss leaves out half the batch's rows and takes the
                   mean over the rest
  altered_update   one leaf's update (the final norm's scale) is doubled
                   where the step produces it
  altered_count    the probe stage adds one to the first entry of the
                   first ARRAY map where it produces the maps
  altered_stats    the stats kernel's wrapper reports twice the rms and
                   the absmax of every probed tensor
  half_tensor      the stats kernel reads the first half of each probed
                   tensor only
  bf16_accum       the stats kernel's running sums are held in bfloat16,
                   one addition per row of the tensor's last axis
  planted_nan      the stats kernel reads a NaN in place of the loss and of
                   the gradient norm, so the guard must veto every update
"""
from __future__ import annotations

import contextlib

import harness


def _wrap_step(edit):
    from repro.train import train_step as TS
    make = TS.make_train_step

    def make_train_step(*a, **k):
        step = make(*a, **k)

        def faulty(state, batch):
            new, metrics = step(state, batch)
            return edit(state, new), metrics
        return faulty
    return harness._patched(TS, "make_train_step", make_train_step)


@contextlib.contextmanager
def unchanged_state():
    with _wrap_step(lambda old, new: {**new, "params": old["params"],
                                      "opt": old["opt"]}):
        yield


@contextlib.contextmanager
def altered_update():
    def edit(old, new):
        p_old = old["params"]["final_norm"]["scale"]
        p_new = new["params"]["final_norm"]["scale"]
        params = {**new["params"],
                  "final_norm": {"scale": p_old + 2 * (p_new - p_old)}}
        return {**new, "params": params}
    with _wrap_step(edit):
        yield


@contextlib.contextmanager
def half_batch():
    from repro.models import registry as MR
    loss_fn = MR.loss_fn

    def half(params, batch, cfg, **k):
        rows = batch["tokens"].shape[0] // 2
        return loss_fn(params, {n: v[:rows] for n, v in batch.items()},
                       cfg, **k)
    with harness._patched(MR, "loss_fn", half):
        yield


@contextlib.contextmanager
def altered_count():
    from repro.core.runtime import BpftimeRuntime
    stage = BpftimeRuntime.probe_stage

    def probe_stage(self, *a, **k):
        maps, aux = stage(self, *a, **k)
        for name, st in maps.items():
            if set(st) == {"values"}:
                st = {"values": st["values"].at[0].add(1)}
                return {**maps, name: st}, aux
        return maps, aux
    with harness._patched(BpftimeRuntime, "probe_stage", probe_stage):
        yield


def _wrap_stats(edit):
    from repro.kernels import ops
    stats = ops.tensor_stats

    def faulty(x, impl=None):
        return edit(stats, x, impl)
    return harness._patched(ops, "tensor_stats", faulty)


@contextlib.contextmanager
def altered_stats():
    def edit(stats, x, impl):
        st = stats(x, impl)
        return {**st, "rms": 2 * st["rms"], "absmax": 2 * st["absmax"]}
    with _wrap_stats(edit):
        yield


@contextlib.contextmanager
def half_tensor():
    def edit(stats, x, impl):
        return stats(x.reshape(-1)[:max(1, x.size // 2)], impl)
    with _wrap_stats(edit):
        yield


@contextlib.contextmanager
def bf16_accum():
    import jax
    import jax.numpy as jnp

    def edit(stats, x, impl):
        st = stats(x, impl)
        rows = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
        z = jnp.where(jnp.isfinite(rows), rows, 0.0)

        def add(acc, part):
            return tuple(a + p.astype(jnp.bfloat16)
                         for a, p in zip(acc, part)), None
        zero = jnp.zeros((), jnp.bfloat16)
        (s, ss), _ = jax.lax.scan(add, (zero, zero),
                                  (z.sum(-1), (z * z).sum(-1)))
        n = jnp.maximum(x.size - st["nan_cnt"] - st["inf_cnt"], 1)
        n = n.astype(jnp.float32)
        return {**st, "mean": s.astype(jnp.float32) / n,
                "rms": jnp.sqrt(ss.astype(jnp.float32) / n)}
    with _wrap_stats(edit):
        yield


@contextlib.contextmanager
def planted_nan():
    import jax.numpy as jnp

    def edit(stats, x, impl):
        if x.size == 1:                 # the loss and the gradient norm
            x = jnp.full(x.shape, jnp.nan, x.dtype)
        return stats(x, impl)
    with _wrap_stats(edit):
        yield


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch,
          "altered_update": altered_update, "altered_count": altered_count,
          "altered_stats": altered_stats, "half_tensor": half_tensor,
          "bf16_accum": bf16_accum, "planted_nan": planted_nan}
