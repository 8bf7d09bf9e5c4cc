"""The jitted train step: loss -> grads (with microbatch accumulation and
remat) -> clip -> optimizer -> probe-execution stage.

bpftime integration points:
  * model probe sites fire during the forward (uprobe analogue);
  * step-level sites: 'loss', 'grad.norm', 'optimizer.update';
  * the probe stage runs ONCE per step over the whole event tape, fully
    in-graph (the paper's no-context-switch property);
  * a 'filter'-style program that calls override_return on any device event
    makes the step SKIP the optimizer update (guard-rail semantics —
    syscall-filter behavior applied to training, e.g. NaN-loss batches).

State pytree:  {params, opt, step, maps, aux_rand}
Batch layout:  [microbatches, micro_bs, seq] when accumulating, else [B, S].
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, TrainConfig
from repro.core import events as E, jit as J
from repro.models import registry as MR
from repro.optim import (clip_by_global_norm, make_optimizer, warmup_cosine)

F32 = jnp.float32


def init_train_state(key, cfg: ModelConfig, tcfg: TrainConfig, runtime=None):
    params = MR.init_params(key, cfg)
    if tcfg.param_dtype == "bfloat16":
        params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    opt_init, _ = make_optimizer(tcfg.optimizer)
    maps = runtime.init_device_maps() if runtime is not None else {}
    return {
        "params": params,
        "opt": opt_init(params),
        "step": jnp.zeros((), jnp.int32),
        "maps": maps,
    }


def abstract_train_state(cfg: ModelConfig, tcfg: TrainConfig, runtime=None):
    """ShapeDtypeStruct tree without allocating (for the dry-run)."""
    return jax.eval_shape(
        lambda: init_train_state(jax.random.PRNGKey(0), cfg, tcfg, runtime))


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig, runtime=None,
                    probe_mode: str | None = None):
    _, opt_update = make_optimizer(tcfg.optimizer)
    collector_wanted = runtime.wanted_sites() if runtime else set()

    def train_step(state, batch):
        params = state["params"]
        col = E.Collector(collector_wanted) if runtime else None

        def loss_and_events(p, mb):
            def compute():
                loss, metrics = MR.loss_fn(p, mb, cfg, remat=tcfg.remat)
                if col is not None:
                    E.probe_site("loss", loss.reshape(1))
                return loss, metrics
            if col is None:
                loss, metrics = compute()
                rows = jnp.zeros((0, E.EVENT_WIDTH), jnp.int64)
                return loss, (metrics, rows)
            with col.frame() as fr:
                loss, metrics = compute()
                rows = col.stacked_rows(fr)
            return loss, (metrics, rows)

        grad_fn = jax.value_and_grad(loss_and_events, has_aux=True)

        ctx = col if col is not None else _nullcontext()
        with ctx:
            if tcfg.microbatch and batch["tokens"].ndim == 3:
                def micro(carry, mb):
                    acc = carry
                    (loss, (metrics, rows)), grads = grad_fn(params, mb)
                    acc = jax.tree.map(
                        lambda a, g: a + g.astype(F32), acc, grads)
                    return acc, (loss, rows)

                zero = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, F32), params)
                acc, (losses, rows_stack) = jax.lax.scan(micro, zero, batch)
                nmb = batch["tokens"].shape[0]
                grads = jax.tree.map(lambda a: a / nmb, acc)
                loss = losses.mean()
                with jax.named_scope(E.COLLECT_SCOPE):
                    rows = rows_stack.reshape(-1, E.EVENT_WIDTH)
            else:
                (loss, (metrics, rows)), grads = grad_fn(params, batch)

            grads, gnorm = clip_by_global_norm(grads, tcfg.clip_norm)
            if tcfg.grad_compression == "int8":
                from repro.dist.compression import int8_roundtrip
                grads = int8_roundtrip(grads)

            if col is not None:
                with col.frame() as fr:
                    E.probe_site("grad.norm", gnorm.reshape(1))
                    E.probe_site("optimizer.update", loss.reshape(1))
                    rows2 = col.stacked_rows(fr)
                with jax.named_scope(E.COLLECT_SCOPE):
                    rows = jnp.concatenate([rows, rows2], axis=0)

        lr = warmup_cosine(state["step"], lr=tcfg.lr, warmup=tcfg.warmup,
                           total=tcfg.total_steps)
        new_params, new_opt = opt_update(
            params, grads, state["opt"], lr,
            weight_decay=tcfg.weight_decay, step=state["step"])

        # ---- probe execution stage (in-graph; the bpftime hot path)
        maps = state["maps"]
        aux = J.make_aux(time_ns=state["step"].astype(jnp.int64))
        if runtime is not None and rows.shape[0] > 0:
            with jax.named_scope(E.COLLECT_SCOPE):
                rows = rows.at[:, 3].set(state["step"].astype(jnp.int64))
            maps, aux = runtime.probe_stage(rows, maps, aux,
                                            mode=probe_mode)
            # filter semantics: an override vetoes this step's update
            veto = aux["override_set"] != 0
            new_params = jax.tree.map(
                lambda n, o: jnp.where(veto, o, n), new_params, params)
            new_opt = jax.tree.map(
                lambda n, o: jnp.where(veto, o, n), new_opt, state["opt"])

        new_state = {
            "params": new_params,
            "opt": new_opt,
            "step": state["step"] + 1,
            "maps": maps,
        }
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr,
                   "vetoed": aux["override_set"] if runtime is not None
                   else jnp.zeros((), jnp.int64)}
        return new_state, metrics

    return train_step


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False
