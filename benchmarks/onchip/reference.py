"""Plain reference of the probed train step, in float32: what every
architecture shares.

The architecture module (`arch/`, named by the configuration) states
the model: its weights, its row loss and the probe-site statistics of
each row. Here is the rest, written out in `jax.numpy`: the train loop
over the batch one row at a time, clipping by the global norm and AdamW,
the whole-batch statistics at each probe site from the rows' partials,
and what the probe programs of a traffic mix compute from them: the
maps that the programs' declared `reference` effects build. A
reference computes every matrix product at `HI`, `Precision.HIGHEST`,
and imports nothing of the program.

Memory: one row of the batch at a time, with the gradient sum, the
parameters and AdamW's moments updated in place, so that a full-width
step fits beside the optimizer state on one chip.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
ENTRY, EXIT, POINT = 0, 1, 2        # uprobe, uretprobe, probe
SCALAR_SITES = ("loss", "grad.norm")


def leaf_name(path) -> str:
    return ".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


@jax.jit
def _norms(tree):
    return jax.tree.map(lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(F32)))),
                        tree)


def named(tree) -> dict:
    """{leaf name: value} of a tree of scalars."""
    return {leaf_name(p): float(v)
            for p, v in jax.tree_util.tree_leaves_with_path(tree)}


def leaf_norms(tree) -> dict:
    """{leaf name: L2 norm} over a parameter-shaped tree."""
    return named(_norms(tree))


def partials(x):
    """Mergeable statistics of one tensor: finite count, sum, sum of
    squares, min, max, NaN and Inf counts."""
    x = x.reshape(-1).astype(F32)
    nan, inf = jnp.isnan(x), jnp.isinf(x)
    bad = nan | inf
    z = jnp.where(bad, 0.0, x)
    return jnp.stack([jnp.sum(~bad).astype(F32), jnp.sum(z), jnp.sum(z * z),
                      jnp.min(jnp.where(bad, jnp.inf, x)),
                      jnp.max(jnp.where(bad, -jnp.inf, x)),
                      jnp.sum(nan).astype(F32), jnp.sum(inf).astype(F32)])


def merge_partials(parts: np.ndarray) -> dict:
    """Statistics of a tensor from the partials of its row blocks
    (`parts`: [..., blocks, 7]), as the probe row reports them."""
    p = np.asarray(parts, np.float64)
    n = p[..., 0].sum(-1)
    s, ss = p[..., 1].sum(-1), p[..., 2].sum(-1)
    mn, mx = p[..., 3].min(-1), p[..., 4].max(-1)
    ok = n > 0
    n1 = np.maximum(n, 1.0)
    mn, mx = np.where(ok, mn, 0.0), np.where(ok, mx, 0.0)
    return {"mean": s / n1, "rms": np.sqrt(ss / n1), "min": mn, "max": mx,
            "absmax": np.maximum(np.abs(mn), np.abs(mx)),
            "nan_cnt": p[..., 5].sum(-1), "inf_cnt": p[..., 6].sum(-1)}


@functools.lru_cache(maxsize=None)
def _update_fn(train_items: tuple):
    train = dict(train_items)

    # the parameters, the gradient sum and AdamW's moments are donated, so
    # a full-width step holds four parameter-sized trees and one row's
    # temporaries
    @functools.partial(jax.jit, donate_argnums=(0, 1, 3, 4))
    def update(params, gsum, count, m, v, step):
        g = jax.tree.map(lambda a: a / count, gsum)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(a))
                             for a in jax.tree.leaves(g)))
        g = jax.tree.map(
            lambda a: a * jnp.minimum(1.0, train["clip_norm"]
                                      / jnp.maximum(gnorm, 1e-9)), g)
        s = step.astype(F32)
        warm = train["lr"] * s / max(train["warmup"], 1)
        frac = jnp.clip((s - train["warmup"])
                        / max(train["total_steps"] - train["warmup"], 1),
                        0.0, 1.0)
        lr = jnp.where(s < train["warmup"], warm,
                       train["lr"] * 0.5 * (1 + jnp.cos(jnp.pi * frac)))
        b1, b2 = train["b1"], train["b2"]
        m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
        v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
        bc1, bc2 = 1 - b1 ** (s + 1), 1 - b2 ** (s + 1)
        params = jax.tree.map(
            lambda p, a, b: p - lr * ((a / bc1) / (jnp.sqrt(b / bc2)
                                                  + train["eps"])
                                      + train["weight_decay"] * p),
            params, m, v)
        return params, m, v, g, gnorm

    return update


_zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))


def run_steps(params, train: dict, batches: list, grad_row, sites: list,
              change) -> dict:
    """The reference's train loop over `batches` from `params`: the shared
    half of an architecture's `train_steps`.

    `grad_row(params, gsum, tokens, labels)` gives one row's summed loss,
    its probe-site partials ([len(sites), 7], in the order of `sites`, the
    architecture's `layer_sites`) and `gsum` plus the row's gradient
    (`gsum` donated); `change(params)` the per-leaf norms of the change
    from the start.

    Returns each step's loss and pre-clip global gradient norm, the
    per-leaf norms of the first clipped gradient, the per-leaf norms of
    the parameters' change over all the steps, and each step's probe-site
    statistics (`sites`: {(site, kind, layer): stats})."""
    update = _update_fn(tuple(sorted(train.items())))
    m, v = _zeros(params), _zeros(params)
    out = {"loss": [], "grad_norm": [], "sites": []}
    for step, batch in enumerate(batches):
        tokens, labels = batch["tokens"], batch["labels"]
        count = float(np.sum(labels >= 0))
        gsum, nll, parts = _zeros(params), 0.0, []
        for r in range(tokens.shape[0]):
            loss_r, part, gsum = grad_row(params, gsum, tokens[r], labels[r])
            nll += float(loss_r)
            parts.append(part)
        params, m, v, g, gnorm = update(params, gsum, count, m, v,
                                        jnp.asarray(step))
        del gsum
        if step == 0:
            out["grad1"] = leaf_norms(g)
        del g
        loss = nll / count
        out["loss"].append(loss)
        out["grad_norm"].append(float(gnorm))
        out["sites"].append(_site_stats(parts, sites, loss, float(gnorm)))
    del m, v
    out["change"] = named(change(params))
    return out


def _site_stats(parts: list, sites: list, loss: float, gnorm: float) -> dict:
    """Whole-batch statistics at each probe site of one step: the rows'
    partials merged site by site, for each (site, kind, layer) of
    `sites`, and the scalar sites."""
    merged = merge_partials(np.stack(parts, axis=-2))   # [sites, rows, 7]
    out = {site: {k: v[i] for k, v in merged.items()}
           for i, site in enumerate(sites)}
    for site, value in zip(SCALAR_SITES, (loss, gnorm)):
        out[(site, POINT, 0)] = {
            "mean": value, "rms": abs(value), "min": value, "max": value,
            "absmax": abs(value), "nan_cnt": float(math.isnan(value)),
            "inf_cnt": float(math.isinf(value))}
    return out


# ---------------------------------------------------------------- probes

KIND_OF = {"uprobe": ENTRY, "uretprobe": EXIT, "probe": POINT}


def fx(value: float) -> int:
    """The probe row's fixed point: value x 2**16, truncated, saturating."""
    if not math.isfinite(value):
        return 0
    return int(max(-(2 ** 62 - 1), min(2 ** 62 - 1, value * 65536.0)))


def log2_bin(v: int) -> int:
    return 0 if v <= 0 else min(63, v.bit_length())


COUNTS = ("nan_cnt", "inf_cnt")


def _summand(st: dict, names: list) -> int:
    """What a `sum` program adds for one event: counts as they are, a
    statistic in the probe row's fixed point."""
    if all(n in COUNTS for n in names):
        return int(sum(st[n] for n in names))
    (name,) = names
    return fx(float(st[name]))


def expected_maps(programs: list, sites_by_step: list) -> dict:
    """What the traffic's programs leave in their maps after these steps,
    from each program's declared `reference` effect:

      count     +1 at key `layer`, or `site_layer` = (site name, layer)
      log2hist  +1 in the log2 bin of the fixed-point `field`
      sum       at key (site name, layer, i), + the i-th entry of `fields`:
                the summed counts it names, or the fixed point of the one
                statistic it names
      veto      the step is vetoed when the sum of `fields` is above 0

    Returns {map: {key: total}} and the number of vetoed steps."""
    maps: dict = {}
    vetoed = 0
    for sites in sites_by_step:
        veto = False
        for prog in programs:
            eff = prog["reference"]
            for target in prog["targets"]:
                kind_name, site = target.split(":")
                for (s, kind, layer), st in sites.items():
                    if s != site or kind != KIND_OF[kind_name]:
                        continue
                    if eff["op"] == "veto":
                        veto |= sum(st[f] for f in eff["fields"]) > 0
                        continue
                    m = maps.setdefault(eff["map"], {})
                    if eff["op"] == "sum":
                        for i, names in enumerate(eff["fields"]):
                            key = (s, layer, i)
                            m[key] = m.get(key, 0) + _summand(st, names)
                        continue
                    if eff["op"] == "count":
                        key = layer if eff["key"] == "layer" else (s, layer)
                    else:
                        key = log2_bin(fx(float(st[eff["field"]])))
                    m[key] = m.get(key, 0) + 1
        vetoed += int(veto)
    return {"maps": maps, "vetoed": vetoed}
