"""Plain reference of the probed train step, in float32.

A decoder-only transformer as the configuration file states it (RMSNorm,
rotary embeddings on the whole head, grouped-query attention, SwiGLU,
tied embeddings), its cross-entropy loss, gradients, clipping by the
global norm and AdamW, written out in `jax.numpy` with every matrix
product at `Precision.HIGHEST`. It imports nothing of the program.

It also states what the probe programs of a traffic mix compute: each
probe site's statistics over the whole tensor, and the maps that the
programs' declared `reference` effects build from them.

Memory: one row of the batch at a time, with the layers under
`jax.checkpoint`, so that a full-width step fits beside the optimizer
state on one chip.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from weights import change_norms, head_dim, make_params

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
ENTRY, EXIT, POINT = 0, 1, 2        # uprobe, uretprobe, probe
LAYER_SITES = (("block", ENTRY), ("attn.out", POINT), ("ffn.out", POINT),
               ("block", EXIT))
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


def _partials(x):
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


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: [S, heads, hd]; rotate-half convention over the whole head."""
    S, _, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs          # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(x, p, model):
    S, D = x.shape
    H, KH, hd = model["num_heads"], model["num_kv_heads"], head_dim(model)
    eps = model["norm_eps"]
    entry = _partials(x)
    h = _rmsnorm(x, p["norm1"]["scale"], eps)
    a = p["attn"]
    q = jnp.matmul(h, a["wq"], precision=HI)
    k = jnp.matmul(h, a["wk"], precision=HI)
    v = jnp.matmul(h, a["wv"], precision=HI)
    if model["qkv_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q.reshape(S, H, hd), model["rope_theta"])
    k = _rope(k.reshape(S, KH, hd), model["rope_theta"])
    v = v.reshape(S, KH, hd)
    # query head i reads key/value head i // (H // KH)
    k = jnp.repeat(k, H // KH, axis=1)
    v = jnp.repeat(v, H // KH, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v, precision=HI)
    attn_out = jnp.matmul(o.reshape(S, H * hd), a["wo"], precision=HI)
    x = x + attn_out
    h = _rmsnorm(x, p["norm2"]["scale"], eps)
    m = p["mlp"]
    f = jax.nn.silu(jnp.matmul(h, m["wg"], precision=HI)) \
        * jnp.matmul(h, m["wi"], precision=HI)
    ffn_out = jnp.matmul(f, m["wo"], precision=HI)
    x = x + ffn_out
    stats = jnp.stack([entry, _partials(attn_out), _partials(ffn_out),
                       _partials(x)])
    return x, jax.lax.stop_gradient(stats)


def _row_loss(params, tokens, labels, model):
    """Summed next-token loss of one row, and its probe-site partials."""
    V = model["vocab_size"]
    emb = params["embed"]["embedding"]
    x = emb[tokens]
    embed_part = _partials(x)
    blocks = params["stack"]["blocks"][0]
    x, layer_parts = jax.lax.scan(
        jax.checkpoint(lambda c, p: _layer(c, p, model)), x, blocks)
    x = _rmsnorm(x, params["final_norm"]["scale"], model["norm_eps"])
    logits = jnp.matmul(x, emb[:V].T, precision=HI)
    logz = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[:, None],
                                 -1)[:, 0]
    nll = jnp.where(labels >= 0, logz - picked, 0.0)
    aux = {"embed": embed_part, "layers": layer_parts,
           "logits": _partials(logits)}
    return jnp.sum(nll), jax.lax.stop_gradient(aux)


@functools.lru_cache(maxsize=None)
def _fns(model_items: tuple, train_items: tuple):
    model, train = dict(model_items), dict(train_items)
    grad = jax.value_and_grad(lambda p, t, l: _row_loss(p, t, l, model),
                              has_aux=True)

    # the gradient sum, the parameters and AdamW's moments are updated in
    # place (donated), so a full-width step holds four parameter-sized
    # trees and one row's temporaries
    @functools.partial(jax.jit, donate_argnums=(1,))
    def grad_row(params, gsum, tokens, labels):
        (loss, aux), g = grad(params, tokens, labels)
        return loss, aux, jax.tree.map(jnp.add, gsum, g)

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

    return grad_row, update


def train_steps(seed: int, model: dict, train: dict, batches: list) -> dict:
    """Run the reference over `batches` from the seed's weights.

    Returns each step's loss and pre-clip global gradient norm, the
    per-leaf norms of the first clipped gradient, the per-leaf norms of
    the parameters' change over all the steps, and each step's probe-site
    statistics (`sites`: {(site, kind, layer): stats})."""
    grad_row, update = _fns(tuple(sorted(model.items())),
                            tuple(sorted(train.items())))
    params = make_params(seed, model)
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    m, v = zeros(params), zeros(params)
    out = {"loss": [], "grad_norm": [], "sites": []}
    for step, batch in enumerate(batches):
        tokens, labels = batch["tokens"], batch["labels"]
        count = float(np.sum(labels >= 0))
        gsum, nll, parts = zeros(params), 0.0, []
        for r in range(tokens.shape[0]):
            loss_r, aux, gsum = grad_row(params, gsum, tokens[r], labels[r])
            nll += float(loss_r)
            parts.append(jax.tree.map(np.asarray, aux))
        params, m, v, g, gnorm = update(params, gsum, count, m, v,
                                        jnp.asarray(step))
        del gsum
        if step == 0:
            out["grad1"] = leaf_norms(g)
        del g
        loss = nll / count
        out["loss"].append(loss)
        out["grad_norm"].append(float(gnorm))
        out["sites"].append(_site_stats(parts, model, loss, float(gnorm)))
    del m, v
    out["change"] = named(change_norms(params, seed, model))
    return out


def _site_stats(parts: list, model: dict, loss: float, gnorm: float) -> dict:
    """Whole-batch statistics at each probe site of one step."""
    sites = {("embed.out", POINT, 0): merge_partials(
                 np.stack([p["embed"] for p in parts])),
             ("logits", POINT, 0): merge_partials(
                 np.stack([p["logits"] for p in parts]))}
    layers = np.stack([p["layers"] for p in parts], axis=-2)   # [L,4,rows,7]
    merged = merge_partials(layers)
    for layer in range(model["num_layers"]):
        for j, (site, kind) in enumerate(LAYER_SITES):
            sites[(site, kind, layer)] = {k: v[layer, j]
                                          for k, v in merged.items()}
    for site, value in zip(SCALAR_SITES, (loss, gnorm)):
        sites[(site, POINT, 0)] = {
            "mean": value, "rms": abs(value), "min": value, "max": value,
            "absmax": abs(value), "nan_cnt": float(math.isnan(value)),
            "inf_cnt": float(math.isinf(value))}
    return sites


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
