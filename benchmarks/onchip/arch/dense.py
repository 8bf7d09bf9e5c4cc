"""The dense decoder-only transformer, as the program's `family: dense`
keeps it: RMSNorm, rotary embeddings on the whole head, grouped-query
attention with optional QKV bias, SwiGLU, and the output head tied to the
embedding.

Weights: layers stacked on a leading axis, the embedding padded to a
multiple of 256 rows (`weights.padded_vocab`); projections normal(0,
1/sqrt(fan_in)), the embedding and QKV biases normal(0, 0.02), norm
scales one.

Reference: the row loss with the probe-site partials of each row, every
layer under `jax.checkpoint`; the loop, AdamW and site statistics are
`reference.py`'s. Four tensor sites fire on every layer (block entry and
exit, `attn.out`, `ffn.out`), and `embed.out` and `logits` once.

FLOPs per trained token: 6 x the parameters that multiply a token's
activations (every projection of every layer and the output head once),
plus 12 x layers x sequence x the attention width for the score and value
products (the causal mask not subtracted).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import reference as R
import weights as W
from harness import HarnessError

F32, HI, ENTRY, EXIT, POINT = R.F32, R.HI, R.ENTRY, R.EXIT, R.POINT
BF16_BYTES, F32_BYTES = 2, 4
LAYER_SITES = (("block", ENTRY), ("attn.out", POINT), ("ffn.out", POINT),
               ("block", EXIT))
# what the reference computes: these keys with these values, and sizes
COMPUTED = {"family": "dense", "norm": "rmsnorm", "rope_kind": "rope",
            "tie_embeddings": True, "act": "swiglu"}
SIZES = ("num_layers", "d_model", "num_heads", "num_kv_heads", "d_ff",
         "vocab_size", "qkv_bias", "norm_eps", "rope_theta")
OPTIONAL = {"head_dim", "dtype", "num_experts"}


def check_config(model: dict) -> None:
    for key, value in COMPUTED.items():
        if model.get(key) != value:
            raise HarnessError(f"arch dense computes {key} = {value!r}; the "
                               f"model states {model.get(key)!r}")
    for key in SIZES:
        if key not in model:
            raise HarnessError(f"arch dense needs the model key {key!r}")
    if model.get("num_experts", 0) > 0:
        raise HarnessError(f"arch dense has no experts; the model states "
                           f"num_experts = {model['num_experts']!r}")
    for key in model:
        if key not in COMPUTED and key not in SIZES and key not in OPTIONAL:
            raise HarnessError(f"arch dense does not compute the model key "
                               f"{key!r}")


def head_dim(model: dict) -> int:
    return model.get("head_dim") or model["d_model"] // model["num_heads"]


# ---------------------------------------------------------------- weights

def _tree(words, model: dict):
    D, L = model["d_model"], model["num_layers"]
    H, KH, hd, Fh = (model["num_heads"], model["num_kv_heads"],
                     head_dim(model), model["d_ff"])
    keys = iter(jax.random.split(W.seed_key(words), 16))

    def normal(shape, scale):
        return jax.random.normal(next(keys), shape, F32) * scale

    attn = {"wq": normal((L, D, H * hd), 1 / math.sqrt(D)),
            "wk": normal((L, D, KH * hd), 1 / math.sqrt(D)),
            "wv": normal((L, D, KH * hd), 1 / math.sqrt(D)),
            "wo": normal((L, H * hd, D), 1 / math.sqrt(H * hd))}
    if model["qkv_bias"]:
        attn.update(bq=normal((L, H * hd), 0.02),
                    bk=normal((L, KH * hd), 0.02),
                    bv=normal((L, KH * hd), 0.02))
    block = {"norm1": {"scale": jnp.ones((L, D), F32)},
             "attn": attn,
             "norm2": {"scale": jnp.ones((L, D), F32)},
             "mlp": {"wi": normal((L, D, Fh), 1 / math.sqrt(D)),
                     "wg": normal((L, D, Fh), 1 / math.sqrt(D)),
                     "wo": normal((L, Fh, D), 1 / math.sqrt(Fh))}}
    return {"embed": {"embedding": normal((W.padded_vocab(model), D), 0.02)},
            "stack": {"blocks": [block]},
            "final_norm": {"scale": jnp.ones((D,), F32)}}


def make_params(seed: int, model: dict, dtype: str = "float32"):
    return W.seeded_tree(_tree, seed, model, dtype)


def change_norms(params, seed: int, model: dict, dtype: str = "float32"):
    return W.tree_change_norms(_tree, params, seed, model, dtype)


# -------------------------------------------------------------- reference

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
    entry = R.partials(x)
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
    stats = jnp.stack([entry, R.partials(attn_out), R.partials(ffn_out),
                       R.partials(x)])
    return x, jax.lax.stop_gradient(stats)


def _row_loss(params, tokens, labels, model):
    """Summed next-token loss of one row, and its probe-site partials."""
    V = model["vocab_size"]
    emb = params["embed"]["embedding"]
    x = emb[tokens]
    embed_part = R.partials(x)
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
           "logits": R.partials(logits)}
    return jnp.sum(nll), jax.lax.stop_gradient(aux)


@functools.lru_cache(maxsize=None)
def _grad_row_fn(model_items: tuple):
    model = dict(model_items)
    grad = jax.value_and_grad(lambda p, t, l: _row_loss(p, t, l, model),
                              has_aux=True)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def grad_row(params, gsum, tokens, labels):
        (loss, aux), g = grad(params, tokens, labels)
        return loss, aux, jax.tree.map(jnp.add, gsum, g)

    def row(params, gsum, tokens, labels):
        loss, aux, gsum = grad_row(params, gsum, tokens, labels)
        aux = jax.tree.map(np.asarray, aux)
        # in the order of layer_sites: embed.out, each layer's four, logits
        return loss, np.concatenate([aux["embed"][None],
                                     aux["layers"].reshape(-1, 7),
                                     aux["logits"][None]]), gsum
    return row


def layer_sites(model: dict) -> list:
    return ([("embed.out", POINT, 0)]
            + [(site, kind, layer) for layer in range(model["num_layers"])
               for site, kind in LAYER_SITES]
            + [("logits", POINT, 0)])


def train_steps(seed: int, model: dict, train: dict, batches: list) -> dict:
    return R.run_steps(make_params(seed, model), train, batches,
                       _grad_row_fn(tuple(sorted(model.items()))),
                       layer_sites(model),
                       lambda params: change_norms(params, seed, model))


# ------------------------------------------------------------------ work

def matmul_params(model: dict) -> int:
    D, H, KH, hd = (model["d_model"], model["num_heads"],
                    model["num_kv_heads"], head_dim(model))
    attn = D * H * hd + 2 * D * KH * hd + H * hd * D
    mlp = 3 * D * model["d_ff"]
    return model["num_layers"] * (attn + mlp) + D * model["vocab_size"]


def flops_per_token(model: dict, seq_len: int) -> float:
    attn_width = model["num_heads"] * head_dim(model)
    return 6.0 * matmul_params(model) \
        + 12.0 * model["num_layers"] * seq_len * attn_width


def site_bytes(model: dict, batch: int, seq_len: int) -> dict:
    """The residual stream and the sublayer outputs are bf16, at every
    layer; the logits are f32 over the padded vocabulary the step
    computes; loss and gradient norm are one f32 each."""
    act = batch * seq_len * model["d_model"] * BF16_BYTES
    per_layer = model["num_layers"] * act
    return {("block", ENTRY): per_layer, ("block", EXIT): per_layer,
            ("attn.out", POINT): per_layer, ("ffn.out", POINT): per_layer,
            ("embed.out", POINT): act,
            ("logits", POINT): batch * seq_len * W.padded_vocab(model)
            * F32_BYTES,
            ("loss", POINT): F32_BYTES, ("grad.norm", POINT): F32_BYTES}
