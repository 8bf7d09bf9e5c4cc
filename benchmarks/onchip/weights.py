"""Weights and token batches made from the run's seed.

Both the program under test and the plain reference take their weights
from `make_params` and their batches from `make_batch`, so the two start
from the same numbers while neither takes anything the other made.

The parameter tree has the layout the program's dense transformer keeps:
layers stacked on a leading axis, the embedding padded to a multiple of
256 rows (rows past the vocabulary are never looked up and are masked
out of the logits).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def padded_vocab(model: dict) -> int:
    return -(-model["vocab_size"] // 256) * 256


def head_dim(model: dict) -> int:
    return model.get("head_dim") or model["d_model"] // model["num_heads"]


def seed_words(seed: int) -> np.ndarray:
    """The seed as two uint32 words: seeds above 32 bits stay distinct."""
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed {seed} is outside 0 .. 2**64-1")
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def _key(words):
    key = jax.random.PRNGKey(0)
    return jax.random.fold_in(jax.random.fold_in(key, words[0]), words[1])


def _params(words, *, model_items: tuple):
    model = dict(model_items)
    D, L = model["d_model"], model["num_layers"]
    H, KH, hd, Fh = (model["num_heads"], model["num_kv_heads"],
                     head_dim(model), model["d_ff"])
    keys = iter(jax.random.split(_key(words), 16))

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
    return {"embed": {"embedding": normal((padded_vocab(model), D), 0.02)},
            "stack": {"blocks": [block]},
            "final_norm": {"scale": jnp.ones((D,), F32)}}


@functools.lru_cache(maxsize=None)
def _params_fn(model_items: tuple, dtype: str):
    def make(words):
        p = _params(words, model_items=model_items)
        return jax.tree.map(lambda a: a.astype(dtype), p)
    return jax.jit(make)


def make_params(seed: int, model: dict, dtype: str = "float32"):
    """The whole parameter tree on the default device, in one jitted call."""
    return _params_fn(tuple(sorted(model.items())), dtype)(
        jnp.asarray(seed_words(seed)))


@functools.lru_cache(maxsize=None)
def _change_fn(model_items: tuple, dtype: str):
    start = _params_fn(model_items, dtype)

    def norms(params, words):
        return jax.tree.map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a.astype(F32)
                                                     - b.astype(F32)))),
            params, start(words))
    return jax.jit(norms)


def change_norms(params, seed: int, model: dict, dtype: str = "float32"):
    """Per-leaf L2 norm of `params` less the seed's starting parameters, in
    one jitted call that makes the start anew: no second parameter tree is
    held beside `params` between calls."""
    return _change_fn(tuple(sorted(model.items())), dtype)(
        params, jnp.asarray(seed_words(seed)))


def make_batch(seed: int, step: int, batch: int, seq_len: int,
               vocab_size: int) -> dict:
    """Batch `step` of the run: token ids uniform over the vocabulary, each
    row its own draw; the label of a position is the next token, and the
    last position has none (-1)."""
    rng = np.random.default_rng([int(w) for w in seed_words(seed)] + [step])
    tokens = rng.integers(0, vocab_size, (batch, seq_len), dtype=np.int32)
    labels = np.roll(tokens, -1, axis=1)
    labels[:, -1] = -1
    return {"tokens": tokens, "labels": labels}
