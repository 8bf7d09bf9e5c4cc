"""Weights and token batches made from the run's seed.

Both the program under test and the plain reference take their weights
from the architecture module's `make_params` and their batches from
`make_batch`, so the two start from the same numbers while neither takes
anything the other made.

What is generic lives here: the seed as words and a key, jitting an
architecture's tree maker so that the whole tree is made on the device
in one call, the per-leaf change from the seed's tree, the program's
vocabulary padding, and the batches. The tree itself is the
architecture module's (`arch/`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def padded_vocab(model: dict) -> int:
    """The vocabulary padded to a multiple of 256 rows, as the program
    keeps its embedding (rows past the vocabulary are never looked up and
    are masked out of the logits)."""
    return -(-model["vocab_size"] // 256) * 256


def seed_words(seed: int) -> np.ndarray:
    """The seed as two uint32 words: seeds above 32 bits stay distinct."""
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed {seed} is outside 0 .. 2**64-1")
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def seed_key(words):
    """The PRNG key of the seed's words, inside a jitted tree maker."""
    key = jax.random.PRNGKey(0)
    return jax.random.fold_in(jax.random.fold_in(key, words[0]), words[1])


@functools.lru_cache(maxsize=None)
def _params_fn(tree, model_items: tuple, dtype: str):
    def make(words):
        p = tree(words, dict(model_items))
        return jax.tree.map(lambda a: a.astype(dtype), p)
    return jax.jit(make)


def seeded_tree(tree, seed: int, model: dict, dtype: str = "float32"):
    """`tree(words, model)`, an architecture's float32 parameter tree from
    the seed's words, in `dtype` on the default device, in one jitted
    call."""
    return _params_fn(tree, tuple(sorted(model.items())), dtype)(
        jnp.asarray(seed_words(seed)))


@functools.lru_cache(maxsize=None)
def _change_fn(tree, model_items: tuple, dtype: str):
    start = _params_fn(tree, model_items, dtype)

    def norms(params, words):
        return jax.tree.map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a.astype(F32)
                                                     - b.astype(F32)))),
            params, start(words))
    return jax.jit(norms)


def tree_change_norms(tree, params, seed: int, model: dict,
                      dtype: str = "float32"):
    """Per-leaf L2 norm of `params` less the seed's starting parameters
    (`seeded_tree`), in one jitted call that makes the start anew: no
    second parameter tree is held beside `params` between calls."""
    return _change_fn(tree, tuple(sorted(model.items())), dtype)(
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
