"""jit'd public wrappers for the Pallas kernels, with impl dispatch.

impl:
  'ref'               pure-jnp oracle (default on CPU — this container)
  'pallas'            compiled Pallas (TPU target)
  'pallas_interpret'  Pallas kernel body interpreted on CPU (tests)

Default comes from REPRO_KERNEL_IMPL or the backend: TPU->pallas, else ref.
On a TPU backend the default is always 'pallas': REPRO_KERNEL_IMPL naming
'ref' or 'pallas_interpret' there raises rather than quietly running the
jnp or interpreted path on the chip. An explicit `impl=` argument is
honoured on every backend (references in tests and smoke checks).
"""
from __future__ import annotations

import os

import jax

from . import hash_update, ref, ringbuf_emit, tensor_stats as ts

_DEFAULT = None


def default_impl() -> str:
    global _DEFAULT
    if _DEFAULT is None:
        env = os.environ.get("REPRO_KERNEL_IMPL")
        on_tpu = jax.default_backend() == "tpu"
        if env and on_tpu and env != "pallas":
            raise ValueError(
                f"REPRO_KERNEL_IMPL={env!r} on a TPU backend: only 'pallas' "
                f"runs on the chip (pass impl= explicitly for a reference)")
        _DEFAULT = env or ("pallas" if on_tpu else "ref")
    return _DEFAULT


def set_default_impl(impl: str | None):
    global _DEFAULT
    _DEFAULT = impl


def tensor_stats(x, impl: str | None = None) -> dict:
    impl = impl or default_impl()
    if impl == "ref":
        return ref.tensor_stats(x)
    return ts.tensor_stats_pallas(x, interpret=(impl == "pallas_interpret"))


def log2_histogram(x, n_bins: int = 64, impl: str | None = None):
    # histogram builds on the same pass; ref-only jnp fallback provided
    return ref.log2_histogram(x, n_bins)


def hash_fetch_add_batch(keys_tbl, used_tbl, vals_tbl, keys, deltas, valid,
                         impl: str | None = None):
    impl = impl or default_impl()
    if impl == "ref":
        return ref.hash_fetch_add_batch(keys_tbl, used_tbl, vals_tbl,
                                        keys, deltas, valid)
    return hash_update.hash_fetch_add_batch_pallas(
        keys_tbl, used_tbl, vals_tbl, keys, deltas, valid,
        interpret=(impl == "pallas_interpret"))


def ringbuf_emit_batch(data, head, rows, valid, impl: str | None = None):
    impl = impl or default_impl()
    if impl == "ref":
        return ref.ringbuf_emit_batch(data, head, rows, valid)
    return ringbuf_emit.ringbuf_emit_batch_pallas(
        data, head, rows, valid, interpret=(impl == "pallas_interpret"))
