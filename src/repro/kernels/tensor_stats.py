"""Fused tensor-statistics Pallas kernel — the probe hot path.

One pass over HBM computes sum/sumsq/min/max/nan/inf simultaneously, so an
attached probe costs ~1 read of the tensor (memory-roofline optimal) instead
of 6 separate reductions. TPU adaptation of the paper's JIT'd probe body:
the working set is tiled (BR, 1024) into VMEM; lane dim 1024 = 8×128 keeps
the VPU fully packed; the grid walks rows sequentially and accumulates into
(1,1) scalar outputs held in SMEM (legal on TPU because the grid is
sequential; Mosaic cannot store scalars to VMEM).

Layout: the wrapper flattens + zero-pads x to (R, 1024); a global-index mask
inside the kernel excludes padding from every statistic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 1024       # 8 sublanes * 128 lanes
DEF_BLOCK_ROWS = 8


def _kernel(x_ref, sum_ref, ssq_ref, min_ref, max_ref, nan_ref, inf_ref,
            *, numel: int, lanes: int):
    i = pl.program_id(0)
    br = x_ref.shape[0]
    x = x_ref[...].astype(jnp.float32)

    # mask out padding via global element index
    row0 = i * br
    ridx = jax.lax.broadcasted_iota(jnp.int32, (br, lanes), 0)
    cidx = jax.lax.broadcasted_iota(jnp.int32, (br, lanes), 1)
    gidx = (row0 + ridx) * lanes + cidx
    pad = gidx >= numel

    nan = jnp.isnan(x) & ~pad
    inf = jnp.isinf(x) & ~pad
    bad = nan | inf | pad
    z = jnp.where(bad, 0.0, x)

    psum = jnp.sum(z)
    pssq = jnp.sum(z * z)
    pmin = jnp.min(jnp.where(bad, jnp.inf, x))
    pmax = jnp.max(jnp.where(bad, -jnp.inf, x))
    pnan = jnp.sum(nan.astype(jnp.float32))
    pinf = jnp.sum(inf.astype(jnp.float32))

    @pl.when(i == 0)
    def _init():
        sum_ref[0, 0] = jnp.float32(0.0)
        ssq_ref[0, 0] = jnp.float32(0.0)
        min_ref[0, 0] = jnp.float32(jnp.inf)
        max_ref[0, 0] = jnp.float32(-jnp.inf)
        nan_ref[0, 0] = jnp.float32(0.0)
        inf_ref[0, 0] = jnp.float32(0.0)

    sum_ref[0, 0] += psum
    ssq_ref[0, 0] += pssq
    min_ref[0, 0] = jnp.minimum(min_ref[0, 0], pmin)
    max_ref[0, 0] = jnp.maximum(max_ref[0, 0], pmax)
    nan_ref[0, 0] += pnan
    inf_ref[0, 0] += pinf


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def tensor_stats_pallas(x, *, block_rows: int = DEF_BLOCK_ROWS,
                        interpret: bool = False) -> dict:
    numel = x.size
    xf = jnp.asarray(x, jnp.float32).reshape(-1)
    rows = max(1, -(-numel // LANES))
    rows_pad = -(-rows // block_rows) * block_rows
    xf = jnp.pad(xf, (0, rows_pad * LANES - numel))
    xf = xf.reshape(rows_pad, LANES)

    grid = (rows_pad // block_rows,)
    scalar_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    out_shape = [jax.ShapeDtypeStruct((1, 1), jnp.float32)] * 6
    # the package runs with x64 on, which makes the grid index and the
    # index-map results i64; Mosaic only legalizes i32 there
    with jax.enable_x64(False):
        s, ss, mn, mx, nan, inf = pl.pallas_call(
            functools.partial(_kernel, numel=numel, lanes=LANES),
            grid=grid,
            in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))],
            out_specs=[scalar_spec] * 6,
            out_shape=out_shape,
            interpret=interpret,
        )(xf)

    s, ss = s[0, 0], ss[0, 0]
    mn, mx = mn[0, 0], mx[0, 0]
    nan_c, inf_c = nan[0, 0], inf[0, 0]
    n_ok = jnp.maximum(jnp.float32(numel) - nan_c - inf_c, 1.0)
    any_ok = (nan_c + inf_c) < jnp.float32(numel)
    mn = jnp.where(any_ok, mn, 0.0)
    mx = jnp.where(any_ok, mx, 0.0)
    return {
        "mean": s / n_ok,
        "rms": jnp.sqrt(ss / n_ok),
        "min": mn,
        "max": mx,
        "absmax": jnp.maximum(jnp.abs(mn), jnp.abs(mx)),
        "nan_cnt": nan_c.astype(jnp.int64),
        "inf_cnt": inf_c.astype(jnp.int64),
    }
