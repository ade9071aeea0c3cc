"""Compressed sparse matvec Pallas kernel — SONIC's FC dataflow (§III.C).

y[B, N] = Σ_c x_nz[:, c] · Wt[idx[c], :]

This is the zero-compression product of Fig. 1(b): the activation vector is
dense after compression (x_nz), and only the weight rows the surviving
activations touch are read.  Wt is stored input-major as a row table
(K, 1, N) (``row_table``) and stays in HBM (``memory_space=pl.ANY``);
``idx`` arrives by scalar prefetch, and the kernel DMAs each kept row's
bn-column stripe into a VMEM scratch — like the photonic VDU that never
fires a VCSEL for a zero, untouched weight rows are never read.

Why a row table: a (K, N) array sits in HBM in (8, 128) tiles, so the
smallest row slice a DMA may take is 8 rows.  With a unit second-minor
dimension the TPU lays each row out as its own (1, 128)-tiled stripe, with
no padding, and one kept row is one DMA.  This holds for 32-bit weights;
16-bit rows pack two to a sublane and cannot be copied one at a time.

Grid = (N/bn, ⌈knz/tk⌉), kept rows innermost: step (j, c) copies rows
idx[c·tk : (c+1)·tk] of column tile j into a (tk, 1, bn) scratch, one row
DMA each, and accumulates x_nz[:, c·tk : (c+1)·tk] @ rows into the resident
(B, bn) output tile.  Weight bytes read are knz · N · itemsize, whatever the
index set.  When knz > tk it is padded to whole chunks with zero
activations against row 0, which add nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def row_table(wt: jax.Array) -> jax.Array:
    """(K, N) weight → the (K, 1, N) row table the kernel gathers from.

    Convert once, where the weight is stored: on the TPU the two layouts
    differ, so converting on every call copies the whole weight."""
    k, n = wt.shape
    return wt.reshape(k, 1, n)


def _kernel(idx_ref, x_ref, w_hbm, o_ref, rows, sem, *, tk: int, bn: int):
    j, c = pl.program_id(0), pl.program_id(1)

    def copy(r):
        return pltpu.make_async_copy(
            w_hbm.at[pl.ds(idx_ref[c * tk + r], 1), :, pl.ds(j * bn, bn)],
            rows.at[pl.ds(r, 1)],
            sem,
        )

    @pl.loop(0, tk)
    def _start(r):
        copy(r).start()

    @pl.when(c == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.loop(0, tk)
    def _wait(r):
        copy(r).wait()

    o_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32),
        rows[:, 0, :].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )


def sparse_matvec_pallas(
    x_nz: jax.Array,  # (B, knz)
    idx: jax.Array,  # (knz,) int32
    rows: jax.Array,  # (K, 1, N) from row_table
    *,
    bn: int = 512,
    tk: int = 256,
    interpret: bool,
) -> jax.Array:
    """Returns y (B, N) fp32."""
    b, knz = x_nz.shape
    _, _, n = rows.shape
    bn = min(bn, n)
    assert n % bn == 0, (n, bn)
    tk = min(tk, knz)
    pad = (-knz) % tk
    if pad:
        x_nz = jnp.pad(x_nz, ((0, 0), (0, pad)))
        idx = jnp.pad(idx, (0, pad))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // bn, (knz + pad) // tk),
        in_specs=[
            pl.BlockSpec((b, tk), lambda j, c, idx: (0, c)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((b, bn), lambda j, c, idx: (0, j)),
        scratch_shapes=[
            pltpu.VMEM((tk, 1, bn), rows.dtype),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, tk=tk, bn=bn),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        interpret=interpret,
    )(idx, x_nz, rows)
