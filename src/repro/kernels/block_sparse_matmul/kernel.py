"""Block-sparse weight matmul Pallas kernel (VCSEL power gating, MXU-tile
granularity — DESIGN.md §2).

y[M, N] = x[M, K] @ W,  W balanced block-sparse: for every N-block j only the
R highest-norm K-blocks survive pruning (``core.sonic_layers.make_block_sparse``).

  values  (Nb, R, bk, bn)  — kept blocks, dense inside
  indices (Nb, R) int32    — source K-block of each kept block (ascending)

Grid = (M/bm, Nb, R).  The x BlockSpec's index map reads ``indices`` via
scalar prefetch, so only the K-blocks that survive pruning are ever DMA'd
HBM→VMEM: compute AND weight traffic scale with (1 − sparsity).  Zero blocks
cost nothing — the dataflow skip SONIC implements with per-wavelength gating,
restructured to the systolic array's natural tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, x_ref, v_ref, o_ref, *, r_steps: int):
    r = pl.program_id(2)

    @pl.when(r == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32),
        v_ref[0].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )


def block_sparse_matmul_pallas(
    x: jax.Array,  # (M, K)
    values: jax.Array,  # (Nb, R, bk, bn)
    indices: jax.Array,  # (Nb, R) int32
    *,
    bm: int = 256,
    interpret: bool,
) -> jax.Array:
    """Returns y (M, N) fp32."""
    m, k = x.shape
    nb, r, bk, bn = values.shape
    assert k == 0 or k % bk == 0, (k, bk)
    bm = min(bm, m)
    assert m % bm == 0, (m, bm)
    vflat = values.reshape(nb * r, bk, bn)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m // bm, nb, r),
        in_specs=[
            # x block (bm, bk) at K-block indices[j, rr] — the sparse gather
            pl.BlockSpec((bm, bk), lambda i, j, rr, idx: (i, idx[j, rr])),
            # value block (1, bk, bn) at flat position j*R + rr
            pl.BlockSpec((1, bk, bn), lambda i, j, rr, idx: (j * r + rr, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, rr, idx: (i, j)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, r_steps=r),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, nb * bn), jnp.float32),
        interpret=interpret,
    )(indices, x, vflat)


def _int8_kernel(idx_ref, x_ref, v_ref, s_ref, o_ref, *, r_steps: int):
    j = pl.program_id(1)
    r = pl.program_id(2)

    @pl.when(r == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # dequant-inside-kernel: the int8 block is scaled against its per-block
    # fp32 scale at the MXU's edge — weights stay int8 in HBM and VMEM
    w = v_ref[0].astype(jnp.float32) * s_ref[j * r_steps + r]
    o_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32), w, preferred_element_type=jnp.float32
    )


def block_sparse_matmul_int8_pallas(
    x: jax.Array,  # (M, K)
    values: jax.Array,  # (Nb, R, bk, bn) int8
    scales: jax.Array,  # (Nb, R) fp32 per-block dequant scales
    indices: jax.Array,  # (Nb, R) int32
    *,
    bm: int = 256,
    interpret: bool,
) -> jax.Array:
    """Int8-weight variant (ISSUE 10): same sparse gather as the fp kernel,
    but kept blocks travel HBM→VMEM as int8 (4× fewer weight bytes than fp32)
    and dequantize in-kernel against ``scales``.  The scales are tiny (one
    fp32 per kept block) and sit whole in SMEM, flattened to (Nb·R,), so the
    kernel reads its block's scale as a scalar.  Returns y (M, N) fp32."""
    m, k = x.shape
    nb, r, bk, bn = values.shape
    assert k == 0 or k % bk == 0, (k, bk)
    bm = min(bm, m)
    assert m % bm == 0, (m, bm)
    vflat = values.reshape(nb * r, bk, bn)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m // bm, nb, r),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, rr, idx: (i, idx[j, rr])),
            pl.BlockSpec((1, bk, bn), lambda i, j, rr, idx: (j * r + rr, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, rr, idx: (i, j)),
    )
    return pl.pallas_call(
        functools.partial(_int8_kernel, r_steps=r),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, nb * bn), jnp.float32),
        interpret=interpret,
    )(indices, x, vflat, scales.reshape(nb * r))
