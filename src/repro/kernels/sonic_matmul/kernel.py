"""Fused SONIC serving matmul: block-sparse structure × clustered int8 values.

This is the paper's full co-design in one kernel (beyond-paper fusion —
SONIC's photonic core applies the two mechanisms in separate hardware stages):

  * C1/C4 block sparsity — only surviving K-blocks are DMA'd (scalar-prefetch
    index map), so weight traffic ∝ (1 − sparsity);
  * C2 clustering — surviving blocks travel as int8 cluster indices (2× under
    bf16; the 6-bit packing the paper's 64 clusters allow would give 2.7×)
    and are dequantized against the VMEM-resident codebook at the MXU's edge.

Combined HBM weight bytes vs dense bf16: (1 − s) / 2 — e.g. s = 0.75 ⇒ 8×.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.clustered_matmul.kernel import codebook_dequant, codebook_row


def _matvec_kernel(idx_ref, x_ref, v_ref, cb_ref, o_ref):
    r = pl.program_id(1)

    @pl.when(r == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = codebook_dequant(cb_ref, v_ref[0].astype(jnp.int32))
    o_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32), w, preferred_element_type=jnp.float32
    )


def sonic_matvec_pallas(
    x: jax.Array,  # (M, K) with M below the tile threshold (decode rows)
    idx_values: jax.Array,  # (Nb, R, bk, bn) int8
    codebook: jax.Array,  # (C,) fp32
    indices: jax.Array,  # (Nb, R) int32
    *,
    interpret: bool,
) -> jax.Array:
    """Decode-shaped fused matvec: grid over (Nb, R) only — no M-tiling.

    The matmul kernel below pads decode activations (M = B·1, typically ≤ 8)
    up to a bm-row tile, spending MXU cycles and x-traffic on zero rows.
    Here the whole activation sliver rides along every grid step as a
    (M, bk) block and only the *kept* K-blocks are gathered via a
    scalar-prefetch index map — per-token HBM weight
    bytes stay at the (1 − s)/2 the SONIC format promises.
    """
    m, k = x.shape
    nb, r, bk, bn = idx_values.shape
    assert k % bk == 0, (k, bk)
    vflat = idx_values.reshape(nb * r, bk, bn)
    cb = codebook_row(codebook)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, r),
        in_specs=[
            pl.BlockSpec((m, bk), lambda j, rr, idx: (0, idx[j, rr])),
            pl.BlockSpec((1, bk, bn), lambda j, rr, idx: (j * r + rr, 0, 0)),
            pl.BlockSpec(cb.shape, lambda j, rr, idx: (0, 0)),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda j, rr, idx: (0, j)),
    )
    return pl.pallas_call(
        _matvec_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, nb * bn), jnp.float32),
        interpret=interpret,
    )(indices, x, vflat, cb)


def _matvec_int8_kernel(idx_ref, x_ref, v_ref, s_ref, o_ref, *, r_steps: int):
    j = pl.program_id(0)
    r = pl.program_id(1)

    @pl.when(r == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # dequant-inside-kernel against the per-block scale (ISSUE 10): the
    # kept block arrives as raw int8 and is scaled at the MXU's edge
    w = v_ref[0].astype(jnp.float32) * s_ref[j * r_steps + r]
    o_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32), w, preferred_element_type=jnp.float32
    )


def sonic_matvec_int8_pallas(
    x: jax.Array,  # (M, K) with M below the tile threshold (decode rows)
    values: jax.Array,  # (Nb, R, bk, bn) int8
    scales: jax.Array,  # (Nb, R) fp32 per-block dequant scales
    indices: jax.Array,  # (Nb, R) int32
    *,
    interpret: bool,
) -> jax.Array:
    """Decode-shaped int8-weight matvec: same no-M-padding grid over (Nb, R)
    as ``sonic_matvec_pallas``, but kept blocks stream as raw int8 against a
    per-block fp32 scale instead of cluster ids against a codebook — the
    scales (one fp32 per kept block) sit whole in SMEM, flattened to
    (Nb·R,), and each step reads its block's scale as a scalar."""
    m, k = x.shape
    nb, r, bk, bn = values.shape
    assert k % bk == 0, (k, bk)
    vflat = values.reshape(nb * r, bk, bn)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, r),
        in_specs=[
            pl.BlockSpec((m, bk), lambda j, rr, idx: (0, idx[j, rr])),
            pl.BlockSpec((1, bk, bn), lambda j, rr, idx: (j * r + rr, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda j, rr, idx: (0, j)),
    )
    return pl.pallas_call(
        functools.partial(_matvec_int8_kernel, r_steps=r),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, nb * bn), jnp.float32),
        interpret=interpret,
    )(indices, x, vflat, scales.reshape(nb * r))


def _kernel(idx_ref, x_ref, v_ref, cb_ref, o_ref):
    r = pl.program_id(2)

    @pl.when(r == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = codebook_dequant(cb_ref, v_ref[0].astype(jnp.int32))
    o_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32), w, preferred_element_type=jnp.float32
    )


def sonic_matmul_pallas(
    x: jax.Array,  # (M, K)
    idx_values: jax.Array,  # (Nb, R, bk, bn) int8
    codebook: jax.Array,  # (C,) fp32
    indices: jax.Array,  # (Nb, R) int32
    *,
    bm: int = 256,
    interpret: bool,
) -> jax.Array:
    m, k = x.shape
    nb, r, bk, bn = idx_values.shape
    bm = min(bm, m)
    assert m % bm == 0 and k % bk == 0, (m, bm, k, bk)
    vflat = idx_values.reshape(nb * r, bk, bn)
    cb = codebook_row(codebook)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m // bm, nb, r),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, rr, idx: (i, idx[j, rr])),
            pl.BlockSpec((1, bk, bn), lambda i, j, rr, idx: (j * r + rr, 0, 0)),
            pl.BlockSpec(cb.shape, lambda i, j, rr, idx: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, rr, idx: (i, j)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, nb * bn), jnp.float32),
        interpret=interpret,
    )(indices, x, vflat, cb)
