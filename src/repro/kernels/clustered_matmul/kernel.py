"""Clustered-weight matmul Pallas kernel.

y[M, N] = x[M, K] @ dequant(indices[K, N], codebook[C])

The weight tensor never exists in HBM as floats: each grid step DMAs an
int8 (bk × bn) index tile into VMEM (2× smaller than bf16 traffic; the packed
6-bit variant the paper's 64-cluster result implies is 2.7×), dequantizes
against the codebook held in VMEM as 128-lane rows, and feeds the MXU.

Grid = (M/bm, N/bn, K/bk) with K innermost; the fp32 output tile (i, j) is
revisited across the K steps and accumulates in place (standard Pallas matmul
pattern — the tile stays resident in VMEM between steps).  Tile defaults
(bm, bn, bk) = (256, 256, 512): working set ≈ x 256·512·2B + idx 512·256·1B +
acc 256·256·4B ≈ 0.6 MB « 16 MB VMEM, all dims 128-aligned for the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


LANES = 128  # codebook strip width: one vreg lane row holds 128 entries


def codebook_row(codebook: jax.Array) -> jax.Array:
    """(C,) centroids → the (1, 128·⌈C/128⌉) fp32 row ``codebook_dequant``
    reads: one 128-lane strip per 128 centroids."""
    (c,) = codebook.shape
    width = -(-c // LANES) * LANES
    return jnp.pad(codebook.astype(jnp.float32), (0, width - c))[None]


def codebook_dequant(cb_ref, idx: jax.Array) -> jax.Array:
    """idx (bk, bn) int32 cluster ids → their (bk, bn) fp32 centroids, from
    the ``codebook_row`` held in ``cb_ref``.

    A lane gather per 128-column strip of ids: each codebook strip is
    broadcast down the bk sublanes and indexed by the ids' low 7 bits
    (``take_along_axis`` on two 2-D operands, which Mosaic lowers to an
    in-register gather).  Past 128 centroids every further codebook strip
    takes its gather where the id falls in its range."""
    bk, bn = idx.shape
    n_strips = cb_ref.shape[1] // LANES
    tables = [jnp.broadcast_to(cb_ref[:, t * LANES:(t + 1) * LANES],
                               (bk, LANES)) for t in range(n_strips)]
    cols = []
    for c in range(0, bn, LANES):
        ids = idx[:, c:c + LANES]
        lane = ids % LANES if n_strips > 1 else ids
        w = jnp.take_along_axis(tables[0], lane, axis=1)
        for t in range(1, n_strips):
            w = jnp.where(ids >= t * LANES,
                          jnp.take_along_axis(tables[t], lane, axis=1), w)
        cols.append(w)
    return cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)


def _kernel(x_ref, idx_ref, cb_ref, o_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = codebook_dequant(cb_ref, idx_ref[...].astype(jnp.int32))
    o_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32), w, preferred_element_type=jnp.float32
    )


def clustered_matmul_pallas(
    x: jax.Array,  # (M, K)
    indices: jax.Array,  # (K, N) int8/int32
    codebook: jax.Array,  # (C,) fp32
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    interpret: bool,
) -> jax.Array:
    """Returns y (M, N) fp32 (cast at the call site if bf16 is wanted)."""
    m, k = x.shape
    k2, n = indices.shape
    assert k == k2, (x.shape, indices.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    nk = k // bk
    cb = codebook_row(codebook)

    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec(cb.shape, lambda i, j, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, indices, cb)
