"""Public jit'd wrapper for the block-sparse matmul kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.sonic_layers import BlockSparseWeight, BlockSparseWeightInt8
from repro.kernels.block_sparse_matmul.kernel import (
    block_sparse_matmul_int8_pallas,
    block_sparse_matmul_pallas,
)
from repro.kernels.dispatch import run_kernel


@functools.partial(jax.jit, static_argnames=("bm",))
def block_sparse_matmul(
    x: jax.Array,  # (..., K)
    w: BlockSparseWeight,
    *,
    bm: int = 256,
) -> jax.Array:
    lead = x.shape[:-1]
    k = x.shape[-1]
    kb_expect = w.k_blocks * w.block_shape[0]
    assert k == kb_expect, (k, kb_expect)
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    bm_eff = min(bm, max(8, m))
    pad_m = (-m) % bm_eff
    if pad_m:
        x2 = jnp.pad(x2, ((0, pad_m), (0, 0)))
    y = run_kernel(block_sparse_matmul_pallas, x2, w.values, w.indices,
                   bm=bm_eff)
    if pad_m:
        y = y[:m]
    n = w.values.shape[0] * w.block_shape[1]
    return y.reshape(*lead, n).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("bm",))
def block_sparse_matmul_int8(
    x: jax.Array,  # (..., K)
    w: BlockSparseWeightInt8,
    *,
    bm: int = 256,
) -> jax.Array:
    """Int8-weight block-sparse matmul (dequant fused in-kernel)."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    kb_expect = w.k_blocks * w.block_shape[0]
    assert k == kb_expect, (k, kb_expect)
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    bm_eff = min(bm, max(8, m))
    pad_m = (-m) % bm_eff
    if pad_m:
        x2 = jnp.pad(x2, ((0, pad_m), (0, 0)))
    y = run_kernel(block_sparse_matmul_int8_pallas, x2, w.values, w.scales,
                   w.indices, bm=bm_eff)
    if pad_m:
        y = y[:m]
    n = w.values.shape[0] * w.block_shape[1]
    return y.reshape(*lead, n).astype(x.dtype)
