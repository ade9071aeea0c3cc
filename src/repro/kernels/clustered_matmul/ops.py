"""Public jit'd wrapper for the clustered-matmul kernel.

Accepts any (..., K) activation against (K, N) int8 indices + (C,) codebook
(the ``ClusteredWeight`` storage from ``repro.core.clustering``).  The
Pallas kernel is compiled on the TPU and interpreted elsewhere
(``repro.kernels.dispatch``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.clustered_matmul.kernel import clustered_matmul_pallas
from repro.kernels.dispatch import run_kernel


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def clustered_matmul(
    x: jax.Array,
    indices: jax.Array,
    codebook: jax.Array,
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
) -> jax.Array:
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    # pad M to the tile multiple (K/N must already be tile-aligned — true for
    # every assigned arch: all d_model/d_ff are multiples of 128)
    bm_eff = min(bm, max(8, m))
    pad_m = (-m) % bm_eff
    if pad_m:
        x2 = jnp.pad(x2, ((0, pad_m), (0, 0)))
    y = run_kernel(
        clustered_matmul_pallas,
        x2,
        indices,
        codebook.astype(jnp.float32),
        bm=bm_eff,
        bn=bn,
        bk=bk,
    )
    if pad_m:
        y = y[:m]
    return y.reshape(*lead, indices.shape[1]).astype(x.dtype)
