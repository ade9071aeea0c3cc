"""Public wrappers: compressed matvec + the full top-k compress-then-multiply
op (SONIC §III.C as one jit'd call)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import run_kernel
from repro.kernels.sparse_matvec.kernel import row_table, sparse_matvec_pallas


@functools.partial(jax.jit, static_argnames=("bn",))
def sparse_matvec(
    x_nz: jax.Array,  # (..., knz): (knz,), (B, knz), or decode (B, 1, knz)
    idx: jax.Array,  # (knz,) int32
    wt: jax.Array,  # (K, N), or its (K, 1, N) row_table
    *,
    bn: int = 512,
) -> jax.Array:
    """Leading dims are flattened into the kernel's row axis — decode-shaped
    (B, 1, knz) activations run unpadded, one kernel row per sequence.
    Pass the weight as its ``row_table`` where it is kept: a (K, N) weight
    is converted on every call."""
    squeeze = x_nz.ndim == 1
    lead = x_nz.shape[:-1]
    x2 = x_nz.reshape(-1, x_nz.shape[-1]) if x_nz.ndim != 2 else x_nz
    rows = row_table(wt) if wt.ndim == 2 else wt
    y = run_kernel(sparse_matvec_pallas, x2, idx.astype(jnp.int32), rows,
                   bn=bn)
    y = y.astype(x_nz.dtype)
    return y[0] if squeeze else y.reshape(*lead, wt.shape[-1])


@functools.partial(jax.jit, static_argnames=("k", "bn"))
def topk_sparse_matmul(
    x: jax.Array,  # (..., K) activations (possibly sparse)
    wt: jax.Array,  # (K, N), or its (K, 1, N) row_table
    k: int,
    *,
    bn: int = 512,
) -> jax.Array:
    """Fused: shared top-k compression (batch-union magnitude) + compressed
    product.  Equals x @ wt exactly when x has ≤ k nonzero columns."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    scores = jnp.abs(x2.astype(jnp.float32)).sum(0)
    _, idx = jax.lax.top_k(scores, min(k, x2.shape[1]))
    idx = jnp.sort(idx)  # ascending → quasi-sequential HBM stripes
    x_nz = jnp.take(x2, idx, axis=1)
    return sparse_matvec(x_nz, idx, wt, bn=bn).reshape(*lead, wt.shape[-1])
