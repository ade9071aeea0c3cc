"""Public wrapper + weight converter for the fused SONIC matmul."""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.clustering import ClusteringConfig, cluster_weights
from repro.core.sonic_layers import BlockSparseWeightInt8, make_block_sparse
from repro.kernels.block_sparse_matmul.kernel import (
    block_sparse_matmul_int8_pallas,
)
from repro.kernels.dispatch import run_kernel
from repro.kernels.sonic_matmul.kernel import (
    sonic_matmul_pallas,
    sonic_matvec_int8_pallas,
    sonic_matvec_pallas,
)

# Flattened row counts below this dispatch to the decode-shaped matvec kernel
# (grid over (Nb, R) only) instead of padding up to an M-tile.  8 = the fp32
# sublane tile — at M ≥ 8 the padded matmul wastes nothing.
DECODE_M_THRESHOLD = 8


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SonicWeight:
    """Block-sparse + clustered weight: the full serving-format tensor."""

    idx_values: jax.Array  # (Nb, R, bk, bn) int8 cluster ids
    codebook: jax.Array  # (C,) fp32
    indices: jax.Array  # (Nb, R) int32 K-block ids
    k_blocks: int

    def tree_flatten(self):
        return (self.idx_values, self.codebook, self.indices), self.k_blocks

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, k_blocks=aux)

    @property
    def dense_shape(self):
        nb, r, bk, bn = self.idx_values.shape
        return self.k_blocks * bk, nb * bn

    def dense(self, dtype=jnp.float32) -> jax.Array:
        from repro.kernels.sonic_matmul.ref import sonic_matmul_ref

        k, _ = self.dense_shape
        eye = jnp.eye(k, dtype=jnp.float32)
        return sonic_matmul_ref(
            eye, self.idx_values, self.codebook, self.indices, self.k_blocks
        ).astype(dtype)


def make_sonic_weight(
    w: jax.Array,  # (K, N) trained dense weight
    sparsity: float = 0.75,
    block: tuple[int, int] = (128, 128),
    num_clusters: int = 64,
) -> SonicWeight:
    """Dense → SONIC serving format: cluster first (C2, preserve_zero), then
    balanced block-prune (C1), storing kept blocks as cluster ids."""
    clustered, cw = cluster_weights(w, ClusteringConfig(num_clusters=num_clusters))
    bs = make_block_sparse(clustered, sparsity, block)
    # map kept block values back to cluster indices
    flat = bs.values.reshape(-1)
    ids = jnp.argmin(
        jnp.abs(flat[:, None] - cw.codebook[None, :]), axis=1
    ).astype(jnp.int8)
    return SonicWeight(
        idx_values=ids.reshape(bs.values.shape),
        codebook=cw.codebook,
        indices=bs.indices,
        k_blocks=bs.k_blocks,
    )


@functools.partial(jax.jit, static_argnames=("bm",))
def sonic_matmul(x: jax.Array, w: SonicWeight, *, bm: int = 256) -> jax.Array:
    """x (..., K) @ SONIC weight → (..., N).

    Shape-dispatched: flattened row counts < ``DECODE_M_THRESHOLD`` (the
    decode hot path — M = batch × 1 token) take the matvec kernel, which
    never pads M; larger M takes the tiled matmul kernel.
    """
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    if m < DECODE_M_THRESHOLD:
        y = run_kernel(sonic_matvec_pallas, x2, w.idx_values, w.codebook,
                       w.indices)
        return y.reshape(*lead, w.dense_shape[1]).astype(x.dtype)
    bm_eff = min(bm, max(8, m))
    pad_m = (-m) % bm_eff
    if pad_m:
        x2 = jnp.pad(x2, ((0, pad_m), (0, 0)))
    y = run_kernel(sonic_matmul_pallas, x2, w.idx_values, w.codebook,
                   w.indices, bm=bm_eff)
    if pad_m:
        y = y[:m]
    return y.reshape(*lead, w.dense_shape[1]).astype(x.dtype)


@jax.jit
def sonic_matvec(x: jax.Array, w: SonicWeight) -> jax.Array:
    """Decode-shaped entry point: x (K,) or (B, K) → (N,) / (B, N), always
    through the no-padding matvec kernel regardless of B."""
    squeeze = x.ndim == 1
    x2 = x[None] if squeeze else x
    y = run_kernel(sonic_matvec_pallas, x2, w.idx_values, w.codebook,
                   w.indices).astype(x.dtype)
    return y[0] if squeeze else y


@functools.partial(jax.jit, static_argnames=("bm",))
def sonic_matmul_int8(
    x: jax.Array, w: BlockSparseWeightInt8, *, bm: int = 256
) -> jax.Array:
    """Int8-weight x (..., K) @ W → (..., N), shape-dispatched like
    ``sonic_matmul``: flattened M < ``DECODE_M_THRESHOLD`` takes the
    unpadded int8 matvec kernel, larger M the tiled int8 matmul kernel.
    (The int8-scale format has no codebook stage, so the tiled path is the
    block-sparse int8 kernel — structure skip + in-kernel dequant is the
    whole fusion.)"""
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    n = w.dense_shape[1]
    if m < DECODE_M_THRESHOLD:
        y = run_kernel(sonic_matvec_int8_pallas, x2, w.values, w.scales,
                       w.indices)
        return y.reshape(*lead, n).astype(x.dtype)
    bm_eff = min(bm, max(8, m))
    pad_m = (-m) % bm_eff
    if pad_m:
        x2 = jnp.pad(x2, ((0, pad_m), (0, 0)))
    y = run_kernel(block_sparse_matmul_int8_pallas, x2, w.values, w.scales,
                   w.indices, bm=bm_eff)
    if pad_m:
        y = y[:m]
    return y.reshape(*lead, n).astype(x.dtype)


@jax.jit
def sonic_matvec_int8(x: jax.Array, w: BlockSparseWeightInt8) -> jax.Array:
    """Decode-shaped int8 entry point: x (K,) or (B, K) → (N,) / (B, N),
    always through the no-padding int8 matvec kernel regardless of B."""
    squeeze = x.ndim == 1
    x2 = x[None] if squeeze else x
    y = run_kernel(sonic_matvec_int8_pallas, x2, w.values, w.scales,
                   w.indices).astype(x.dtype)
    return y[0] if squeeze else y
