"""Decode-style attention over a KV cache, each slot up to its length.

``paged_decode_attention`` reads a stacked block pool through a block
table; ``dense_decode_attention`` views the dense layout's per-slot rows as
blocks of the identity table and calls it, so both layouts run one
computation and their greedy outputs agree bit for bit.  On the TPU the
Pallas kernel runs; elsewhere its jnp twin (``ref.py``), the same
algorithm without the interpreter's cost."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import run_kernel
from repro.kernels.paged_attention.kernel import paged_decode_attention_pallas
from repro.kernels.paged_attention.ref import paged_decode_attention_ref

# K bytes one compute step reads (V the same): a step's blocks load as
# DMAs of a block each into a double buffer, so 4 × this much VMEM
STEP_BYTES = 1 << 19


def positions_per_step(kh: int, dh: int, itemsize: int) -> int:
    """The positions a compute step covers: the largest power of two whose
    K fits ``STEP_BYTES``.  A power of two, so any power-of-two block
    length divides it and both layouts step alike."""
    return 1 << max(0, (STEP_BYTES // (kh * dh * itemsize)).bit_length() - 1)


def blocks_per_step(block_len: int, max_blocks: int, kh: int, dh: int,
                    itemsize: int) -> int:
    return max(1, min(max_blocks,
                      positions_per_step(kh, dh, itemsize) // block_len))


def decode_walk(pos: jax.Array, c: int, live: jax.Array | None,
                block_len: int) -> jax.Array:
    """(B,) blocks of ``block_len`` that a window of ``c`` queries from
    ``pos`` reads: up to its last query's position.  A slot that is not
    ``live`` reads one block (its output is dropped), so a stale ``pos``
    never walks a long stale table."""
    walk = (pos.astype(jnp.int32) + c + block_len - 1) // block_len
    return walk if live is None else jnp.where(live, walk, 1)


def paged_decode_attention(
    q: jax.Array,  # (B, C, H, Dh): C decode-style queries a slot
    k_pool: jax.Array,  # (L, n_blocks, block_len, KH, Dh)
    v_pool: jax.Array,
    layer: jax.Array,  # () int32
    block_table: jax.Array,  # (B, MB) int32
    pos: jax.Array,  # (B,) position of each slot's first query
    walk: jax.Array,  # (B,) blocks each slot reads (``decode_walk``)
    *,
    scale: float | None = None,  # Dh^-1/2 unless given
) -> jax.Array:
    """Query i of slot b attends the positions ≤ pos[b] + i of its blocks
    ``block_table[b, :walk[b]]`` in layer ``layer`` → (B, C, H, Dh)."""
    _, _, bl, kh, dh = k_pool.shape
    mb = block_table.shape[1]
    nb = blocks_per_step(bl, mb, kh, dh, k_pool.dtype.itemsize)
    return run_kernel(
        paged_decode_attention_pallas, q, k_pool, v_pool,
        jnp.asarray(layer, jnp.int32), block_table, pos,
        jnp.clip(walk, 1, mb), blocks_per_step=nb,
        scale=dh**-0.5 if scale is None else scale,
        otherwise=paged_decode_attention_ref)


def dense_decode_attention(
    q: jax.Array,  # (B, C, H, Dh)
    k_cache: jax.Array,  # (B, S_max, KH, Dh)
    v_cache: jax.Array,
    pos: jax.Array,  # (B,)
    live: jax.Array | None = None,  # (B,) bool: rows whose output is used
    *,
    scale: float | None = None,
) -> jax.Array:
    """``paged_decode_attention`` over the dense layout: row b's cache is
    blocks b·(S_max/w) .. of width w, a free view; w is the largest power
    of two dividing S_max and a compute step, so a step covers the
    positions it covers over a paged pool of any power-of-two block."""
    b, s_max, kh, dh = k_cache.shape
    w = math.gcd(s_max, positions_per_step(kh, dh, k_cache.dtype.itemsize))
    n = s_max // w
    table = jnp.arange(b * n, dtype=jnp.int32).reshape(b, n)
    return paged_decode_attention(
        q, k_cache.reshape(1, b * n, w, kh, dh),
        v_cache.reshape(1, b * n, w, kh, dh), 0, table, pos,
        decode_walk(pos, q.shape[1], live, w), scale=scale)
