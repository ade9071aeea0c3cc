"""The kernel's jnp twin: the same blockwise algorithm, for platforms
without Mosaic.

Each step gathers ``blocks_per_step`` table entries of every slot from the
stacked pool and runs the kernel's own step (``online_softmax_step``) on
them; the loop runs as many steps as the longest walk, and a step past a
slot's walk is masked whole, which leaves that slot's state unchanged bit
for bit.  So a slot's output does not depend on the other slots, and a
query row's output does not depend on how many rows its window holds."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.kernel import (
    online_softmax_step,
    row_col_index,
)


def paged_decode_attention_ref(
    q: jax.Array,  # (B, C, H, Dh)
    k_pool: jax.Array,  # (L, n_blocks, block_len, KH, Dh)
    v_pool: jax.Array,
    layer: jax.Array,  # () int32
    block_table: jax.Array,  # (B, MB) int32
    pos: jax.Array,  # (B,) int32
    walk: jax.Array,  # (B,) int32, ≥ 1
    *,
    blocks_per_step: int,
    scale: float,
) -> jax.Array:
    b, c, h, dh = q.shape
    n_layers, n_pool, bl, kh, _ = k_pool.shape
    mb = block_table.shape[1]
    nb = blocks_per_step
    t = nb * bl
    idx = [jnp.asarray(a) for a in row_col_index(c, h, kh, t)]
    flat_k = k_pool.reshape(n_layers * n_pool, bl, kh, dh)
    flat_v = v_pool.reshape(n_layers * n_pool, bl, kh, dh)
    qr = q.reshape(b, c * h, dh)
    pos3 = pos.astype(jnp.int32)[:, None, None]
    walk_len = (walk.astype(jnp.int32) * bl)[:, None, None]

    def body(i, carry):
        cols = jnp.clip(i * nb + jnp.arange(nb), 0, mb - 1)
        ids = (jnp.clip(block_table[:, cols], 0, n_pool - 1)
               + layer * n_pool)  # (B, nb)
        k = jnp.take(flat_k, ids, axis=0).reshape(b, t * kh, dh)
        v = jnp.take(flat_v, ids, axis=0).reshape(b, t * kh, dh)
        return online_softmax_step(qr, k, v, *carry, idx, i * t, pos3, c,
                                   walk_len, scale)

    n_steps = jnp.max(-(-walk // nb))
    m0 = jnp.full((b, c * h, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, c * h, 1), jnp.float32)
    acc0 = jnp.zeros((b, c * h, dh), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_steps, body, (m0, l0, acc0))
    return (acc / l).astype(q.dtype).reshape(b, c, h, dh)
