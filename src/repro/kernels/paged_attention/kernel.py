"""Paged decode attention Pallas kernel: each slot reads its own KV blocks,
up to its length, straight from the stacked pool.

    out[b, c, h] = softmax_t(q[b, c, h] · K[b, t, h // G] · scale) · V[b, t, h // G]
    over t ≤ pos[b] + c, t < walk[b] · block_len

The pool is ``(L, n_blocks, block_len, KH, Dh)``, every layer's blocks in one
array; the layer index, the block table, the first query's position and the
blocks each slot walks arrive by scalar prefetch.  Slot b reads the
physical blocks ``table[b, 0 .. walk[b] − 1]`` of layer ``layer`` and
nothing else: no per-layer slice of the pool, no gathered copy.

Grid = (B,), one slot a grid step, in order.  Inside a slot a loop walks
its blocks ``nb`` at a time (one compute step): each block is one DMA of
``block_len · KH · Dh`` values HBM → VMEM, K and V into double buffers, so
the next step's blocks — or the next slot's first — load while this step
computes.  A block past the walk is not loaded; its stale buffer is masked.

One compute step scores the C·H query rows (rows ordered (c, kv head, g))
against all ``nb · block_len · KH`` key rows of the step (rows ordered
(position, kv head)) in one matmul and keeps a row's own kv head by mask.
The MXU streams the same tiles as one matmul per head would (C·G rows fit
one tile either way); only the fp32 score block is KH× wider, and no
relayout of the (KH, Dh)-minor blocks is needed.  Scores, the online softmax
and the accumulator are fp32; probabilities meet V in V's dtype, as the
gathered formulation's do.  Masked keys score −inf and masked values are
zeroed, so nothing past a slot's length — stale or NaN — reaches its output,
and a step a row cannot see changes nothing of its state, bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def row_col_index(c: int, h: int, kh: int, t: int):
    """Static index vectors of one compute step of T positions: each query
    row's query offset and kv head ((C·H, 1) int32 each), each key row's
    position in the step and kv head ((1, T·KH) int32 each), and the key
    rows' positions again as a column ((T·KH, 1))."""
    g = h // kh
    r = np.arange(c * h)
    n = np.arange(t * kh)
    return ((r // h)[:, None].astype(np.int32),
            ((r % h) // g)[:, None].astype(np.int32),
            (n // kh)[None, :].astype(np.int32),
            (n % kh)[None, :].astype(np.int32),
            (n // kh)[:, None].astype(np.int32))


def online_softmax_step(q, k, v, m, l, acc, idx, p0, pos, c, walk_len,
                        scale):
    """One compute step of the online softmax, shared by the kernel and its
    jnp twin (``ref.py``): q (…, R, Dh), k and v (…, N, Dh), state m and l
    (…, R, 1), acc (…, R, Dh) fp32, ``idx`` from ``row_col_index``; key row
    n sits at position ``p0 + col_t[n]``.  Query row r sees the positions
    ≤ pos + row_c[r] that lie below ``walk_len``; the C queries sit at
    pos .. pos + c − 1."""
    row_c, row_h, col_t, col_h, col_t_column = idx
    s = jax.lax.dot_general(
        q, k, (((q.ndim - 1,), (k.ndim - 1,)), (tuple(range(q.ndim - 2)),) * 2),
        preferred_element_type=jnp.float32) * scale
    lim = jnp.minimum(pos + row_c, walk_len - 1)
    s = jnp.where((col_h == row_h) & (p0 + col_t <= lim), s, -jnp.inf)
    # values past the window's last position may be stale or NaN: zero them
    vlim = jnp.minimum(pos + (c - 1), walk_len - 1)
    v = jnp.where(p0 + col_t_column <= vlim, v.astype(jnp.float32),
                  0.0).astype(v.dtype)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v,
        (((p.ndim - 1,), (v.ndim - 2,)), (tuple(range(p.ndim - 2)),) * 2),
        preferred_element_type=jnp.float32)
    return m_new, l, acc * corr + pv


def _kernel(layer_ref, table_ref, pos_ref, walk_ref,  # scalar prefetch
            q_ref, *refs, nb: int, mb: int, n_pool: int, c: int,
            scale: float):
    *idx_refs, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, buf_ref = refs
    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    bl = kbuf.shape[2]
    layer = layer_ref[0]

    def block_copies(slot, step, buf, j):
        blk = jnp.clip(table_ref[slot * mb + step * nb + j], 0, n_pool - 1)
        return (pltpu.make_async_copy(k_hbm.at[layer, blk], kbuf.at[buf, j],
                                      sems.at[buf, 0]),
                pltpu.make_async_copy(v_hbm.at[layer, blk], vbuf.at[buf, j],
                                      sems.at[buf, 1]))

    def for_walked(slot, step, buf, fn):
        """``fn`` on the copies of each block of (slot, step) in the walk."""
        left = walk_ref[slot] - step * nb
        for j in range(nb):
            @pl.when(j < left)
            def _():
                for cp in block_copies(slot, step, buf, j):
                    fn(cp)

    def start(slot, step, buf):
        for_walked(slot, step, buf, lambda cp: cp.start())

    @pl.when(b == 0)
    def _first():
        buf_ref[0] = 0
        start(0, 0, 0)

    walk = walk_ref[b]
    n_steps = pl.cdiv(walk, nb)
    pos = pos_ref[b]
    q = q_ref[0]
    idx = [r[...] for r in idx_refs]
    t = nb * bl
    n_rows = q.shape[0]

    def body(i, carry):
        m, l, acc = carry
        buf = (buf_ref[0] + i) % 2
        nxt = 1 - buf

        @pl.when(i + 1 < n_steps)
        def _next_step():
            start(b, i + 1, nxt)

        @pl.when((i + 1 == n_steps) & (b + 1 < n_slots))
        def _next_slot():
            start(b + 1, 0, nxt)

        for_walked(b, i, buf, lambda cp: cp.wait())
        k = kbuf[buf].reshape(t * kbuf.shape[3], kbuf.shape[4])
        v = vbuf[buf].reshape(t * vbuf.shape[3], vbuf.shape[4])
        return online_softmax_step(q, k, v, m, l, acc, idx, i * t, pos, c,
                                   walk * bl, scale)

    m0 = jnp.full((n_rows, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((n_rows, 1), jnp.float32)
    acc0 = jnp.zeros((n_rows, q.shape[1]), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_steps, body, (m0, l0, acc0))
    buf_ref[0] = (buf_ref[0] + n_steps) % 2
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def paged_decode_attention_pallas(
    q: jax.Array,  # (B, C, H, Dh)
    k_pool: jax.Array,  # (L, n_blocks, block_len, KH, Dh)
    v_pool: jax.Array,
    layer: jax.Array,  # () int32
    block_table: jax.Array,  # (B, MB) int32
    pos: jax.Array,  # (B,) int32: position of each slot's first query
    walk: jax.Array,  # (B,) int32: blocks each slot reads, ≥ 1
    *,
    blocks_per_step: int,
    scale: float,
    interpret: bool,
) -> jax.Array:
    """Returns (B, C, H, Dh) in q's dtype."""
    b, c, h, dh = q.shape
    _, n_pool, bl, kh, _ = k_pool.shape
    mb = block_table.shape[1]
    nb = blocks_per_step
    idx = row_col_index(c, h, kh, nb * bl)
    full = [pl.BlockSpec(a.shape, lambda i, *_: (0, 0)) for a in idx]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, c * h, dh), lambda i, *_: (i, 0, 0)),
            *full,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, c * h, dh), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, nb, bl, kh, dh), k_pool.dtype),
            pltpu.VMEM((2, nb, bl, kh, dh), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, nb=nb, mb=mb, n_pool=n_pool, c=c,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, c * h, dh), q.dtype),
        # the next slot's first blocks load during this slot's last step,
        # so slots run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      block_table.reshape(-1).astype(jnp.int32), pos.astype(jnp.int32),
      walk.astype(jnp.int32), q.reshape(b, c * h, dh), *map(jnp.asarray, idx),
      k_pool, v_pool)
    return out.reshape(b, c, h, dh)
