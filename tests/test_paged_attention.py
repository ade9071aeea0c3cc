"""Paged decode attention (``kernels/paged_attention``): the Pallas kernel in
interpret mode and its jnp twin against the gathered ``decode_attention``,
lengths at and around block edges, masked slots with a stale position,
NaN past every slot's length, the layer index of a stacked pool, the dense
layout's identity-table view, and the scheduler's KV block counters."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention import (
    decode_walk,
    dense_decode_attention,
    paged_decode_attention,
)
from repro.kernels.paged_attention.kernel import paged_decode_attention_pallas
from repro.kernels.paged_attention.ref import paged_decode_attention_ref
from repro.models.layers import LayerPool, decode_attention, paged_cache_gather
from repro.models.registry import get_arch
from repro.serve import ContinuousScheduler, ServeConfig, ServeEngine
from repro.sharding.mesh import MeshPlan

BL, MB, DH, L = 4, 6, 16, 3  # block length, blocks a slot, head size, layers


def _case(b, c, kh, g, seed=0, dtype=jnp.float32):
    """A stacked pool with scratch blocks 0..b−1 and each slot's MB blocks
    shuffled over the rest, a table, and queries."""
    n_pool = b + b * MB
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (L, n_pool, BL, kh, DH)
    kp = jax.random.normal(ks[0], shape).astype(dtype)
    vp = jax.random.normal(ks[1], shape).astype(dtype)
    q = jax.random.normal(ks[2], (b, c, kh * g, DH)).astype(dtype)
    rng = np.random.RandomState(seed)
    table = rng.permutation(np.arange(b, n_pool)).reshape(b, MB)
    return q, kp, vp, jnp.asarray(table, jnp.int32)


def _gathered(q, kp, vp, layer, table, pos):
    k = paged_cache_gather(LayerPool(kp, jnp.int32(layer)), table)
    v = paged_cache_gather(LayerPool(vp, jnp.int32(layer)), table)
    return decode_attention(q, k, v, pos)


def _kernel(q, kp, vp, layer, table, pos, walk, nb=2):
    return paged_decode_attention_pallas(
        q, kp, vp, jnp.int32(layer), table, pos, walk, blocks_per_step=nb,
        scale=DH**-0.5, interpret=True)


def _twin(q, kp, vp, layer, table, pos, walk, nb=2):
    return paged_decode_attention_ref(
        q, kp, vp, jnp.int32(layer), table, pos, walk, blocks_per_step=nb,
        scale=DH**-0.5)


def _close(a, b, tol=2e-6):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("kh,g,c", [(1, 1, 1), (2, 2, 5), (8, 4, 2),
                                    (1, 4, 3), (2, 1, 4), (8, 2, 1)])
def test_kernel_and_twin_equal_gathered_attention(kh, g, c):
    """KV heads 1, 2 and 8, groups 1, 2 and 4, windows of 1 to 5 queries:
    the kernel (interpreted) and its twin equal the gathered formulation;
    lengths 1, at a block edge, just past one and the whole max_len, with
    the last two steps of a slot partly walked."""
    lens = np.array([1, BL, BL + 1, MB * BL, 2 * BL + 3])
    q, kp, vp, table = _case(len(lens), c, kh, g, seed=kh * 10 + g + c)
    pos = jnp.asarray(np.maximum(lens - c, 0), jnp.int32)
    walk = decode_walk(pos, c, None, BL)
    want = _gathered(q, kp, vp, 1, table, pos)
    twin = _twin(q, kp, vp, 1, table, pos, walk)
    _close(twin, want)
    _close(_kernel(q, kp, vp, 1, table, pos, walk), twin)


def test_nothing_past_a_slot_length_is_read():
    """Every position past each slot's window, in its mapped blocks and in
    every unmapped block, NaN: the output stays finite and equal, in the
    kernel and the twin."""
    c, lens = 2, np.array([3, BL, 2 * BL + 1, MB * BL])
    q, kp, vp, table = _case(len(lens), c, 2, 2, seed=3)
    pos = jnp.asarray(lens - c, jnp.int32)
    walk = decode_walk(pos, c, None, BL)
    bad_k, bad_v = np.asarray(kp).copy(), np.asarray(vp).copy()
    seen = np.zeros(kp.shape[:3], bool)  # (L, block, offset) in a window
    for s, n in enumerate(lens):
        for t in range(n):
            seen[:, int(table[s, t // BL]), t % BL] = True
    bad_k[~seen], bad_v[~seen] = np.nan, np.nan
    for fn in (_kernel, _twin):
        clean = fn(q, kp, vp, 2, table, pos, walk)
        dirty = fn(q, jnp.asarray(bad_k), jnp.asarray(bad_v), 2, table, pos,
                   walk)
        assert np.isfinite(np.asarray(dirty)).all()
        np.testing.assert_array_equal(np.asarray(dirty), np.asarray(clean))


def test_masked_slot_with_stale_position_reads_one_block():
    """A retired slot keeps a stale position and a table of its scratch
    block: it walks one block, its output is finite, and the live slots'
    outputs do not change."""
    q, kp, vp, table = _case(3, 1, 2, 2, seed=5)
    table = table.at[1].set(1)  # retired: every entry its scratch block
    live = jnp.asarray([True, False, True])
    pos = jnp.asarray([9, MB * BL - 1, 17], jnp.int32)
    walk = decode_walk(pos, 1, live, BL)
    np.testing.assert_array_equal(np.asarray(walk), [3, 1, 5])
    fresh = pos.at[1].set(0)
    for fn in (_kernel, _twin):
        got = np.asarray(fn(q, kp, vp, 0, table, pos, walk))
        ref = np.asarray(fn(q, kp, vp, 0, table, fresh, walk))
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got[[0, 2]], ref[[0, 2]])


def test_layer_index_selects_its_layer():
    q, kp, vp, table = _case(2, 1, 2, 2, seed=7)
    pos = jnp.asarray([6, 13], jnp.int32)
    walk = decode_walk(pos, 1, None, BL)
    outs = []
    for layer in range(L):
        got = _kernel(q, kp, vp, layer, table, pos, walk)
        _close(got, _gathered(q, kp, vp, layer, table, pos))
        outs.append(np.asarray(got))
    assert not np.array_equal(outs[0], outs[1])


def test_verify_rows_equal_decode_steps_bitwise():
    """Row i of a C-query window computes what a one-query step at pos + i
    does, bit for bit: the contract greedy speculation rests on."""
    c = 4
    q, kp, vp, table = _case(3, c, 2, 2, seed=11)
    pos = jnp.asarray([0, 5, 11], jnp.int32)
    window = paged_decode_attention(q, kp, vp, 1, table, pos,
                                    decode_walk(pos, c, None, BL))
    for i in range(c):
        step = paged_decode_attention(q[:, i:i + 1], kp, vp, 1, table, pos + i,
                                      decode_walk(pos + i, 1, None, BL))
        np.testing.assert_array_equal(np.asarray(window[:, i:i + 1]),
                                      np.asarray(step))


def test_dense_rows_as_identity_table_equal_the_pool():
    """The dense layout's rows, viewed as blocks, give the paged pool's
    output bit for bit when both hold the same values."""
    q, kp, vp, table = _case(3, 1, 2, 2, seed=13, dtype=jnp.bfloat16)
    pos = jnp.asarray([2, 10, MB * BL - 1], jnp.int32)
    k_rows = paged_cache_gather(LayerPool(kp, jnp.int32(0)), table)
    v_rows = paged_cache_gather(LayerPool(vp, jnp.int32(0)), table)
    paged = paged_decode_attention(q, kp, vp, 0, table, pos,
                                   decode_walk(pos, 1, None, BL))
    dense = dense_decode_attention(q, k_rows, v_rows, pos)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(paged))


# --------------------------------------------------- the block counters


def _scheduler(arch_id, layout="paged", segment_len=8):
    arch = get_arch(arch_id, reduced=True)
    sc = ServeConfig(max_len=32, kv_layout=layout, block_len=8)
    eng = ServeEngine(arch, arch.init_params(jax.random.PRNGKey(0)),
                      MeshPlan(), sc)
    return ContinuousScheduler(
        eng, n_slots=2, segment_len=segment_len, segment_mode="scan",
        n_blocks=8 if layout == "paged" else None)


def test_kv_block_counters_equal_a_hand_count():
    """One request (7-token prompt, 6 tokens) in one 8-step segment over 2
    slots, 4 blocks of 8 a slot: its 5 decode steps at positions 7..11
    walk 1, 2, 2, 2, 2 blocks; its 3 steps after it finished and the idle
    slot's 8 walk one each."""
    sched = _scheduler("tinyllama-1.1b")
    h = sched.submit(np.arange(7, dtype=np.int32), 6)
    sched.run()
    st, n_layers = sched.stats, sched.engine.cfg.n_layers
    assert h.done and len(h.tokens) == 6 and st["steps_total"] == 8
    assert st["decode_kv_blocks_read"] == n_layers * (1 + 4 * 2 + 3 + 8)
    assert st["decode_kv_blocks_span"] == n_layers * 8 * 2 * 4


def test_kv_block_counters_read_the_span_where_attention_gathers():
    """Latent attention gathers max_len: read equals span.  The dense
    layout counts no blocks."""
    sched = _scheduler("moonlight-16b-a3b")
    sched.submit(np.arange(7, dtype=np.int32), 6)
    sched.run()
    st = sched.stats
    assert st["decode_kv_blocks_read"] == st["decode_kv_blocks_span"] > 0
    dense = _scheduler("tinyllama-1.1b", layout="dense")
    dense.submit(np.arange(7, dtype=np.int32), 6)
    dense.run()
    assert dense.stats["decode_kv_blocks_span"] == 0
