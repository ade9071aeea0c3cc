"""Paged KV cache (ISSUE 3 acceptance tests): bit-identical greedy outputs
vs the dense slot layout, block-gated admission (deferral, no deadlock),
per-family paged-cache contract, and the no-retrace guarantee for the paged
slot programs."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ALL_ARCH_IDS
from repro.models.registry import check_paged_cache_contract, get_arch
from repro.serve import ContinuousScheduler, ServeConfig, ServeEngine
from repro.sharding.mesh import MeshPlan

PLAN = MeshPlan()
MAX_LEN, BLOCK_LEN = 64, 8


@pytest.fixture(scope="module")
def arch_params():
    arch = get_arch("tinyllama-1.1b", reduced=True)
    params = arch.init_params(jax.random.PRNGKey(0))
    return arch, params


def _engine(arch_params, layout="paged", **kw):
    arch, params = arch_params
    sc = ServeConfig(max_len=MAX_LEN, kv_layout=layout,
                     block_len=BLOCK_LEN, **kw)
    return ServeEngine(arch, params, PLAN, sc)


def _prompt(seed, length):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (length,), 0, 256),
        np.int32,
    )


# ------------------------------------------------- bit-identical vs dense


@pytest.mark.parametrize("mode", ["scan", "while"])
def test_uniform_workload_bit_identical_to_static_engine(arch_params, mode):
    """Greedy outputs through the PAGED scheduler equal the static engine's
    bit-for-bit — same contract the dense slot layout upholds."""
    prompts = jnp.stack([jnp.asarray(_prompt(i, 8)) for i in range(6)])
    want = np.asarray(_engine(arch_params, "dense").generate(prompts, 10))
    sched = ContinuousScheduler(
        _engine(arch_params), n_slots=3, segment_len=4, segment_mode=mode
    )
    handles = [sched.submit(np.asarray(prompts[i]), 10) for i in range(6)]
    sched.run()
    got = np.stack([h.tokens for h in handles])
    np.testing.assert_array_equal(got, want, err_msg=mode)
    assert all(h.done for h in handles)


def test_ragged_workload_matches_dense_scheduler(arch_params):
    """Ragged prompts/budgets (incl. a 1-token request): paged and dense
    schedulers emit identical streams request-by-request."""
    lens = [4, 7, 11, 5, 9, 3]
    news = [6, 12, 3, 1, 9, 14]
    scheds = {
        layout: ContinuousScheduler(
            _engine(arch_params, layout), n_slots=2, segment_len=5,
            n_blocks=10 if layout == "paged" else None,
        )
        for layout in ("dense", "paged")
    }
    handles = {
        layout: [s.submit(_prompt(10 + i, n), m)
                 for i, (n, m) in enumerate(zip(lens, news))]
        for layout, s in scheds.items()
    }
    for s in scheds.values():
        while s.has_work():
            s.run_segment()
            s.check_block_invariants()
    for a, b in zip(handles["dense"], handles["paged"]):
        assert a.tokens == b.tokens, f"rid={a.rid}"
        assert b.done


def test_eos_retirement_frees_blocks(arch_params):
    """An eos retirement mid-budget returns the slot's blocks to the pool
    (the dense test's scenario, plus allocator bookkeeping)."""
    base = np.asarray(_engine(arch_params, "dense").generate(
        jnp.asarray(_prompt(40, 8))[None, :], 12))[0]
    eos = int(base[4])
    sched = ContinuousScheduler(
        _engine(arch_params, eos_token=eos), n_slots=1, segment_len=4,
        n_blocks=4,
    )
    h = sched.submit(_prompt(40, 8), 12)
    h2 = sched.submit(_prompt(41, 8), 3)
    while sched.has_work():
        sched.run_segment()
        sched.check_block_invariants()
    assert h.done and h2.done
    assert eos in h.tokens and h.tokens[-1] == eos
    assert len(h2.tokens) == 3
    assert sched.allocator.n_free == sched.allocator.capacity


# ------------------------------------------------- block-gated admission


def test_small_pool_defers_admission_without_deadlock(arch_params):
    """A pool that fits one request at a time serializes the workload via
    deferral: admissions wait for blocks (not slots) and every request
    still completes with the exact dense-scheduler stream."""
    lens = [8, 8, 8]
    news = [16, 16, 16]  # each request needs ceil(24/8)=3 blocks
    dense = ContinuousScheduler(
        _engine(arch_params, "dense"), n_slots=2, segment_len=4)
    paged = ContinuousScheduler(
        _engine(arch_params), n_slots=2, segment_len=4, n_blocks=3)
    hd = [dense.submit(_prompt(50 + i, n), m)
          for i, (n, m) in enumerate(zip(lens, news))]
    hp = [paged.submit(_prompt(50 + i, n), m)
          for i, (n, m) in enumerate(zip(lens, news))]
    dense.run()
    while paged.has_work():
        paged.run_segment()
        paged.check_block_invariants()
        assert paged.allocator.n_mapped <= paged.n_blocks
    assert paged.stats["admit_deferred"] > 0  # the pool really gated
    assert paged.stats["blocks_in_use_peak"] <= paged.n_blocks
    for a, b in zip(hd, hp):
        assert a.tokens == b.tokens and b.done


def test_submit_rejects_request_that_can_never_fit(arch_params):
    sched = ContinuousScheduler(_engine(arch_params), n_slots=1, n_blocks=2)
    with pytest.raises(ValueError, match="blocks"):
        sched.submit(_prompt(60, 20), 10)  # needs 4 blocks, pool has 2


# ------------------------------------------------------- compiled once


@pytest.mark.parametrize("mode", ["scan", "while"])
def test_paged_slot_programs_compiled_once_across_segments(arch_params, mode):
    """One trace of the paged segment program per session; one paged prefill
    trace per distinct prompt length — block table changes never retrace."""
    eng = _engine(arch_params)
    sched = ContinuousScheduler(eng, n_slots=2, segment_len=3,
                                segment_mode=mode, n_blocks=12)
    lens = [4, 7, 4, 7, 4]
    handles = [sched.submit(_prompt(60 + i, n), 5 + i)
               for i, n in enumerate(lens)]
    sched.run()
    assert all(h.done for h in handles)
    assert sched.stats["segments"] >= 2
    seg_key = ("slot_segment_paged" if mode == "scan"
               else "slot_segment_while_paged")
    assert eng.trace_counts[seg_key] == 1
    assert eng.call_counts[seg_key] == sched.stats["segments"]
    assert eng.trace_counts["prefill_slot_paged"] == 2  # 2 distinct lengths
    assert eng.call_counts["prefill_slot_paged"] == len(lens)
    # the dense programs were never touched
    assert eng.trace_counts["prefill_slot"] == 0
    assert eng.trace_counts["slot_segment"] == 0


# ------------------------------------------------------- cache contract


@pytest.mark.parametrize("arch_id", ALL_ARCH_IDS)
def test_paged_cache_contract_across_families(arch_id):
    """Families with a growing KV cache uphold the paged pool contract;
    the others surface their skip reason through the registry."""
    arch = get_arch(arch_id, reduced=True)
    reason = arch.paged_skip_reason()
    if reason:
        assert not arch.supports_paged_kv
        with pytest.raises(NotImplementedError):
            check_paged_cache_contract(arch)
        pytest.skip(reason)
    check_paged_cache_contract(arch)


# ------------------------------------------- the pool is carried in place
#
# The layer scan carries the stacked pool (n_layers, n_blocks, …) and each
# layer scatters into and gathers from its own blocks by (layer, block)
# index.  The tests below hold the compiled programs to that (no op copies,
# slices or updates a whole pool leaf or one layer of it) and hold every
# write to the positions it owns, in every layer.

N_SLOTS, N_ALLOC = 3, 10  # physical blocks: 3 scratch + 10 allocatable


def _quant_engine(arch_params, quant, **kw):
    arch, params = arch_params
    sc = ServeConfig(max_len=MAX_LEN, kv_layout="paged",
                     block_len=BLOCK_LEN, **kw)
    return ServeEngine(arch, params, MeshPlan(cache_quant_int8=quant), sc)


def _slot_state(n):
    return (jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.int32),
            jnp.zeros((n,), bool))


_HLO_OP = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* ([\w\-]+)\(")
_POOL_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice", "broadcast")


def _pool_shaped_ops(hlo: str, pool: dict) -> set[tuple[str, tuple]]:
    """(opcode, dims) of every instruction shaped like a whole stacked pool
    leaf or one layer of it (leading unit dims dropped)."""
    shapes = {tuple(a.shape) for a in pool.values()}
    shapes |= {s[1:] for s in shapes}
    found = set()
    for line in hlo.splitlines():
        m = _HLO_OP.match(line)
        if not m:
            continue
        dims = tuple(int(d) for d in m.group(1).split(",") if d)
        if dims[:1] == (1,) and dims[1:] in shapes:
            dims = dims[1:]
        if dims in shapes:
            found.add((m.group(2), dims))
    return found


@pytest.mark.parametrize("program", ["slot_segment_while_paged",
                                     "prefill_slots_paged"])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_paged_programs_move_no_whole_pool(arch_params, quant, program):
    """Only scatters and gathers touch the pool: no copy, slice, update or
    broadcast of a whole pool leaf, or of one layer's pool, in the compiled
    decode segment or chunk-prefill program."""
    eng = _quant_engine(arch_params, quant)
    _, params = arch_params
    pool = eng.init_paged_cache(N_ALLOC, N_SLOTS)
    tok, pos, done = _slot_state(N_SLOTS)
    table = jnp.zeros((N_SLOTS, eng.max_blocks_per_slot), jnp.int32)
    key = jax.random.PRNGKey(0)
    if program == "slot_segment_while_paged":
        lowered = eng._slot_segment_while_paged.lower(
            4, params, pool, tok, pos, done, key, jnp.ones((N_SLOTS,), bool),
            jnp.full((N_SLOTS,), 20, jnp.int32), jnp.bool_(False), table)
    else:
        rows = jnp.zeros((N_SLOTS,), jnp.int32)
        lowered = eng._prefill_slots_paged.lower(
            params, pool, tok, pos, done, jnp.zeros((N_SLOTS, 16), jnp.int32),
            jnp.arange(N_SLOTS, dtype=jnp.int32), rows, rows, table, key)
    ops = _pool_shaped_ops(lowered.compile().as_text(), pool)
    moved = sorted(o for o in ops if o[0] in _POOL_MOVES)
    assert not moved, moved
    stacked = tuple(pool["k"].shape)
    assert ("scatter", stacked) in ops, sorted(ops)  # the parse saw the pool


def _random_pool(eng, seed):
    """A pool of distinct nonzero values, so an unchanged position is
    told from a rewritten one bit for bit."""
    pool = eng.init_paged_cache(N_ALLOC, N_SLOTS)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(pool))
    out = {}
    for k, (name, a) in zip(keys, sorted(pool.items())):
        if a.dtype == jnp.int8:
            out[name] = jax.random.randint(k, a.shape, -127, 128, jnp.int8)
        elif "scale" in name:
            out[name] = jax.random.uniform(k, a.shape, a.dtype, 1e-3, 2e-2)
        else:
            out[name] = jax.random.normal(k, a.shape, a.dtype)
    return out


def _bits(a):
    a = np.asarray(a)
    return a.view(f"u{a.dtype.itemsize}")


def _virtual(pool, layer, table):
    """Layer ``layer`` of the pool as per-slot virtual caches."""
    from repro.models.layers import LayerPool, paged_cache_gather

    return {name: paged_cache_gather(LayerPool(a, jnp.int32(layer)), table)
            for name, a in pool.items()}


def _dense_from_pool(pool, table):
    """The dense slot cache holding what the paged slots address."""
    n_layers = pool["k"].shape[0]
    per_layer = [_virtual(pool, layer, table) for layer in range(n_layers)]
    return {name: jnp.stack([v[name] for v in per_layer])
            for name in pool}


def _assert_writes(old, new, dense_new, written, rows):
    """Every position outside ``written`` (a (n_blocks, block_len) mask) is
    bitwise unchanged in every layer and leaf; each (slot, table row,
    first, last) of ``rows`` reads back, layer by layer, what the dense
    slot layout wrote at logical positions first..last."""
    for name in old:
        o, n = _bits(old[name]), _bits(new[name])
        np.testing.assert_array_equal(n[:, ~written], o[:, ~written],
                                      err_msg=name)
    for layer in range(old["k"].shape[0]):
        virt = _virtual(new, layer, jnp.asarray(np.stack([r[1] for r in rows])))
        for i, (slot, _, first, last) in enumerate(rows):
            for name in old:
                np.testing.assert_array_equal(
                    _bits(virt[name][i, first:last + 1]),
                    _bits(dense_new[name][layer, slot, first:last + 1]),
                    err_msg=f"{name} layer {layer} slot {slot}")


def _written_mask(table, spans):
    """(n_blocks, block_len) mask of the positions that logical ``spans``
    (row, first, last) reach through in-range entries of ``table``."""
    n_blocks, bl = N_SLOTS + N_ALLOC, BLOCK_LEN
    mask = np.zeros((n_blocks, bl), bool)
    for row, first, last in spans:
        for p in range(first, last + 1):
            phys = table[row, p // bl]
            if 0 <= phys < n_blocks:
                mask[phys, p % bl] = True
    return mask


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_chunk_resume_writes_only_its_own_positions(arch_params, quant):
    """One chunk-resume launch with bucket-padding spill ids and masked
    dummy rows: no out-of-range id of layer l lands in layer l+1 (every
    position the launch does not own is bitwise unchanged in every layer),
    and what it writes equals the dense slot layout's writes."""
    eng = _quant_engine(arch_params, quant)
    _, params = arch_params
    n_blocks, mb = N_SLOTS + N_ALLOC, eng.max_blocks_per_slot
    width, chunk = 4, 16
    # the scheduler's fill: distinct out-of-range ids everywhere a row maps
    # nothing (spill past the mapped blocks, and the two dummy rows)
    bt = n_blocks + np.arange(width * mb, dtype=np.int32).reshape(width, mb)
    bt[0, :2] = [3, 4]  # slot 0: 2 blocks, resumes at 8, 6 real tokens
    bt[1, :3] = [7, 9, 10]  # slot 2: 3 blocks, resumes at 4, 16 real
    slots = np.array([0, 2, N_SLOTS, N_SLOTS + 1], np.int32)
    starts = np.array([8, 4, 0, 0], np.int32)
    last_local = np.array([5, 15, 0, 0], np.int32)
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(1),
                                            (width, chunk), 0, 256), np.int32)
    # each slot's own table (scratch where unmapped) for the dense copy
    slot_table = np.repeat(np.arange(N_SLOTS, dtype=np.int32)[:, None], mb, 1)
    slot_table[0, :2], slot_table[2, :3] = bt[0, :2], bt[1, :3]

    pool = _random_pool(eng, seed=2)
    old = {name: np.asarray(a) for name, a in pool.items()}
    dense = _dense_from_pool(pool, jnp.asarray(slot_table))
    args = (jnp.asarray(prompts), jnp.asarray(slots), jnp.asarray(starts),
            jnp.asarray(last_local))
    key = jax.random.PRNGKey(3)
    new, *_, firsts = eng._prefill_slots_paged(
        params, pool, *_slot_state(N_SLOTS), *args, jnp.asarray(bt), key)
    dense_new, *_, dense_firsts = eng._prefill_slots(
        params, dense, *_slot_state(N_SLOTS), *args, key)

    np.testing.assert_array_equal(np.asarray(firsts)[:2],
                                  np.asarray(dense_firsts)[:2])
    # slot 0 writes 8..23, of which 16..23 spill past its mapped blocks
    written = _written_mask(bt, [(0, 8, 23), (1, 4, 19)])
    assert written.sum() == 8 + 16
    _assert_writes(old, new, dense_new, written,
                   [(0, bt[0], 8, 15), (2, bt[1], 4, 19)])


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_spec_verify_window_writes_only_its_own_positions(arch_params, quant):
    """One speculative draft-and-verify step over the paged pool (the
    full-depth self-drafter threads the pool, the verify window writes
    K+1 positions a slot): positions outside each slot's window are bitwise
    unchanged in every layer, and the windows equal the dense layout's."""
    from repro.serve import SpecConfig

    k = 2
    eng = _quant_engine(arch_params, quant,
                        spec=SpecConfig(k=k, draft="self", draft_sparsity=0.0))
    _, params = arch_params
    mb = eng.max_blocks_per_slot
    table = np.repeat(np.arange(N_SLOTS, dtype=np.int32)[:, None], mb, 1)
    table[0, :2] = [3, 4]  # slot 0 at 10: window 10..12 in block 4
    table[1, :2] = [5, 8]  # slot 1 at 6: window 6..8 straddles 5 → 8
    pos = np.array([10, 6, 0], np.int32)  # slot 2 idle: its scratch block
    active = jnp.asarray([True, True, False])
    limit = jnp.full((N_SLOTS,), 40, jnp.int32)
    tok = np.array([17, 42, 0], np.int32)

    pool = _random_pool(eng, seed=4)
    old = {name: np.asarray(a) for name, a in pool.items()}
    dense = _dense_from_pool(pool, jnp.asarray(table))
    key = jax.random.PRNGKey(5)

    def state():
        return tok, jnp.asarray(pos), jnp.zeros((N_SLOTS,), bool), key

    toks, new, *_ = eng._slot_spec_segment_paged(
        1, params, eng.draft_params, pool, *state(), active, limit,
        jnp.asarray(table))
    dense_toks, dense_new, *_ = eng._slot_spec_segment(
        1, params, eng.draft_params, dense, *state(), active, limit)

    np.testing.assert_array_equal(np.asarray(toks), np.asarray(dense_toks))
    spans = [(s, int(pos[s]), int(pos[s]) + k) for s in range(N_SLOTS)]
    written = _written_mask(table, spans)
    assert written.sum() == N_SLOTS * (k + 1)
    _assert_writes(old, new, dense_new, written,
                   [(s, table[s], a, b) for s, a, b in spans])
