"""Speculative decoding (ISSUE 5 acceptance tests): greedy draft-and-verify
through the continuous scheduler must be bit-identical to the plain
scheduler and the sequential oracle — dense AND paged, k ∈ {1, 2, 4},
including eos-within-draft-window and max_new boundary cases — with
rollback as pure cursor truncation (cache beyond the accepted position is
never read), a single compiled spec-segment program per engine, and the
skip/fallback matrix mirroring chunked prefill.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.registry import get_arch
from repro.serve import (
    ContinuousScheduler, ServeConfig, ServeEngine, SpecConfig, spec_accept,
    spec_accept_len,
)
from repro.sharding.mesh import MeshPlan

PLAN = MeshPlan()
MAX_LEN, BLOCK_LEN = 64, 8
LENS = [3, 5, 8, 13, 5, 8]
NEWS = [9, 2, 5, 16, 1, 7]  # includes max_new == 1 (admission-only) and 2


@pytest.fixture(scope="module")
def arch_params():
    arch = get_arch("tinyllama-1.1b", reduced=True)
    params = arch.init_params(jax.random.PRNGKey(0))
    return arch, params


@pytest.fixture(scope="module")
def workload():
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 256, (n,)).astype(np.int32) for n in LENS]
    return prompts, NEWS


def _engine(arch_params, spec=None, layout="dense", **kw):
    arch, params = arch_params
    sc = ServeConfig(max_len=MAX_LEN, kv_layout=layout, block_len=BLOCK_LEN,
                     spec=spec, **kw)
    return ServeEngine(arch, params, PLAN, sc)


def _run(eng, prompts, news, n_slots=3, segment_len=4, mode="while", **kw):
    if eng.sc.kv_layout == "paged":
        kw.setdefault("n_blocks", 24)
    sched = ContinuousScheduler(eng, n_slots=n_slots, segment_len=segment_len,
                                segment_mode=mode, **kw)
    handles = [sched.submit(p, n) for p, n in zip(prompts, news)]
    sched.run()
    assert all(h.done for h in handles)
    return [h.tokens for h in handles], sched


@pytest.fixture(scope="module")
def baseline(arch_params, workload):
    """Plain (non-speculative) scheduler outputs + the sequential oracle."""
    prompts, news = workload
    plain, _ = _run(_engine(arch_params), prompts, news)
    eng = _engine(arch_params)
    oracle = [
        list(np.asarray(eng.generate(jnp.asarray(p)[None, :], n))[0])
        for p, n in zip(prompts, news)
    ]
    assert plain == oracle  # PR 2 contract — spec tests lean on it below
    return plain


# ----------------------------------------------- bit-identicality matrix


@pytest.mark.parametrize("layout", ["dense", "paged"])
@pytest.mark.parametrize("k", [1, 2, 4])
def test_spec_bit_identical(arch_params, workload, baseline, layout, k):
    """Speculative greedy outputs equal the plain scheduler (and therefore
    the sequential oracle) bit-for-bit, whatever the drafter proposes —
    here a deliberately weak 1-layer drafter, so mismatch/rollback paths
    are exercised constantly."""
    prompts, news = workload
    spec = SpecConfig(k=k, draft="truncate:1")
    got, sched = _run(_engine(arch_params, spec, layout), prompts, news)
    assert got == baseline, (layout, k)
    st = sched.stats
    assert st["spec_steps"] > 0
    assert st["spec_emitted"] == sum(c * n for n, c in
                                     st["accepted_hist"].items())
    assert all(1 <= n <= k + 1 for n in st["accepted_hist"])


def test_spec_exact_drafter_accepts_everything(arch_params, workload, baseline):
    """A sparsity-0 self-drafter is an exact conversion of the served
    weights, so every draft matches: apart from eos/budget-truncated steps,
    each draft-and-verify round emits the full k+1 tokens."""
    prompts, news = workload
    spec = SpecConfig(k=2, draft="self", draft_sparsity=0.0)
    got, sched = _run(_engine(arch_params, spec), prompts, news)
    assert got == baseline
    hist = sched.stats["accepted_hist"]
    # full-window emissions dominate; every sub-window step must be
    # explained by a budget edge (one per request at most) — not rejection
    assert hist.get(3, 0) >= sum(c for n, c in hist.items() if n < 3)
    # the mean accepted length the autotuner prices speculation with
    assert spec_accept_len(sched.stats) >= 1.5


def test_spec_sparse_self_drafter_bit_identical(arch_params, workload, baseline):
    """A lossy (75%-sparse) self-drafter changes only the SPEED profile,
    never the output stream."""
    prompts, news = workload
    spec = SpecConfig(k=4, draft="self", draft_sparsity=0.75)
    got, _ = _run(_engine(arch_params, spec), prompts, news)
    assert got == baseline


def test_spec_scan_segments_match_while(arch_params, workload, baseline):
    prompts, news = workload
    spec = SpecConfig(k=2, draft="truncate:1")
    got, _ = _run(_engine(arch_params, spec), prompts, news, mode="scan")
    assert got == baseline


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_spec_with_chunked_prefill_admission(arch_params, workload, baseline,
                                             layout):
    """Speculative segments × batched/chunked admission, BOTH layouts — on
    the paged one this is the only deterministic cover of verify windows
    landing at the frozen cursors of mid-prefill (claimed, not yet active)
    slots whose block-table rows are still mostly scratch."""
    prompts, news = workload
    spec = SpecConfig(k=2, draft="truncate:1")
    got, _ = _run(_engine(arch_params, spec, layout), prompts, news,
                  prefill_chunk=8, prefill_buckets=2)
    assert got == baseline


# ------------------------------------------------- eos / budget boundaries


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_eos_within_draft_window(arch_params, layout):
    """An eos landing mid-window must cut acceptance exactly where the
    sequential scheduler stops: the eos is emitted, nothing after it."""
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, 256, (6,)).astype(np.int32)
    base = np.asarray(
        _engine(arch_params).generate(jnp.asarray(prompt)[None, :], 12)
    )[0]
    eos = int(base[5])  # a token greedy decoding emits mid-stream
    want, _ = _run(_engine(arch_params, layout=layout, eos_token=eos),
                   [prompt, prompt[:4]], [12, 8], n_slots=2)
    spec = SpecConfig(k=4, draft="truncate:1")
    got, sched = _run(
        _engine(arch_params, spec, layout=layout, eos_token=eos),
        [prompt, prompt[:4]], [12, 8], n_slots=2,
    )
    assert got == want
    assert got[0][-1] == eos and eos not in got[0][:-1]
    assert len(got[0]) < 12


def test_max_new_boundary_within_window(arch_params, workload, baseline):
    """Budgets that exhaust mid-window (max_new − 1 not a multiple of the
    window) truncate acceptance on the device exactly like the sequential
    limit check; max_new == 1 never reaches a segment at all."""
    prompts, news = workload
    spec = SpecConfig(k=4, draft="self", draft_sparsity=0.0)
    # full acceptance + budgets 1, 2, 5 ⇒ every boundary case is hit
    got, sched = _run(_engine(arch_params, spec), prompts, news, n_slots=2)
    assert got == baseline
    assert all(len(g) == n for g, n in zip(got, news))


# ----------------------------------------------------- rollback invariant


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_rollback_cache_beyond_cursor_never_read(arch_params, workload,
                                                 baseline, layout):
    """Cursor-truncation rollback is sound iff nothing ever reads cache
    content past a slot's accepted position.  Poison every such position
    with a large finite value between segments — any read of rejected-tail
    (or stale-tenant / free-block) KV would corrupt the greedy stream."""
    prompts, news = workload
    POISON = 1.0e4
    spec = SpecConfig(k=4, draft="truncate:1")
    eng = _engine(arch_params, spec, layout)
    kw = {"n_blocks": 24} if layout == "paged" else {}
    sched = ContinuousScheduler(eng, n_slots=3, segment_len=4,
                                segment_mode="while", **kw)
    handles = [sched.submit(p, n) for p, n in zip(prompts, news)]

    def poison():
        pos = np.asarray(sched.pos)
        if layout == "dense":
            idx = np.arange(MAX_LEN)  # (S,)
            # (n_slots, S): True where position >= slot cursor
            stale = idx[None, :] >= pos[:, None]
            mask = jnp.asarray(stale[None, :, :, None, None])
            sched.cache = {
                name: jnp.where(mask, jnp.asarray(POISON, leaf.dtype), leaf)
                for name, leaf in sched.cache.items()
            }
        else:
            nb_total = sched.n_slots + sched.n_blocks
            bl = sched.block_len
            # physical-block-position grid of logical positions per slot
            stale = np.ones((nb_total, bl), bool)  # default: poison all
            for slot in range(sched.n_slots):
                for j, phys in enumerate(sched.block_table[slot]):
                    logical = j * bl + np.arange(bl)
                    keep = logical < pos[slot]
                    stale[phys] &= ~keep
            mask = jnp.asarray(stale[None, :, :, None, None])
            sched.cache = {
                name: jnp.where(mask, jnp.asarray(POISON, leaf.dtype), leaf)
                for name, leaf in sched.cache.items()
            }

    for _ in range(10_000):
        if not sched.has_work():
            break
        sched.run_segment()
        poison()
    assert [h.tokens for h in handles] == baseline, layout


# ------------------------------------------------- compiled-once / traces


@pytest.mark.parametrize("mode", ["scan", "while"])
def test_spec_segment_compiled_once(arch_params, workload, mode):
    prompts, news = workload
    spec = SpecConfig(k=2, draft="truncate:1")
    eng = _engine(arch_params, spec)
    _, sched = _run(eng, prompts, news, mode=mode)
    seg_key = ("slot_spec_segment" if mode == "scan"
               else "slot_spec_segment_while")
    assert eng.trace_counts[seg_key] == 1
    assert getattr(eng, "_" + seg_key)._cache_size() == 1
    assert eng.call_counts[seg_key] == sched.stats["segments"]
    # the plain segment programs were never traced on the spec path
    assert eng.trace_counts["slot_segment"] == 0
    assert eng.trace_counts["slot_segment_while"] == 0


# ------------------------------------------------------ fallback / config


def test_spec_skip_reason_families():
    """Families without chunk-resume fall back to plain decode with the
    reason surfaced — exactly the chunked-prefill machinery."""
    for arch_id in ("rwkv6-3b", "zamba2-7b"):
        arch = get_arch(arch_id, reduced=True)
        reason = arch.spec_decode_skip_reason()
        assert reason and reason == arch.chunked_prefill_skip_reason()
    assert get_arch("tinyllama-1.1b", reduced=True).supports_spec_decode


def test_spec_falls_back_on_unsupported_family():
    arch = get_arch("rwkv6-3b", reduced=True)
    params = arch.init_params(jax.random.PRNGKey(0))
    sc = ServeConfig(max_len=MAX_LEN, spec=SpecConfig(k=2, draft="truncate:1"))
    eng = ServeEngine(arch, params, PLAN, sc)
    assert eng.spec is None and "rwkv" in eng.spec_skip_reason
    # the scheduler keeps serving (plain decode) and surfaces the reason
    prompts = [np.arange(1, 5, dtype=np.int32)]
    got, sched = _run(eng, prompts, [4], n_slots=1)
    assert sched.spec is None
    assert sched.stats["spec_skip_reason"] == eng.spec_skip_reason
    assert len(got[0]) == 4


def test_spec_runs_first_class_under_int8_cache(arch_params, workload):
    """The int8-quantized KV cache no longer disables speculation
    (ISSUE 10): verify rows attend the same dequantized values sequential
    decode attends, so draft-and-verify stays bit-identical to the
    sequential int8-KV oracle."""
    arch, params = arch_params
    plan = dataclasses.replace(PLAN, cache_quant_int8=True)
    sc = ServeConfig(max_len=MAX_LEN, spec=SpecConfig(k=2, draft="truncate:1"))
    eng = ServeEngine(arch, params, plan, sc)
    assert eng.spec is not None and not eng.spec_skip_reason

    oracle_eng = ServeEngine(arch, params, plan, ServeConfig(max_len=MAX_LEN))
    prompts, news = [np.arange(1, 9, dtype=np.int32),
                     np.arange(3, 8, dtype=np.int32)], [10, 6]
    oracle = [
        list(np.asarray(oracle_eng.generate(jnp.asarray(p)[None, :], n))[0])
        for p, n in zip(prompts, news)
    ]
    got, sched = _run(eng, prompts, news, n_slots=2)
    assert got == oracle
    assert sched.stats["spec_steps"] > 0
    assert sched.stats["spec_skip_reason"] == ""


def test_spec_rejects_sampling_temperature(arch_params):
    arch, params = arch_params
    with pytest.raises(AssertionError, match="greedy-only"):
        ServeEngine(arch, params, PLAN,
                    ServeConfig(max_len=MAX_LEN, temperature=0.7,
                                spec=SpecConfig(k=2)))


def test_spec_window_must_fit_scratch_block(arch_params):
    arch, params = arch_params
    with pytest.raises(AssertionError, match="scratch block"):
        ServeEngine(arch, params, PLAN,
                    ServeConfig(max_len=MAX_LEN, kv_layout="paged",
                                block_len=4, spec=SpecConfig(k=4)))


def test_submit_requires_draft_window_headroom(arch_params):
    eng = _engine(arch_params, SpecConfig(k=4, draft="truncate:1"))
    sched = ContinuousScheduler(eng, n_slots=1)
    with pytest.raises(ValueError, match="draft window"):
        sched.submit(np.arange(1, 31, dtype=np.int32), MAX_LEN - 32)


# ------------------------------------------------ drafter conversion units


def test_drafter_conversion_helpers(arch_params):
    from repro.core.sonic_layers import (
        sparse_draft_params, truncated_draft_params,
    )

    arch, params = arch_params
    # sparsity-0 conversion keeps every block → exact weights (the
    # full-acceptance oracle the matrix tests rely on)
    exact = sparse_draft_params(params, 0.0)
    for a, b in zip(jax.tree_util.tree_leaves(params["layers"]),
                    jax.tree_util.tree_leaves(exact["layers"])):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # clustered conversion confines each layer matrix to the codebook
    # (+ the pruned-block zeros)
    clustered = sparse_draft_params(params, 0.5, num_clusters=8)
    wq = np.asarray(clustered["layers"]["attn"]["wq"]["kernel"][0])
    assert len(np.unique(wq)) <= 9
    # truncation slices the stacked layers and shares everything else
    trunc = truncated_draft_params(params, 1)
    for leaf in jax.tree_util.tree_leaves(trunc["layers"]):
        assert leaf.shape[0] == 1
    assert trunc["embed"]["embedding"] is params["embed"]["embedding"]


# -------------------------------------------------- spec_accept unit tests


def _accept(window, verify, live, pos, limit, eos=-1):
    out = spec_accept(
        jnp.asarray(window, jnp.int32), jnp.asarray(verify, jnp.int32),
        jnp.asarray(live), jnp.asarray(pos, jnp.int32),
        jnp.asarray(limit, jnp.int32), eos,
    )
    return [np.asarray(o) for o in out]


def test_spec_accept_longest_prefix():
    # drafts d=[7, 9]; verifier says [7, 8, 3]: d1 matches v0, d2 != v1
    emitted, n, last = _accept([[5, 7, 9]], [[7, 8, 3]],
                               [True], [10], [100])
    assert emitted.tolist() == [[7, 8, -1]] and n[0] == 2 and last[0] == 8


def test_spec_accept_full_window_and_bonus():
    emitted, n, last = _accept([[5, 7, 8]], [[7, 8, 3]],
                               [True], [10], [100])
    assert emitted.tolist() == [[7, 8, 3]] and n[0] == 3 and last[0] == 3


def test_spec_accept_eos_cuts_window():
    # v0 is eos: emitted, but nothing after — even though drafts match
    emitted, n, last = _accept([[5, 2, 8]], [[2, 8, 3]],
                               [True], [10], [100], eos=2)
    assert emitted.tolist() == [[2, -1, -1]] and n[0] == 1 and last[0] == 2


def test_spec_accept_budget_cuts_window():
    # pos=10, limit=11: after the first emission pos'=11 >= limit → stop
    emitted, n, last = _accept([[5, 7, 8]], [[7, 8, 3]],
                               [True], [10], [11])
    assert emitted.tolist() == [[7, -1, -1]] and n[0] == 1 and last[0] == 7


def test_spec_accept_masked_slot_emits_nothing():
    emitted, n, _ = _accept([[5, 7, 8]], [[7, 8, 3]],
                            [False], [10], [100])
    assert emitted.tolist() == [[-1, -1, -1]] and n[0] == 0
