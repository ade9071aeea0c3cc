"""Deadline edge cases under preemption/chunking (ISSUE 9 satellite).

Engine-backed tests for the corners where the terminal sweep overlaps the
overcommit machinery:

* expiry mid-prefill-chunk — a TTFT deadline passing while the slot is
  still walking its prompt chunks;
* expiry mid-replay — a total deadline passing while a recompute readmit
  is still re-deriving its already-emitted tokens;
* ``Request.cancel()`` racing preemption victim selection in the same
  segment (forced pool exhaustion);
* priority-aware victim selection — with a ``TenantPolicy`` installed,
  pool exhaustion evicts batch before interactive even when interactive
  has less progress;
* SLO control — an interactive request waiting past its class deadline
  raises the brownout ladder, which sheds the next batch submission.

Every case asserts blocks are reclaimed and the allocator invariants hold
(``debug_invariants`` also checks them after every segment), and that
survivors stay bit-identical to the offline oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.registry import get_arch
from repro.serve import (ChaosConfig, ContinuousScheduler, Overloaded,
                         PriorityClass, ServeConfig, ServeEngine, SloConfig,
                         TenantPolicy, TenantSpec)
from repro.sharding.mesh import MeshPlan

PLAN = MeshPlan()
MAX_LEN, BLOCK_LEN = 64, 8


@pytest.fixture(scope="module")
def arch_params():
    arch = get_arch("tinyllama-1.1b", reduced=True)
    params = arch.init_params(jax.random.PRNGKey(0))
    return arch, params


@pytest.fixture(scope="module")
def engines(arch_params):
    arch, params = arch_params

    def mk(layout):
        sc = ServeConfig(max_len=MAX_LEN, kv_layout=layout,
                         block_len=BLOCK_LEN, debug_invariants=True)
        return ServeEngine(arch, params, PLAN, sc)

    return {"paged": mk("paged"), "oracle": mk("dense")}


def _prompt(seed, length):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (length,), 0, 256),
        np.int32,
    )


def _oracle(engines, prompts, news):
    eng = engines["oracle"]
    return [
        list(np.asarray(eng.generate(jnp.asarray(p)[None, :], n))[0])
        for p, n in zip(prompts, news)
    ]


def _drain(sched, max_iters=10_000):
    for _ in range(max_iters):
        if not sched.has_work():
            return
        sched.run_segment()
    raise RuntimeError("scheduler did not drain — deadlock?")


def _slot_of(sched, req):
    for slot, r in enumerate(sched.slots):
        if r is req:
            return slot
    return None


# -------------------------------------------------- expiry mid-prefill-chunk

def test_ttft_expiry_mid_prefill_chunk(engines):
    """A long prompt walking 8-token chunks under a tight prefill token
    budget blows its TTFT deadline between chunks: it retires EXPIRED with
    zero tokens, its blocks return immediately, and the short survivor
    completes bit-identically."""
    t = {"now": 0.0}
    sched = ContinuousScheduler(
        engines["paged"], n_slots=2, segment_len=4, n_blocks=16,
        prefill_chunk=8, prefill_buckets=2, prefill_token_budget=8,
        clock=lambda: t["now"])
    want = _oracle(engines, [_prompt(20, 6)], [10])[0]
    hv = sched.submit(_prompt(21, 40), 8, ttft_deadline_s=1.0)
    hs = sched.submit(_prompt(20, 6), 10)
    sched.run_segment()  # budget 8: hv advanced at most one chunk
    slot = _slot_of(sched, hv)
    assert slot is not None and slot in sched._prefill_start, (
        "setup: hv must still be mid-prefill for the case to bite")
    assert hv.first_token_t is None
    held = len(sched.allocator.mapped.get(slot, ()))
    t["now"] = 2.0  # past hv's TTFT deadline, mid-chunk-walk
    sched.run_segment()
    assert hv.expired and hv.tokens == []
    assert slot not in sched._prefill_start
    assert held > 0 and slot not in sched.allocator.mapped
    sched.check_block_invariants()
    _drain(sched)
    assert hs.done and hs.tokens == want
    assert sched.stats["expired"] == 1
    assert sched.allocator.n_free == sched.allocator.capacity


# ---------------------------------------------------------- expiry mid-replay

def test_deadline_expiry_mid_replay(engines):
    """Preempt a mid-flight request, let its recompute readmission start
    replaying, then blow its total deadline while the replay deque is
    non-empty: it retires EXPIRED holding an oracle prefix, the replay
    state is dropped with the slot, and the pool fully recovers."""
    t = {"now": 0.0}
    sched = ContinuousScheduler(
        engines["paged"], n_slots=2, segment_len=4, n_blocks=16,
        clock=lambda: t["now"])
    news = [24, 12]
    prompts = [_prompt(30, 8), _prompt(31, 6)]
    want = _oracle(engines, prompts, news)
    hv = sched.submit(prompts[0], news[0], deadline_s=50.0)
    hs = sched.submit(prompts[1], news[1])
    while len(hv.tokens) < 6:
        sched.run_segment()
    victim_slot = _slot_of(sched, hv)
    assert victim_slot is not None
    sched._preempt_slot(victim_slot)  # white-box: forced eviction
    assert sched.queue[0] is hv and hv.preempts == 1
    emitted_at_preempt = len(hv.tokens)
    # run until the readmission is mid-replay: re-prefilled, replay pending
    for _ in range(200):
        sched.run_segment()
        slot = _slot_of(sched, hv)
        if slot is not None and sched._replay.get(slot):
            break
    else:
        pytest.fail("readmission never reached a mid-replay boundary")
    t["now"] = 60.0  # past hv's total deadline while replay is pending
    sched.run_segment()
    assert hv.expired
    assert _slot_of(sched, hv) is None and not sched._replay
    # the host mirror never rolled back: still an oracle prefix, and the
    # replayed tokens never re-emitted
    assert len(hv.tokens) >= emitted_at_preempt
    assert hv.tokens == want[0][:len(hv.tokens)]
    sched.check_block_invariants()
    _drain(sched)
    assert hs.done and hs.tokens == want[1]
    assert sched.allocator.n_free == sched.allocator.capacity


# ------------------------------------- cancel vs victim selection, same segment

def test_cancel_races_victim_selection_same_segment(engines):
    """Cancel a resident in the same segment a forced pool exhaustion
    selects preemption victims: the sweep retires (and reclaims) the
    cancelled slot BEFORE victim selection runs, nothing double-frees, and
    survivors complete bit-identically."""
    news = [20, 20, 20]
    prompts = [_prompt(40 + i, 7) for i in range(3)]
    want = _oracle(engines, prompts, news)
    sched = ContinuousScheduler(
        engines["paged"], n_slots=2, segment_len=4, n_blocks=8,
        overcommit=2.0, chaos=ChaosConfig(seed=0, exhaust_at=(3, 4, 5)))
    handles = [sched.submit(p, n) for p, n in zip(prompts, news)]
    while sched.stats["segments"] < 3:
        sched.run_segment()
    # cancel the least-progressed resident — the scheduler's own victim
    # preference — right before the exhaust segment sweeps
    residents = [s for s in range(2) if sched.slots[s] is not None]
    assert len(residents) == 2
    victim = min(residents, key=sched._progress_key)
    cancelled = sched.slots[victim]
    cancelled.cancel()
    held = len(sched.allocator.mapped[victim])
    sched.run_segment()  # chaos exhaust + cancel sweep in the SAME segment
    assert sched.stats["chaos_exhausts"] >= 1
    assert cancelled.cancelled
    assert sched.stats["blocks_reclaimed_cancel"] >= held > 0
    sched.check_block_invariants()
    _drain(sched)
    for h, w in zip(handles, want):
        if h is cancelled:
            assert h.tokens == w[:len(h.tokens)]
        else:
            assert h.done and h.tokens == w, h.rid
    assert sched.allocator.n_free == sched.allocator.capacity


# --------------------------------------------- priority-aware victim selection

def test_pool_exhaustion_evicts_batch_before_interactive(engines):
    """With a policy installed, forced exhaustion picks the batch resident
    as victim even though an interactive resident has LESS progress — the
    PR 9 class-aware ordering (PR 6 would have evicted least-progress)."""
    policy = TenantPolicy(
        tenants={"it": TenantSpec(default_priority="interactive"),
                 "bt": TenantSpec(default_priority="batch")})
    news = [24, 24, 24]
    prompts = [_prompt(50 + i, 6) for i in range(3)]
    want = _oracle(engines, prompts, news)
    sched = ContinuousScheduler(
        engines["paged"], n_slots=3, segment_len=4, n_blocks=12,
        overcommit=2.0, policy=policy,
        chaos=ChaosConfig(seed=0, exhaust_at=tuple(range(3, 12))))
    # staggered arrivals fix the progress order: interactive A (most,
    # protected) > batch C (middle) > interactive B (least)
    ha = sched.submit(prompts[0], news[0], tenant="it")
    sched.run_segment()
    sched.run_segment()
    hc = sched.submit(prompts[1], news[1], tenant="bt")
    sched.run_segment()
    hb = sched.submit(prompts[2], news[2], tenant="it")
    assert sched.stats["segments"] == 3
    for _ in range(40):
        if sched.stats["preemptions"] >= 1:
            break
        sched.run_segment()
    else:
        pytest.fail("forced exhaustion never produced a preemption")
    assert len(ha.tokens) > len(hc.tokens) >= 0  # progress order as built
    # class-aware victim order: every eviction so far hit the batch class
    assert sched.stats["preemptions_by_class"] == {"batch": sched.stats["preemptions"]}
    assert hc.preempts >= 1 and hb.preempts == 0 and ha.preempts == 0
    sched.chaos = None  # stop injecting; let the schedule drain clean
    _drain(sched)
    for h, w in zip((ha, hc, hb), want):
        assert h.done and h.tokens == w, h.rid
    assert sched.allocator.n_free == sched.allocator.capacity


def test_brownout_sheds_batch_while_interactive_waits(engines):
    """SLO control disrupts batch work: an interactive request queued for
    twice its class's TTFT deadline steps the brownout ladder to its top
    level at the next segment, and the next batch submission is shed; the
    requests already in stay bit-identical to the oracle."""
    t = {"now": 0.0}
    policy = TenantPolicy(
        tenants={"it": TenantSpec(default_priority="interactive"),
                 "bt": TenantSpec(default_priority="batch")},
        classes=(PriorityClass("interactive", level=2, ttft_deadline_s=1.0),
                 PriorityClass("standard", level=1),
                 PriorityClass("batch", level=0)),
        slo=SloConfig(min_obs=1))
    sched = ContinuousScheduler(engines["paged"], n_slots=1, segment_len=4,
                                n_blocks=16, policy=policy,
                                clock=lambda: t["now"])
    prompts, news = [_prompt(900, 6), _prompt(901, 6)], [16, 8]
    want = _oracle(engines, prompts, news)
    hb = sched.submit(prompts[0], news[0], tenant="bt")
    sched.run_segment()  # the batch request takes the only slot
    # its own deadline is far off: it waits, it does not expire
    hi = sched.submit(prompts[1], news[1], tenant="it", ttft_deadline_s=100.0)
    t["now"] = 2.0
    sched.run_segment()
    assert policy.brownout_level == 3
    assert sched.stats["brownout_changes"] >= 1
    with pytest.raises(Overloaded):
        sched.submit(_prompt(902, 6), 8, tenant="bt")
    assert policy.slo.shed == {"batch": 1}
    _drain(sched)
    assert hb.tokens == want[0] and hi.tokens == want[1]
