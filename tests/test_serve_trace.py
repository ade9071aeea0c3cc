"""Trace recorder (ISSUE 7): counters exactly match scheduler stats on
deterministic workloads; tracing off keeps the zero-overhead path; the
energy bridge prices photonic below the electronic baseline."""
import jax
import numpy as np
import pytest

from repro.models.registry import get_arch
from repro.roofline.autotune import KnobConfig, WorkloadSpec, autotune, predict
from repro.roofline.hw import TPU_V5E
from repro.serve import (
    ContinuousScheduler,
    ServeConfig,
    SpecConfig,
    ServeEngine,
    trace_energy,
)
from repro.sharding.mesh import MeshPlan

LENS = [4, 9, 6, 12]
NEWS = [20, 8, 16, 4]


@pytest.fixture(scope="module")
def arch_params():
    arch = get_arch("tinyllama-1.1b", reduced=True)
    params = arch.init_params(jax.random.PRNGKey(0))
    return arch, params


def _prompts(vocab):
    rng = np.random.RandomState(0)
    return [rng.randint(0, vocab, (n,)).astype(np.int32) for n in LENS]


def _run(arch, params, trace, prefill_chunk=0, spec=None, kv_layout="dense"):
    sc = ServeConfig(max_len=64, temperature=0.0, kv_layout=kv_layout,
                     spec=spec, trace=trace)
    eng = ServeEngine(arch, params, MeshPlan(), sc)
    sched = ContinuousScheduler(eng, n_slots=2, segment_len=4,
                                segment_mode="while",
                                prefill_chunk=prefill_chunk)
    reqs = [sched.submit(p, n)
            for p, n in zip(_prompts(arch.cfg.vocab_size), NEWS)]
    sched.run()
    return sched, [list(r.tokens) for r in reqs]


def test_counters_match_stats_per_request(arch_params):
    sched, _ = _run(*arch_params, trace=True)
    tr, st = sched.trace.totals, sched.stats
    assert tr["prefill_tokens"] == sum(LENS)
    assert tr["prefill_launches"] == st["admitted"]
    # every live slot-step of a plain decode segment emits exactly one token
    assert tr["decode_tokens"] == st["slot_steps_live"]
    assert tr["decode_segments"] == st["segments"]
    assert tr["decode_steps"] == st["steps_total"]
    # all useful tokens accounted: prefill emits each request's first token
    assert sched.trace.tokens_total == sum(LENS) + sum(NEWS) - len(NEWS)
    assert tr["decode_tokens"] == sum(NEWS) - len(NEWS)
    assert tr["spec_tokens"] == 0


def test_counters_match_stats_chunked(arch_params):
    sched, _ = _run(*arch_params, trace=True, prefill_chunk=8)
    tr, st = sched.trace.totals, sched.stats
    assert tr["prefill_tokens"] == sum(LENS)
    assert tr["prefill_launches"] == st["prefill_launches"]
    assert tr["decode_tokens"] == st["slot_steps_live"]
    prefills = [e for e in sched.trace.events if e.phase == "prefill"]
    assert len(prefills) == st["prefill_launches"]
    # a bucketed launch never exceeds the chunk length
    assert all(e.steps <= 8 for e in prefills)


def test_counters_match_stats_spec(arch_params):
    sched, _ = _run(*arch_params, trace=True,
                    spec=SpecConfig(k=2, draft="self", draft_sparsity=0.0))
    tr, st = sched.trace.totals, sched.stats
    assert st["spec_emitted"] > 0, st  # spec actually ran
    assert tr["spec_tokens"] == st["spec_emitted"]
    assert tr["spec_live_steps"] == st["spec_steps"]
    assert tr["decode_tokens"] == 0 and tr["decode_segments"] == 0


def test_trace_off_is_zero_overhead_and_identical(arch_params):
    assert ServeConfig().trace is False
    sched_off, outs_off = _run(*arch_params, trace=False)
    assert sched_off.trace is None  # no recorder object, hooks short-circuit
    sched_on, outs_on = _run(*arch_params, trace=True)
    assert outs_off == outs_on  # recording never perturbs scheduling
    assert sched_off.stats["slot_steps_live"] == sched_on.stats["slot_steps_live"]


def test_preempt_event_recorded(arch_params):
    arch, params = arch_params
    sc = ServeConfig(max_len=64, temperature=0.0, kv_layout="paged",
                     block_len=16, trace=True)
    eng = ServeEngine(arch, params, MeshPlan(), sc)
    # tiny pool + overcommit forces at least one mid-flight preemption
    sched = ContinuousScheduler(eng, n_slots=2, segment_len=4,
                                segment_mode="while", n_blocks=3,
                                overcommit=2.0)
    for p, n in zip(_prompts(arch.cfg.vocab_size), NEWS):
        sched.submit(p, n)
    sched.run()
    st, tr = sched.stats, sched.trace.totals
    assert st["preemptions"] >= 1
    assert tr["preemptions"] == st["preemptions"]


def test_energy_bridge(arch_params):
    arch, _ = arch_params
    sched, _ = _run(*arch_params, trace=True)
    rep = trace_energy(sched.trace, arch.cfg, weight_sparsity=0.75,
                       act_sparsity=0.5, platforms=("SONIC", "NullHop"))
    assert rep["tokens"] == sched.trace.tokens_total
    sonic, nullhop = rep["platforms"]["SONIC"], rep["platforms"]["NullHop"]
    assert 0 < sonic["j_per_token"] < nullhop["j_per_token"]
    assert sonic["tok_per_s_per_w"] > nullhop["tok_per_s_per_w"]
    np.testing.assert_allclose(
        sonic["trace_energy_j"], sonic["j_per_token"] * rep["tokens"])


# ---------------------------------------------------------------- autotune


def test_autotune_ranks_roundtrip_heavy_config_last():
    cfg = get_arch("tinyllama-1.1b", reduced=True).cfg
    w = WorkloadSpec(tuple(LENS), tuple(NEWS), n_slots=2, max_len=64)
    cands = [KnobConfig(segment_len=1), KnobConfig(segment_len=8),
             KnobConfig(segment_len=16, prefill_chunk=32)]
    res = autotune(cfg, w, TPU_V5E, candidates=cands)
    assert res.best.segment_len > 1  # per-token round trips rank last
    assert res.ranked[-1].knobs.segment_len == 1
    assert [p.tok_s for p in res.ranked] == sorted(
        (p.tok_s for p in res.ranked), reverse=True)
    assert res.best in [c for c in cands]
    assert "seg1_chunk0" in res.report()


def test_predict_is_deterministic_and_terminates():
    cfg = get_arch("tinyllama-1.1b", reduced=True).cfg
    w = WorkloadSpec((4, 16, 8), (30, 5, 12), n_slots=2, max_len=64)
    a = predict(KnobConfig(segment_len=8, prefill_chunk=16), w, cfg, TPU_V5E)
    b = predict(KnobConfig(segment_len=8, prefill_chunk=16), w, cfg, TPU_V5E)
    assert a == b
    assert a.time_s > 0 and a.tok_s > 0 and a.n_segments > 0
    # spec priced pessimistically at accept_len=1: never beats plain decode
    plain = predict(KnobConfig(segment_len=8), w, cfg, TPU_V5E)
    spec = predict(KnobConfig(segment_len=8, spec_k=4), w, cfg, TPU_V5E)
    assert spec.tok_s < plain.tok_s
