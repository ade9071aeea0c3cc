"""Serving counters and the energy report read off them: the scheduler's
``stats`` account every useful token of deterministic workloads (replays
after a preemption included), and the energy bridge prices photonic below
the electronic baseline from them."""
import logging
import re
import sys

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
    spec_accept_len,
    token_prices,
    trace_energy,
    useful_tokens,
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


def _run(arch, params, prefill_chunk=0, spec=None, kv_layout="dense"):
    sc = ServeConfig(max_len=64, temperature=0.0, kv_layout=kv_layout,
                     spec=spec)
    eng = ServeEngine(arch, params, MeshPlan(), sc)
    sched = ContinuousScheduler(eng, n_slots=2, segment_len=4,
                                segment_mode="while",
                                prefill_chunk=prefill_chunk)
    reqs = [sched.submit(p, n)
            for p, n in zip(_prompts(arch.cfg.vocab_size), NEWS)]
    sched.run()
    return sched, [list(r.tokens) for r in reqs]


def test_counters_match_stats_per_request(arch_params):
    sched, _ = _run(*arch_params)
    st = sched.stats
    assert st["prefill_tokens_real"] == sum(LENS)
    assert st["prefill_launches"] == 0  # per-request admission
    # every live slot-step of a plain decode segment emits exactly one
    # token, and prefill emits each request's first
    assert st["slot_steps_live"] == sum(NEWS) - len(NEWS)
    assert st["spec_steps"] == st["spec_emitted"] == 0
    assert useful_tokens(st) == sum(LENS) + sum(NEWS) - len(NEWS)
    assert sum(st["tenant_tokens"].values()) == sum(NEWS)


def test_counters_match_stats_chunked(arch_params):
    sched, _ = _run(*arch_params, prefill_chunk=8)
    st = sched.stats
    assert st["prefill_tokens_real"] == sum(LENS)
    assert st["prefill_launches"] >= 1
    # a bucketed launch pads, never drops: at least the real tokens ran
    assert st["prefill_tokens_launched"] >= st["prefill_tokens_real"]
    assert st["slot_steps_live"] == sum(NEWS) - len(NEWS)
    assert useful_tokens(st) == sum(LENS) + sum(NEWS) - len(NEWS)


def test_counters_match_stats_spec(arch_params):
    sched, _ = _run(*arch_params,
                    spec=SpecConfig(k=2, draft="self", draft_sparsity=0.0))
    st = sched.stats
    assert st["spec_emitted"] > 0, st  # spec actually ran
    # no plain decode: every live slot-step was a draft-and-verify step
    assert st["slot_steps_live"] == st["spec_steps"]
    assert st["spec_emitted"] == sum(NEWS) - len(NEWS)
    assert useful_tokens(st) == sum(LENS) + sum(NEWS) - len(NEWS)
    assert spec_accept_len(st) == st["spec_emitted"] / st["spec_steps"]


@pytest.mark.parametrize("preempt_mode, useful, replayed",
                         [("recompute", 89, 9), ("swap", 75, 0)])
def test_useful_tokens_after_preemption(arch_params, preempt_mode, useful,
                                        replayed):
    """An overcommitted pool preempts a request mid-flight; a recompute
    readmission prefills its prompt again and replays its emitted tokens,
    and the device's useful work counts both (75 tokens served, 14 more
    recomputed); a swap readmission recomputes nothing.  Pinned for this
    fixed workload."""
    arch, params = arch_params
    sc = ServeConfig(max_len=64, temperature=0.0, kv_layout="paged",
                     block_len=16)
    eng = ServeEngine(arch, params, MeshPlan(), sc)
    sched = ContinuousScheduler(eng, n_slots=2, segment_len=4,
                                segment_mode="while", n_blocks=3,
                                overcommit=2.0, preempt_mode=preempt_mode)
    reqs = [sched.submit(p, n)
            for p, n in zip(_prompts(arch.cfg.vocab_size), NEWS)]
    sched.run()
    st = sched.stats
    assert st["preemptions"] >= 1
    assert [len(r.tokens) for r in reqs] == NEWS
    assert st["replayed_tokens"] == replayed
    assert useful_tokens(st) == useful


def test_preempt_event_recorded(arch_params):
    arch, params = arch_params
    sc = ServeConfig(max_len=64, temperature=0.0, kv_layout="paged",
                     block_len=16)
    eng = ServeEngine(arch, params, MeshPlan(), sc)
    # tiny pool + overcommit forces at least one mid-flight preemption
    sched = ContinuousScheduler(eng, n_slots=2, segment_len=4,
                                segment_mode="while", n_blocks=3,
                                overcommit=2.0)
    for p, n in zip(_prompts(arch.cfg.vocab_size), NEWS):
        sched.submit(p, n)
    sched.run()
    st = sched.stats
    assert st["preemptions"] >= 1
    assert st["readmits"] == st["preemptions"]


def test_energy_bridge(arch_params):
    arch, _ = arch_params
    sched, _ = _run(*arch_params)
    prices = token_prices(arch.cfg, weight_sparsity=0.75, act_sparsity=0.5,
                          platforms=("SONIC", "NullHop"))
    rep = trace_energy(sched.stats, prices)
    assert rep["tokens"] == useful_tokens(sched.stats)
    sonic, nullhop = rep["platforms"]["SONIC"], rep["platforms"]["NullHop"]
    assert 0 < sonic["j_per_token"] < nullhop["j_per_token"]
    assert sonic["tok_per_s_per_w"] > nullhop["tok_per_s_per_w"]
    np.testing.assert_allclose(
        sonic["trace_energy_j"], sonic["j_per_token"] * rep["tokens"])


def test_offline_run_logs_energy_report(monkeypatch, tmp_path):
    """Every poisson run of the serving launcher ends with the energy
    report, priced from the scheduler's counters."""
    from repro.launch import serve as launcher

    # with the variable set the launcher leaves JAX's cache setting alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(sys, "argv", [
        "serve", "--reduced", "--workload", "poisson", "--n-requests", "3",
        "--rate", "1000", "--prompt-len", "6", "--new-tokens", "6",
        "--slots", "2", "--segment-len", "4"])
    lines = []
    handler = logging.Handler()
    handler.emit = lambda rec: lines.append(rec.getMessage())
    launcher.log.addHandler(handler)
    try:
        launcher.main()
    finally:
        launcher.log.removeHandler(handler)
    text = "\n".join(lines)
    m = re.search(r"useful tokens: (\d+) \((\d+) prompt \+ (\d+) decode "
                  r"\+ (\d+) speculative\)", text)
    assert m, text
    total, *parts = map(int, m.groups())
    assert total == sum(parts) > 0
    for name in ("SONIC", "NullHop", "NP100"):
        assert f"energy [{name:<7s}]" in text, text


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
