"""SLO-feedback overload control (PR 9): pure host-side tests.

No engine, no JAX compile.  Covers the brownout ladder (entry thresholds,
hysteresis band, dwell-gated step-down), seeded shedding, the level-2
prefill-knob clamp, elastic DRR redistribution, the ``Overloaded``
exception surface, and the ``DrainPredictor`` calibration contract.
"""
from __future__ import annotations

import collections

import numpy as np
import pytest

from repro.serve.policy import (Overloaded, PriorityClass, RateLimited,
                                SloConfig, SloMonitor, TenantPolicy,
                                TenantSpec)
from repro.serve.request import Request

# target class carries the deadline the controller steers toward
SLO_CLASSES = (
    PriorityClass("interactive", level=2, ttft_deadline_s=1.0),
    PriorityClass("standard", level=1),
    PriorityClass("batch", level=0),
)


def _monitor(**kw) -> SloMonitor:
    cfg = SloConfig(**{"min_obs": 1, **kw})
    return SloMonitor(cfg, {c.name: c for c in SLO_CLASSES})


def _req(rid: int, tenant: str, priority: str = "standard",
         cost: int = 100) -> Request:
    return Request(rid=rid, prompt=np.zeros(cost - 10, np.int32),
                   max_new_tokens=10, tenant=tenant, priority=priority)


# ------------------------------------------------------------- config guards

def test_slo_config_validation():
    with pytest.raises(ValueError, match="quantile"):
        SloConfig(quantile=1.0)
    with pytest.raises(ValueError, match="increasing"):
        SloConfig(enter=(0.9, 0.8, 1.1))
    with pytest.raises(ValueError, match="exit_ratio"):
        SloConfig(exit_ratio=1.5)
    with pytest.raises(ValueError, match="dwell"):
        SloConfig(dwell=0)
    with pytest.raises(ValueError, match="shed_frac"):
        SloConfig(shed_frac=(0.5, 1.5))


def test_monitor_validation():
    classes = {c.name: c for c in SLO_CLASSES}
    with pytest.raises(ValueError, match="not a priority class"):
        SloMonitor(SloConfig(target_class="gold"), classes)
    with pytest.raises(ValueError, match="no .*ttft_deadline_s"):
        SloMonitor(SloConfig(target_class="standard",
                             victim_class="batch"), classes)
    with pytest.raises(ValueError, match="rank below"):
        SloMonitor(SloConfig(victim_class="interactive"), classes)


# ---------------------------------------------------------------- the ladder

def test_ladder_steps_up_immediately_and_down_with_dwell():
    """Entry is immediate (possibly multi-level); exit takes ``dwell``
    consecutive quiet updates and moves one level at a time."""
    m = _monitor(dwell=3)
    # healthy: well under enter[0]*deadline = 0.6s
    m.observe_ttft("interactive", 0.2)
    assert m.update() is None and m.level == 0
    # blows straight through every threshold -> jumps to level 3 in one step
    for _ in range(8):
        m.observe_ttft("interactive", 2.0)
    assert m.update() == 3 and m.level == 3
    # recovery: fill the window with healthy samples (quantile below the
    # exit threshold 0.7*enter[2]*deadline = 0.77s)
    for _ in range(64):
        m.observe_ttft("interactive", 0.1)
    assert m.update() is None  # dwell 1
    assert m.update() is None  # dwell 2
    assert m.update() == 2     # dwell 3: one step down only
    assert m.update() is None and m.update() is None
    assert m.update() == 1
    assert m.update() is None and m.update() is None
    assert m.update() == 0 and m.level == 0
    assert m.level_changes == 4  # 0->3, 3->2, 2->1, 1->0


def test_hysteresis_band_holds_level():
    """Between the exit and entry thresholds the level neither rises nor
    falls, and the dwell counter resets — no flapping."""
    m = _monitor(dwell=2)
    for _ in range(8):
        m.observe_ttft("interactive", 0.65)  # over enter[0]=0.6
    assert m.update() == 1
    # 0.5 is below enter[0] but above exit 0.7*0.6 = 0.42: hold forever
    for _ in range(64):
        m.observe_ttft("interactive", 0.5)
    for _ in range(10):
        assert m.update() is None
    assert m.level == 1
    # one quiet update is not enough (dwell=2), and a loud one resets it
    for _ in range(64):
        m.observe_ttft("interactive", 0.1)
    assert m.update() is None
    for _ in range(64):
        m.observe_ttft("interactive", 0.5)
    assert m.update() is None  # back inside the band: dwell reset
    for _ in range(64):
        m.observe_ttft("interactive", 0.1)
    assert m.update() is None and m.update() == 0


def test_waiting_ages_raise_the_quantile_before_completions():
    """Queued target-class requests that have not seen a token yet push the
    ladder up — the controller reacts before the damage completes."""
    m = _monitor(min_obs=4)
    m.observe_ttft("interactive", 0.1)
    assert m.update() is None  # 1 obs < min_obs
    assert m.update([5.0, 5.0, 5.0]) == 3  # 3 waiting ages complete the sample
    assert m.last_quantile == 5.0


def test_window_bounds_memory():
    m = _monitor(window=8)
    for i in range(100):
        m.observe_ttft("interactive", float(i))
        m.observe_latency("interactive", float(i))
    snap = m.snapshot()["classes"]["interactive"]
    assert snap["observed"] == 8
    assert snap["ttft_p50_s"] >= 92.0  # only the tail survived


# ------------------------------------------------------------------ shedding

def test_shed_targets_only_degrading_classes():
    m = _monitor()
    for _ in range(8):
        m.observe_ttft("interactive", 5.0)
    assert m.update() == 3
    # level 3: victim admission fully closed, higher classes untouched
    assert m.should_shed("batch")
    assert not m.should_shed("standard")
    assert not m.should_shed("interactive")
    assert m.shed == {"batch": 1}
    assert m.degrades("batch")
    assert not m.degrades("standard") and not m.degrades("interactive")


def test_shed_is_seeded_and_fractional():
    """At level 1 sheds draw ``shed_frac[0]`` of victim submissions from a
    seeded stream: two monitors with the same seed agree decision-for-
    decision, and the long-run rate tracks the fraction."""
    def mk():
        m = _monitor(shed_frac=(0.5, 0.85), seed=7)
        for _ in range(8):
            m.observe_ttft("interactive", 0.65)
        assert m.update() == 1
        return m

    a, b = mk(), mk()
    da = [a.should_shed("batch") for _ in range(400)]
    db = [b.should_shed("batch") for _ in range(400)]
    assert da == db  # same seed, same schedule
    assert 0.4 < sum(da) / 400 < 0.6  # tracks shed_frac[0]=0.5
    assert a.shed["batch"] == sum(da)


def test_no_shed_at_level_zero():
    m = _monitor()
    assert not m.should_shed("batch") and m.shed == {}


# --------------------------------------------- policy integration + clamps

def _hot_policy(level: int, **kw) -> TenantPolicy:
    """A TenantPolicy with its SLO monitor driven to ``level``."""
    policy = TenantPolicy(classes=SLO_CLASSES,
                          slo=SloConfig(min_obs=1, **kw))
    if level:
        frac = {1: 0.65, 2: 0.9, 3: 5.0}[level]
        for _ in range(8):
            policy.observe_ttft("interactive", frac)
        assert policy.update_slo() == level
    return policy


def test_policy_shed_delegation_and_overloaded():
    policy = _hot_policy(3)
    assert policy.brownout_level == 3
    assert policy.should_shed("batch") and not policy.should_shed("standard")
    assert policy.shed_retry_after() >= 1.0
    err = Overloaded("acme", 2.5, "batch", 3)
    assert isinstance(err, RateLimited)  # rides every existing 429 path
    assert err.tenant == "acme" and err.retry_after_s == 2.5
    assert err.priority == "batch" and err.level == 3
    assert "brownout level 3" in str(err)


def test_level2_clamps_victim_prefill_knobs_to_min_bucket():
    """At level >= 2 the victim class's chunk cap and token budget shrink
    to the scheduler's smallest prefill bucket; the target class and the
    open-loop accessors are untouched."""
    policy = _hot_policy(2)
    policy.bind_chunk_buckets([8, 16, 32])
    assert policy.chunk_cap("batch") == 8
    assert policy.token_budget("batch") == 8
    assert policy.chunk_cap("interactive") == 0  # inherit, unclamped
    assert policy.token_budget("interactive") is None
    # below level 2 the knobs pass through
    cool = _hot_policy(1)
    cool.bind_chunk_buckets([8, 16, 32])
    assert cool.chunk_cap("batch") == 0
    assert cool.token_budget("batch") is None
    # without the scheduler handshake there is nothing to clamp to
    unbound = _hot_policy(2)
    assert unbound.chunk_cap("batch") == 0


def test_open_loop_policy_has_no_slo_surface():
    policy = TenantPolicy()
    assert policy.slo is None and policy.brownout_level == 0
    assert not policy.should_shed("batch")
    assert policy.update_slo([1.0]) is None
    assert policy.slo_snapshot() is None
    policy.observe_ttft("batch", 1.0)  # no-ops, no crash
    policy.observe_latency("batch", 1.0)


def test_snapshot_shape():
    policy = _hot_policy(1)
    policy.should_shed("batch")
    snap = policy.slo_snapshot()
    assert snap["brownout_level"] == 1
    assert snap["target_class"] == "interactive"
    assert snap["ttft_deadline_s"] == 1.0
    assert snap["last_quantile_s"] is not None
    cls = snap["classes"]
    assert set(cls) == {"interactive", "standard", "batch"}
    assert cls["interactive"]["observed"] == 8
    assert cls["batch"]["shed"] >= 0


# ------------------------------------------------------------- elastic DRR

def _admit_next(policy, queue):
    req = policy.select(queue)
    policy.on_admitted(queue, req)
    queue.remove(req)
    return req


def test_elastic_drr_redistributes_idle_share():
    """With an idle tenant holding half the registered weight, each active
    tenant's per-visit credit doubles: visits serve two equal-cost requests
    back-to-back instead of strictly alternating."""
    tenants = {"a": TenantSpec(), "b": TenantSpec(), "idle": TenantSpec(weight=2.0)}
    policy = TenantPolicy(tenants=tenants, quantum=64)
    queue: collections.deque = collections.deque()
    rid = 0
    for t in ("a", "b"):
        for _ in range(4):
            queue.append(_req(rid, t, cost=100))
            rid += 1
    served = []
    for _ in range(40):
        got = _admit_next(policy, queue)
        served.append(got.tenant)
        queue.append(_req(rid, got.tenant, cost=100))
        rid += 1
    # equal weights: shares stay equal over the window (visit continuation
    # may briefly run one tenant twice once banked credit covers its head)
    assert abs(served.count("a") - served.count("b")) <= 2, served
    # the redistributed credit shows up as faster service: with cost >
    # unscaled quantum a request is served on the FIRST visit (one cycle)
    # instead of banking deficit across cycles
    fresh = TenantPolicy(tenants=tenants, quantum=64)
    q2: collections.deque = collections.deque([_req(100, "a", cost=120)])
    assert fresh.select(q2).rid == 100
    d = dict(fresh._deficit)
    assert not d  # pure peek
    fresh.on_admitted(q2, q2[0])
    # "a" is the only backlogged tenant, so the whole registered weight
    # flows to it: credit 64*1*(4/1)=256 >= 120, served in one visit
    assert fresh._deficit[(1, "a")] == pytest.approx(136.0)


def test_elastic_drr_preserves_relative_shares():
    """The scale multiplies every active tenant's credit equally, so
    weighted shares among the ACTIVE set are unchanged."""
    tenants = {"a": TenantSpec(weight=3.0), "b": TenantSpec(weight=1.0),
               "idle": TenantSpec(weight=4.0)}
    policy = TenantPolicy(tenants=tenants)
    queue: collections.deque = collections.deque()
    rid = 0
    for t in ("a", "b"):
        for _ in range(2):
            queue.append(_req(rid, t))
            rid += 1
    served = collections.Counter()
    for _ in range(400):
        got = _admit_next(policy, queue)
        served[got.tenant] += 1
        queue.append(_req(rid, got.tenant))
        rid += 1
    assert abs(served["a"] / 400 - 0.75) < 0.05, served


# ---------------------------------------------------------- drain predictor

def test_drain_predictor_calibration():
    from repro.configs.base import get_config
    from repro.roofline.autotune import DrainPredictor, KnobConfig
    from repro.roofline.hw import TPU_V5E

    pred = DrainPredictor(get_config("tinyllama-1.1b"),
                          KnobConfig(segment_len=8), n_slots=4, max_len=192,
                          hw=TPU_V5E)
    assert not pred.calibrated
    assert pred.drain_s([16], [32]) is None  # cold: callers fall back
    pred.observe(16, 32, measured_s=2.0)
    assert pred.calibrated and pred.n_obs == 1
    d1 = pred.drain_s([16, 16], [32, 32])
    assert d1 is not None and d1 > 0
    # doubling the measured wall for the same shape doubles the EWMA target;
    # with alpha=0.2 the scale moves toward it monotonically
    s0 = pred.scale
    pred.observe(16, 32, measured_s=4.0)
    assert pred.scale > s0
    # empty queue drains in no time, reported as None (fallback)
    assert pred.drain_s([], []) is None
    # rejected observations leave the scale untouched
    s1 = pred.scale
    pred.observe(16, 0, measured_s=1.0)
    pred.observe(16, 32, measured_s=0.0)
    assert pred.scale == s1 and pred.n_obs == 2


def test_drain_predictor_memoizes_shape_buckets():
    from repro.configs.base import get_config
    from repro.roofline.autotune import DrainPredictor, KnobConfig
    from repro.roofline.hw import TPU_V5E

    pred = DrainPredictor(get_config("tinyllama-1.1b"),
                          KnobConfig(segment_len=8), n_slots=4, max_len=192,
                          hw=TPU_V5E)
    pred.observe(15, 30, 1.0)
    pred.observe(16, 31, 1.0)  # same power-of-two buckets (16, 32)
    assert len(pred._single) == 1
    pred.observe(33, 30, 1.0)  # new plen bucket (64)
    assert len(pred._single) == 2
