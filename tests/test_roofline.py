"""Roofline machinery: analytic cost sanity, HLO collective parsing, terms."""
import jax
import numpy as np

from repro.configs.base import SHAPES, get_config
from repro.roofline.analysis import (
    parse_collectives,
    roofline_terms,
)
from repro.roofline.analytic import _param_counts, analytic_cost
from repro.roofline.hw import TPU_V5E


def test_param_counts_match_eval_shape():
    """Closed-form N_total vs actual initialized trees, all 10 archs."""
    from repro.models.registry import get_arch
    from repro.utils.tree import tree_param_count

    for aid in ("tinyllama-1.1b", "internlm2-1.8b", "rwkv6-3b",
                "moonshot-v1-16b-a3b", "zamba2-7b", "hubert-xlarge"):
        arch = get_arch(aid)
        actual = tree_param_count(arch.abstract_params())
        _, total = _param_counts(arch.cfg)
        assert abs(actual - total) / actual < 0.02, (aid, actual, total)


def test_six_nd_rule_for_dense_train():
    cfg = get_config("tinyllama-1.1b")
    cost = analytic_cost(cfg, SHAPES["train_4k"])
    tokens = 256 * 4096
    six_nd = 6.0 * cost.n_active * tokens
    # model_flops = 6·N·D + causal attention ⇒ within ~25% of the rule
    assert six_nd <= cost.model_flops <= 1.4 * six_nd


def test_decode_is_weight_bound_in_analytic_model():
    cfg = get_config("command-r-35b")
    cost = analytic_cost(cfg, SHAPES["decode_32k"])
    # decode arithmetic intensity ≈ 2 flop/byte ⇒ memory term dominates at
    # v5e's 240 flop/byte ridge
    terms = roofline_terms(cost.model_flops, cost.hlo_flops_est,
                           cost.hbm_bytes, 0.0, 256, TPU_V5E)
    assert terms.dominant == "memory"


def test_train_is_compute_bound_in_analytic_model():
    cfg = get_config("command-r-35b")
    cost = analytic_cost(cfg, SHAPES["train_4k"])
    terms = roofline_terms(cost.model_flops, cost.hlo_flops_est,
                           cost.hbm_bytes, 0.0, 256, TPU_V5E)
    assert terms.dominant == "compute"


_HLO = """\
ENTRY %main (a: f32[8,128]) -> f32[] {
  %w = f32[8,128]{1,0} parameter(0)
  %t = (s32[], f32[8,128]) while(%init), condition=%cond.1, body=%body.1
  ROOT %r = f32[] reduce(%t)
}
%body.1 (arg: (s32[], f32[8,128])) -> (s32[], f32[8,128]) {
  %ar = f32[8,128]{1,0} all-reduce(f32[8,128]{1,0} %x), replica_groups=[2,16]<=[32]
  %ag = f32[8,2048]{1,0} all-gather(f32[8,128]{1,0} %x), replica_groups=[2,16]<=[32]
}
%cond.1 (arg: (s32[], f32[8,128])) -> pred[] {
  %c = s32[] constant(24)
  ROOT %cmp = pred[] compare(%i, %c), direction=LT
}
"""


def test_collective_parser_trip_counts_and_ring_costs():
    colls = parse_collectives(_HLO)
    kinds = {c.kind: c for c in colls}
    assert set(kinds) == {"all-reduce", "all-gather"}
    ar = kinds["all-reduce"]
    assert ar.trip_count == 24
    assert ar.group_size == 16
    bytes_op = 8 * 128 * 4
    np.testing.assert_allclose(ar.wire_bytes, 2 * bytes_op * 15 / 16 * 24)
    ag = kinds["all-gather"]
    out_bytes = 8 * 2048 * 4
    np.testing.assert_allclose(ag.wire_bytes, out_bytes * 15 / 16 * 24)


def test_roofline_dominant_selection():
    t = roofline_terms(1e12, 2e12, 1e9, 1e6, 256, TPU_V5E)
    assert t.useful_fraction == 0.5
    assert t.dominant in ("compute", "memory", "collective")
    assert t.step_time_est_s == max(t.compute_s, t.memory_s, t.collective_s)


def test_moe_active_params_much_smaller_than_total():
    cfg = get_config("moonshot-v1-16b-a3b")
    cost = analytic_cost(cfg, SHAPES["train_4k"])
    assert cost.n_active < 0.25 * cost.n_total


# ------------------------------------------------- serving step costs (PR 7)


def test_decode_step_cost_matches_closed_form():
    """Hand-computed executed flops/bytes for a plain-attention config."""
    from repro.roofline.analytic import decode_step_cost

    cfg = get_config("tinyllama-1.1b")
    b, s = 3, 40
    c = decode_step_cost(cfg, b, s)
    n_active, _ = _param_counts(cfg)
    h, kh, dh, d, L = (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                       cfg.d_model, cfg.n_layers)
    want_flops = 2.0 * n_active * b + 4.0 * h * dh * s * b * L
    want_bytes = (n_active * 2.0 + 2.0 * b * s * kh * dh * 2.0 * L
                  + 4.0 * b * d * 2.0 * L)
    np.testing.assert_allclose(c.flops, want_flops, rtol=1e-12)
    np.testing.assert_allclose(c.hbm_bytes, want_bytes, rtol=1e-12)


def test_decode_step_cost_consistent_with_analytic_cost():
    from repro.roofline.analytic import decode_step_cost
    from repro.configs.base import ShapeSpec

    for aid in ("tinyllama-1.1b", "moonshot-v1-16b-a3b", "rwkv6-3b"):
        cfg = get_config(aid)
        c = decode_step_cost(cfg, 4, 128)
        cell = analytic_cost(cfg, ShapeSpec("x", 128, 4, "decode"))
        assert c.flops == cell.hlo_flops_est, aid
        assert c.hbm_bytes == cell.hbm_bytes, aid


def test_prefill_chunk_cost_matches_closed_form():
    from repro.roofline.analytic import prefill_chunk_cost

    cfg = get_config("tinyllama-1.1b")
    batch, chunk, start = 2, 16, 32
    c = prefill_chunk_cost(cfg, batch, chunk, start=start)
    n_active, n_total = _param_counts(cfg)
    h, kh, dh, d, L = (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                       cfg.d_model, cfg.n_layers)
    tokens = batch * chunk
    # token i of a row starting at `start` attends start+i+1 keys
    ctx_sum = batch * (chunk * start + chunk * (chunk + 1) / 2.0)
    want_flops = 2.0 * n_active * tokens + 4.0 * h * dh * ctx_sum * L
    want_bytes = (2.0 * n_total + 8.0 * tokens * d * 2.0 * L
                  + 2.0 * ctx_sum * kh * dh * 2.0 * L)
    np.testing.assert_allclose(c.flops, want_flops, rtol=1e-12)
    np.testing.assert_allclose(c.hbm_bytes, want_bytes, rtol=1e-12)
    # explicit ctx_sum overrides the uniform-start closed form
    c2 = prefill_chunk_cost(cfg, batch, chunk, ctx_sum=ctx_sum)
    np.testing.assert_allclose(c2.flops, c.flops, rtol=1e-12)


def test_spec_verify_cost_is_draft_plus_verify():
    from repro.roofline.analytic import (decode_step_cost, prefill_chunk_cost,
                                         spec_verify_cost)
    import dataclasses as _dc

    cfg = get_config("tinyllama-1.1b")
    k, b, s = 4, 3, 96
    c = spec_verify_cost(cfg, k, b, s, draft_layers=2)
    draft = decode_step_cost(_dc.replace(cfg, n_layers=2), b, s)
    verify = prefill_chunk_cost(cfg, b, k + 1, start=s)
    np.testing.assert_allclose(c.flops, k * draft.flops + verify.flops)
    np.testing.assert_allclose(c.hbm_bytes,
                               k * draft.hbm_bytes + verify.hbm_bytes)


def test_step_time_is_roofline_max():
    from repro.roofline.analytic import StepCost, step_time

    compute_bound = StepCost(1e15, 1.0, {})
    memory_bound = StepCost(1.0, 1e12, {})
    np.testing.assert_allclose(step_time(compute_bound, TPU_V5E),
                               1e15 / TPU_V5E.peak_flops_bf16)
    np.testing.assert_allclose(step_time(memory_bound, TPU_V5E),
                               1e12 / TPU_V5E.hbm_bw)


def test_peak_table_keyed_by_device_kind():
    import pytest

    from repro.roofline.hw import PEAKS

    v5e = PEAKS["TPU v5 lite"]  # jax.devices()[0].device_kind on a v5e
    assert v5e is TPU_V5E
    assert (v5e.peak_flops_bf16, v5e.peak_ops_int8, v5e.hbm_bw) == (
        197e12, 393e12, 819e9)
    with pytest.raises(KeyError):
        PEAKS["cpu"]


def test_device_peaks_resolves_the_running_device():
    """Entry points price against the device they run on: a v5e by its
    kind, the CPU as the v5e it rehearses, any other accelerator raises."""
    from types import SimpleNamespace

    import jax
    import pytest

    from repro.roofline.hw import device_peaks

    assert device_peaks(jax.devices()[0]) is TPU_V5E  # tests run on the CPU
    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert device_peaks(v5e) is TPU_V5E
    for platform, kind in (("tpu", "TPU v4"), ("gpu", "NVIDIA H100")):
        with pytest.raises(ValueError, match=kind):
            device_peaks(SimpleNamespace(platform=platform, device_kind=kind))
