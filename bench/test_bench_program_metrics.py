"""The per-layer metrics read from the program's phase counters, on a
hand-made context: host and dispatch shares of the window, the share of
launched prefill tokens that were real, and nothing read from a program
that keeps no such counters."""
import pytest

from bench import spec

BEFORE = {"host_s_sweep": 0.5, "host_s_admit": 1.0, "host_s_grow": 0.25,
          "host_s_retire": 2.0, "dispatch_s_prefill": 0.75,
          "dispatch_s_segment": 3.0, "prefill_tokens_real": 100,
          "prefill_tokens_launched": 160, "slot_steps_live": 0,
          "slot_steps_masked": 0}
AFTER = {**BEFORE, "host_s_sweep": 0.55, "host_s_admit": 1.15,
         "host_s_grow": 0.30, "host_s_retire": 2.25,
         "dispatch_s_prefill": 0.85, "dispatch_s_segment": 3.15,
         "prefill_tokens_real": 148, "prefill_tokens_launched": 224}


def ctx(stats0, stats1, t0=10.0, t1=14.0):
    return spec.MetricContext(reduced=None, stats0=stats0, stats1=stats1,
                              launches=None, records=[], t0=t0, t1=t1,
                              shapes=None, peaks={})


def read(name, c):
    return spec.metric_reader(name)(c)


def test_host_share_of_the_window():
    # (0.05 + 0.15 + 0.05 + 0.25) s of host work in a 4 s window
    assert read("sched.host_pct", ctx(BEFORE, AFTER)) == pytest.approx(12.5)


def test_dispatch_share_of_the_window():
    # (0.10 + 0.15) s of dispatch in a 4 s window
    assert read("engine.dispatch_pct", ctx(BEFORE, AFTER)) == pytest.approx(6.25)


def test_useful_prefill_tokens():
    # 48 real tokens of 64 launched
    assert read("prefill.useful_tokens_pct", ctx(BEFORE, AFTER)) == pytest.approx(75.0)


@pytest.mark.parametrize("name", ["sched.host_pct", "engine.dispatch_pct",
                                  "prefill.useful_tokens_pct"])
def test_nothing_to_read_is_none(name):
    # a program without the counters (the metric is new) reads nothing
    bare = {"slot_steps_live": 0, "slot_steps_masked": 0}
    assert read(name, ctx(bare, bare)) is None
    # no window, or no prefill launched in it
    if name == "prefill.useful_tokens_pct":
        assert read(name, ctx(BEFORE, BEFORE)) is None
    else:
        assert read(name, ctx(BEFORE, AFTER, t0=5.0, t1=5.0)) is None
