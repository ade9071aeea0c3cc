"""The reduction from trace events to what the per-layer metrics read:
hand-counted on a synthetic window, and on a slice of a trace recorded on a
TPU v5e (``testdata/trace_v5e.json.gz``) against an independent count."""
import gzip
import json
import pathlib

import pytest

from bench import trace_reduce as tr

E = tr.Event
DATA = pathlib.Path(__file__).resolve().parent / "testdata" / "trace_v5e.json.gz"


def test_hand_counted_window():
    # window 0..100 ns; ops overlap at 10..30 and 25..40, then 60..70
    ops = [E("a", 10, 30), E("b", 25, 40), E("c", 60, 70), E("d", 95, 130)]
    modules = [E("jit_segment", 10, 40), E("jit_prefill_slots", 60, 70),
               E("jit_segment", 95, 130)]
    spans = [E(tr.WINDOW_SPAN, 0, 100), E("bench.run_segment", 0, 55),
             E("bench.segment", 5, 45), E("bench.submit", 50, 90)]
    r = tr.reduce(ops, modules, spans)
    assert r.window_s == pytest.approx(100e-9)
    # union: 10..40, 60..70, 95..100 (clipped) = 30 + 10 + 5
    assert r.busy_s == pytest.approx(45e-9)
    assert r.module_s == pytest.approx({"jit_segment": 35e-9,
                                        "jit_prefill_slots": 10e-9})
    assert r.op_s["a"] == pytest.approx(20e-9) and r.op_s["d"] == pytest.approx(5e-9)
    # idle gaps 0..10 (mid 5: run_segment and segment; segment started
    # last), 40..60 (mid 50: run_segment and submit; submit started last),
    # 70..95 (mid 82.5: submit)
    assert r.idle_by_span == pytest.approx({"bench.segment": 10e-9,
                                            "bench.submit": 45e-9})
    assert r.n_ops == 4


def test_window_span_must_be_unique():
    with pytest.raises(ValueError):
        tr.reduce([], [], [E("bench.run_segment", 0, 1)])


def _brute_busy(ops, lo, hi):
    """Busy time by sweeping every event edge: an independent count."""
    edges = sorted({lo, hi, *[min(max(x, lo), hi) for e in ops
                              for x in (e.start_ns, e.end_ns)]})
    busy = 0.0
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        if any(e.start_ns <= mid < e.end_ns for e in ops):
            busy += b - a
    return busy / 1e9


def test_recorded_v5e_trace():
    raw = json.loads(gzip.decompress(DATA.read_bytes()))
    ops, modules, spans = ([E(*e) for e in raw[k]] for k in ("ops", "modules", "spans"))
    r = tr.reduce(ops, modules, spans)
    win = next(e for e in spans if e.name == tr.WINDOW_SPAN)
    assert r.window_s == pytest.approx((win.end_ns - win.start_ns) / 1e9)
    assert r.busy_s == pytest.approx(_brute_busy(ops, win.start_ns, win.end_ns))
    assert r.busy_s < r.window_s
    # the slot programs' modules as the v5e names them, and their time
    assert "jit_segment" in r.module_s
    for name in r.module_s:
        clipped = sum(max(0, min(e.end_ns, win.end_ns) - max(e.start_ns, win.start_ns))
                      for e in modules if e.name == name)
        assert r.module_s[name] == pytest.approx(clipped / 1e9)
    idle = sum(r.idle_by_span.values())
    assert idle == pytest.approx(r.window_s - r.busy_s)
