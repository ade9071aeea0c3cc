"""Spans and phase counters of ``ContinuousScheduler.run_segment``: flat,
growing counters that a shallow ``dict(stats)`` snapshot can difference;
hand-counted prefill token shapes; one ``serve.*`` profiler span per phase
per segment, nested under ``serve.run_segment`` and tagged with its
segment; outputs unchanged by the profiler; named scopes in the compiled
slot programs' metadata."""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.registry import get_arch
from repro.serve import ContinuousScheduler, ServeConfig, ServeEngine
from repro.serve.trace import Phases
from repro.sharding.mesh import MeshPlan

TIMED = ("host_s_sweep", "host_s_admit", "host_s_grow", "host_s_retire",
         "dispatch_s_prefill", "dispatch_s_segment")
TOKENS = ("prefill_tokens_real", "prefill_tokens_launched")
# spans every run_segment call that launches a segment opens exactly once
ONCE = ("serve.run_segment", "serve.sweep", "serve.admit", "serve.grow",
        "serve.segment_dispatch", "serve.segment_wait", "serve.retire")
SCOPES = ("attn.qkv", "attn.rope", "kv.write", "kv.gather", "attn.core",
          "attn.out", "ffn", "lm_head", "sample")


@pytest.fixture(scope="module")
def engine():
    arch = get_arch("tinyllama-1.1b", reduced=True)
    params = arch.init_params(jax.random.PRNGKey(0))
    sc = ServeConfig(max_len=64, temperature=0.0, kv_layout="paged",
                     block_len=8)
    return ServeEngine(arch, params, MeshPlan(), sc)


def _sched(eng, prefill_chunk=8):
    # chunk 8 with 2 buckets: final chunks pad to 4 or 8 tokens
    return ContinuousScheduler(eng, n_slots=4, segment_len=4,
                               segment_mode="while",
                               prefill_chunk=prefill_chunk, prefill_buckets=2)


def _submit(sched, lens, max_new=6):
    vocab = sched.engine.cfg.vocab_size
    rng = np.random.RandomState(0)
    return [sched.submit(rng.randint(0, vocab, (n,)).astype(np.int32), max_new)
            for n in lens]


def test_phase_counters_take_self_time():
    ticks = iter(range(100))
    stats = {"outer": 0.0, "inner": 0.0}
    phases = Phases(stats, lambda: float(next(ticks)))
    with phases("outer", "outer"):  # clock 0 .. 5
        with phases("inner", "inner"):  # 1 .. 2
            pass
        with phases("wait"):  # 3 .. 4: no counter, still not outer's own
            pass
    assert stats == {"outer": 3.0, "inner": 1.0}


def test_phase_counters_are_flat_and_grow(engine):
    sched = _sched(engine)
    _submit(sched, [3, 5, 6, 13, 20, 9])
    snaps = [dict(sched.stats)]
    while sched.has_work():
        sched.run_segment()
        snaps.append(dict(sched.stats))
    assert len(snaps) >= 4
    for key in TIMED:
        values = [s[key] for s in snaps]
        assert all(isinstance(v, float) and v >= 0.0 for v in values), key
        assert values == sorted(values), key
        assert values[-1] > values[0], key
    for key in TOKENS:
        values = [s[key] for s in snaps]
        assert all(isinstance(v, int) for v in values), key
        assert values == sorted(values), key
    # a shallow copy taken before the segments holds the values of then
    assert snaps[0] != snaps[-1]
    assert all(snaps[0][k] == 0 for k in TIMED + TOKENS)
    last = snaps[-1]
    assert 0 < last["prefill_tokens_real"] <= last["prefill_tokens_launched"]
    assert "admit_time_s" not in last


def test_prefill_tokens_hand_counted(engine):
    sched = _sched(engine)
    _submit(sched, [3, 5, 6])
    sched.run_segment()
    # one admit round: 3 -> bucket 4 (1 row, width 1), 5 and 6 -> bucket 8
    # (2 rows, width 2): 14 real tokens in 1 * 4 + 2 * 8 launched
    st = sched.stats
    assert st["prefill_launches"] == 2
    assert st["prefill_tokens_real"] == 14
    assert st["prefill_tokens_launched"] == 20
    # a 13-token prompt: a full 8-token chunk, then 5 padded to 8
    sched = _sched(engine)
    _submit(sched, [13])
    sched.run()
    assert sched.stats["prefill_tokens_real"] == 13
    assert sched.stats["prefill_tokens_launched"] == 16


def test_prefill_tokens_per_request_path(engine):
    sched = _sched(engine, prefill_chunk=0)
    _submit(sched, [3, 5, 6])
    sched.run_segment()
    assert sched.stats["prefill_tokens_real"] == 14
    assert sched.stats["prefill_tokens_launched"] == 14
    assert sched.stats["dispatch_s_prefill"] > 0


def _spans(trace_dir) -> list[tuple[str, float, float, dict]]:
    from jax.profiler import ProfileData

    files = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    assert len(files) == 1, files
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    out.append((ev.name, float(ev.start_ns),
                                float(ev.start_ns + ev.duration_ns),
                                dict(ev.stats)))
    return out


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_profiler_spans_nest_once_per_segment(engine, tmp_path):
    sched = _sched(engine)
    _submit(sched, [3, 5, 6, 13, 20, 9])
    sched.run_segment()  # compiles outside the trace
    launches0 = sched.stats["prefill_launches"]
    segments = []
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(4):
            segments.append(sched.stats["segments"])
            sched.run_segment()
    assert sched.stats["segments"] == segments[-1] + 1  # each call launched
    spans = _spans(tmp_path)
    by_seg: dict[int, list] = {}
    for s in spans:
        by_seg.setdefault(s[3]["segment"], []).append(s)
    assert sorted(by_seg) == segments
    n_dispatch = n_wait = 0
    for seg, evs in by_seg.items():
        names = [e[0] for e in evs]
        for name in ONCE:
            assert names.count(name) == 1, (seg, name, names)
        parent = next(e for e in evs if e[0] == "serve.run_segment")
        admit = next(e for e in evs if e[0] == "serve.admit")
        assert all(_inside(e, parent) for e in evs), seg
        for e in evs:
            if e[0].startswith("serve.prefill_"):
                assert _inside(e, admit), (seg, e)
        for e in evs:
            if e[0] == "serve.prefill_dispatch":
                n_dispatch += 1
                args = e[3]
                assert 1 <= args["real_tokens"] <= args["width"] * args["bucket"]
            n_wait += e[0] == "serve.prefill_wait"
        retire = next(e for e in evs if e[0] == "serve.retire")
        assert retire[3]["steps"] >= 1 and retire[3]["live"] >= 1
    assert n_dispatch == sched.stats["prefill_launches"] - launches0 > 0
    assert 1 <= n_wait <= n_dispatch


def test_greedy_outputs_identical_with_profiler_on_and_off(engine, tmp_path):
    lens = [3, 5, 6, 13, 20, 9]
    off = _sched(engine)
    reqs_off = _submit(off, lens, max_new=10)
    off.run()
    on = _sched(engine)
    reqs_on = _submit(on, lens, max_new=10)
    with jax.profiler.trace(str(tmp_path)):
        on.run()
    assert [r.tokens for r in reqs_on] == [r.tokens for r in reqs_off]
    assert all(len(r.tokens) == 10 for r in reqs_on)


def test_named_scopes_in_compiled_slot_programs(engine):
    sched = _sched(engine)
    seg = engine._slot_segment_while_paged
    args = (sched.segment_len, engine.params, sched.cache, sched.tok,
            sched.pos, sched.done, sched.key, jnp.asarray(sched.active),
            jnp.asarray(sched.limit), jnp.bool_(False),
            jnp.asarray(sched.block_table))
    hlo = seg.lower(*args).compile().as_text()
    # the jitted function keeps its name, so the XLA module does too
    assert re.search(r"HloModule jit_segment\b", hlo)
    ops = set(re.findall(r'op_name="([^"]+)"', hlo))
    assert any("/slot_segment_while_paged/" in o for o in ops)
    # decode reads each slot's blocks in place: the segment gathers nothing
    for scope in SCOPES:
        assert any(f"/{scope}/" in o for o in ops) == (scope != "kv.gather"), \
            scope

    pre = engine._prefill_slots_paged
    w, c = 2, 8
    pargs = (engine.params, sched.cache, sched.tok, sched.pos, sched.done,
             jnp.zeros((w, c), jnp.int32), jnp.arange(w, dtype=jnp.int32),
             jnp.zeros(w, jnp.int32), jnp.zeros(w, jnp.int32),
             jnp.asarray(sched.block_table[:w]), sched.key)
    hlo = pre.lower(*pargs).compile().as_text()
    assert re.search(r"HloModule jit_prefill_slots\b", hlo)
    ops = set(re.findall(r'op_name="([^"]+)"', hlo))
    assert any("/prefill_slots_paged/" in o for o in ops)
    for scope in ("kv.write", "kv.gather", "attn.core", "ffn", "lm_head",
                  "sample"):
        assert any(f"/{scope}/" in o for o in ops), scope
