"""Drive the program under test: build the engine and scheduler a cell
states, warm every program the cell's traffic uses, then serve its
traffic through ``ContinuousScheduler.run_segment``.

The window drives a ``ServeEngine`` with paged KV and chunked prefill,
greedy decoding and ``eos_token`` -1 (outputs are as long as drawn), the
way ``launch/serve.py``'s poisson loop drives it.  The program receives
only the generated requests.
"""
from __future__ import annotations

import contextlib
import dataclasses
import inspect
import time

import jax
import numpy as np

from bench.window import Record


def span(on: bool, name: str):
    return jax.profiler.TraceAnnotation(name) if on else contextlib.nullcontext()


def build(arch, params, weights: dict, serving: dict, seed: int):
    """(engine, scheduler) for a cell, serving ``params`` in the format
    ``weights`` states; the caller drops its own reference to ``params``
    afterwards."""
    from repro.serve import ContinuousScheduler, ServeConfig, ServeEngine
    from repro.sharding.mesh import MeshPlan

    quant = weights["format"] == "int8_block_sparse"
    sc = ServeConfig(
        max_len=serving["max_len"], kv_layout="paged",
        block_len=serving["block_len"], eos_token=-1,
        weight_quant="int8" if quant else "none",
        weight_quant_sparsity=weights.get("sparsity", 0.0) if quant else 0.0,
        weight_quant_block=tuple(weights["block"]) if quant else None,
    )
    eng = ServeEngine(arch, params, MeshPlan(), sc)
    sched = ContinuousScheduler(
        eng, n_slots=serving["slots"], segment_len=serving["segment_len"],
        segment_mode=serving["segment_mode"], seed=seed % 2**31,
        n_blocks=serving.get("pool_blocks"),
        prefill_chunk=serving["prefill_chunk"],
        prefill_buckets=serving["prefill_buckets"],
    )
    return eng, sched


def warm_programs(sched, vocab: int) -> int:
    """Compile every prefill program the cell can launch (each chunk bucket
    at each launch width) and nothing else: per (bucket, width), ``width``
    one-token requests of ``bucket`` prompt tokens admitted into idle slots
    make exactly one launch of that shape.  Returns the launches made."""
    widths = [1 << i for i in range(sched.n_width_buckets)
              if 1 << i <= sched.n_slots]
    n = 0
    for bucket in sched.buckets:
        for width in widths:
            for _ in range(width):
                sched.submit(np.full(bucket, 1 % vocab, np.int32), 1)
            sched.run_segment()
            n += 1
    assert not sched.has_work()
    return n


@dataclasses.dataclass
class LaunchLog:
    """What the traced window's program launches did (traced runs only):
    per prefill launch, the (start, real tokens) of each row, read from the
    launch's arguments by name; per ``run_segment`` call, the decode steps
    it ran and the context of each token it decoded, from the benchmark's
    own request records."""

    prefill: list[list[tuple[int, int]]] = dataclasses.field(default_factory=list)
    segments: list[tuple[int, list[int]]] = dataclasses.field(default_factory=list)
    on: bool = False

    def decoded(self, recs: list[Record], before: list[int]) -> None:
        """Log the decode work of one ``run_segment`` call: ``before`` holds
        each record's token count before it.  Token j > 0 of a request with
        a P-token prompt attends P + j keys; every step of a segment gives
        each live slot a token, so its steps are the most any request got."""
        ctx, steps = [], 0
        for rec, n0 in zip(recs, before):
            new = range(max(n0, 1), len(rec.emit_t))
            ctx.extend(rec.prompt_len + j for j in new)
            steps = max(steps, len(new))
        if ctx:
            self.segments.append((steps, ctx))


PREFILL_PROGRAM = "_prefill_slots_paged"
PREFILL_ROWS = ("slots", "starts", "last_local")
SEGMENT_PROGRAMS = ("_slot_segment_paged", "_slot_segment_while_paged")


def _program(eng, name: str):
    fn = getattr(eng, name, None)
    if fn is None:
        raise AttributeError(f"the engine has no {name}: the benchmark "
                             f"cannot time or log the program it names")
    return fn


def instrument(eng, n_slots: int, log: LaunchLog) -> None:
    """Wrap the engine's prefill and segment programs in host spans, and
    log each prefill launch's rows (start and real tokens of each) from its
    arguments, bound by name.  A program or argument that is not there is
    an error, never a metric left out."""
    fn = _program(eng, PREFILL_PROGRAM)
    sig = inspect.signature(fn)
    missing = [a for a in PREFILL_ROWS if a not in sig.parameters]
    if missing:
        raise TypeError(f"{PREFILL_PROGRAM}{sig} has no argument {missing}")

    def prefill(*args, _fn=fn):
        with jax.profiler.TraceAnnotation("bench.prefill_launch"):
            if log.on:
                bound = sig.bind(*args).arguments
                slots, starts, last = jax.device_get(
                    [bound[a] for a in PREFILL_ROWS])
                log.prefill.append([(int(s), int(l) + 1) for sl, s, l in
                                    zip(slots, starts, last) if sl < n_slots])
            return _fn(*args)

    setattr(eng, PREFILL_PROGRAM, prefill)
    for name in SEGMENT_PROGRAMS:
        def segment(*args, _fn=_program(eng, name)):
            with jax.profiler.TraceAnnotation("bench.segment"):
                return _fn(*args)

        setattr(eng, name, segment)


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float
    recs: list[Record]
    lateness_s: list[float]
    last_segment_t: float  # when the window's last run_segment call began


def stalled(win: Window) -> int:
    """Requests that had their first token, had not finished by the close,
    and emitted nothing in the last segment: every segment gives each live
    slot at least one step, so a healthy run has none."""
    return sum(1 for r in win.recs if r.emit_t and not r.handle.terminal
               and r.emit_t[-1] < win.last_segment_t)


class Tracer:
    """Starts and stops the profiler at segment boundaries, where the device
    has finished every launch, a few seconds into the window."""

    def __init__(self, trace_dir: str | None, start_at: float, length: float,
                 log: LaunchLog, sched):
        self.dir, self.start_at, self.length = trace_dir, start_at, length
        self.log, self.sched = log, sched
        self.state = "idle" if trace_dir else "off"
        self.ctx = None
        self.stats0 = self.stats1 = None
        self.t0 = self.t1 = 0.0

    def step(self, now: float) -> None:
        if self.state == "idle" and now >= self.start_at:
            jax.profiler.start_trace(self.dir)
            self.ctx = jax.profiler.TraceAnnotation("bench.traced_window")
            self.ctx.__enter__()
            self.stats0 = dict(self.sched.stats)
            self.log.on, self.state = True, "on"
            self.t0 = time.perf_counter()
        elif self.state == "on" and now >= self.t0 + self.length:
            self.stop()

    def stop(self) -> None:
        if self.state != "on":
            return
        self.log.on = False
        self.stats1 = dict(self.sched.stats)
        self.t1 = time.perf_counter()
        self.ctx.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"


def segment(sched, recs: list[Record], log: LaunchLog, traced: bool) -> None:
    """One ``run_segment`` call, its decode work logged while the trace
    is on."""
    before = [len(r.emit_t) for r in recs] if log.on else None
    with span(traced, "bench.run_segment"):
        sched.run_segment()
    if before is not None:
        log.decoded(recs, before)


def _emitter(rec: Record, clock):
    def on_token(_req, _tok):
        rec.emit_t.append(clock())
    return on_token


def run_closed(sched, traffic: dict, reqs: list, seconds: float,
               tracer: Tracer, traced: bool, on_open,
               clock=time.perf_counter) -> Window:
    """Closed loop: each client sends its next request as soon as its last
    one has finished.  Warm-up runs until ``warmup_retired`` requests have
    finished; then the window opens, and closes at the first segment
    boundary ``seconds`` or more later, so it holds whole segments."""
    from repro.serve import SubmitRequest

    recs: list[Record] = []
    nxt = iter(reqs)

    def send() -> Record:
        r = next(nxt, None)
        if r is None:
            raise RuntimeError("the traffic pool ran out before the window closed")
        with span(traced, "bench.submit"):
            rec = Record(due=clock(), prompt_len=len(r.prompt), max_new=r.max_new)
            rec.handle = sched.submit(SubmitRequest(
                r.prompt, r.max_new, on_token=_emitter(rec, clock)))
        recs.append(rec)
        return rec

    live = [send() for _ in range(int(traffic["clients"]))]
    retired, t_open, t_seg = 0, None, 0.0
    while True:
        now = clock()
        if t_open is None and retired >= int(traffic["warmup_retired"]):
            t_open = now
            tracer.start_at += t_open
            on_open()
        if t_open is not None:
            tracer.step(now)
            if now >= t_open + seconds:
                break  # at a segment boundary, like the opening
        t_seg = clock()
        segment(sched, recs, tracer.log, traced)
        with span(traced, "bench.resubmit"):
            for i, rec in enumerate(live):
                if rec.handle.terminal:
                    retired += 1
                    live[i] = send()
    tracer.stop()
    return Window(t_open, now, recs, [], t_seg)


def run_open(sched, traffic: dict, reqs: list, seconds: float,
             tracer: Tracer, traced: bool, on_open,
             clock=time.perf_counter) -> Window:
    """Open loop: every request due before a ``run_segment`` call is sent
    before it; each is timed from when it was due.  The window opens at the
    first segment boundary ``warmup_s`` after the first request is due, and
    closes at the first one ``seconds`` or more later."""
    from repro.serve import SubmitRequest

    recs: list[Record] = []
    late: list[float] = []
    t0 = clock()
    t_open, t_close = t0 + float(traffic["warmup_s"]), float("inf")
    i, t_seg = 0, 0.0
    while True:
        now = clock()
        if now >= t_open and t_close == float("inf"):
            t_open, t_close = now, now + seconds
            tracer.start_at += t_open
            on_open()
        if now >= t_open:
            tracer.step(now)
        if now >= t_close:
            t_close = now
            break
        with span(traced, "bench.submit"):
            while i < len(reqs) and t0 + reqs[i].due <= now:
                r = reqs[i]
                rec = Record(due=t0 + r.due, prompt_len=len(r.prompt),
                             max_new=r.max_new)
                rec.handle = sched.submit(SubmitRequest(
                    r.prompt, r.max_new, on_token=_emitter(rec, clock)))
                recs.append(rec)
                if rec.due >= t_open:
                    late.append(clock() - rec.due)
                i += 1
        if i >= len(reqs):
            raise RuntimeError("the traffic pool ran out before the window closed")
        if sched.has_work():
            t_seg = clock()
            segment(sched, recs, tracer.log, traced)
        else:
            with span(traced, "bench.wait"):
                time.sleep(max(0.0, min(t0 + reqs[i].due, t_close) - clock()))
    tracer.stop()
    return Window(t_open, t_close, recs, late, t_seg)

