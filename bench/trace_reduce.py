"""Reduce a profiler trace of the traced window to what the per-layer
metrics read.

Input: the ``.xplane.pb`` the JAX profiler writes.  Three kinds of event
are taken from it:

  device ops      on a TPU device plane, line "XLA Ops" (on the CPU, the
                  events that carry an ``hlo_module`` stat)
  device modules  on a TPU device plane, line "XLA Modules"; the name
                  drops its "(program id)" suffix, so the jitted function
                  ``segment`` is ``jit_segment``
  host spans      the benchmark's own ``TraceAnnotation`` spans, named
                  ``bench.*``; ``bench.traced_window`` marks the window

Output (``Reduced``): the window's length, the union of device-op
intervals inside it (busy), device seconds per module and per op, and the
idle gaps attributed to the innermost host span over each gap's midpoint.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import re

WINDOW_SPAN = "bench.traced_window"
SPAN_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    module_s: dict[str, float]
    op_s: dict[str, float]
    idle_by_span: dict[str, float]
    n_ops: int

    def top(self, d: dict[str, float], n: int = 10) -> list[list]:
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:  # an event without stats
        return {}


def events_from_xplane(path: str) -> tuple[list[Event], list[Event], list[Event]]:
    """(device ops, device modules, host spans) of one xplane file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, spans = [], [], []
    for plane in pd.planes:
        tpu = plane.name.startswith("/device:TPU:")
        host = plane.name.startswith("/host:")
        for line in plane.lines:
            for ev in line.events:
                e = Event(ev.name, float(ev.start_ns),
                          float(ev.start_ns) + float(ev.duration_ns))
                if tpu and line.name == "XLA Ops":
                    # the TPU names an op by its whole HLO instruction
                    ops.append(Event(ev.name.split(" = ")[0].lstrip("%"),
                                     e.start_ns, e.end_ns))
                elif tpu and line.name == "XLA Modules":
                    modules.append(Event(re.sub(r"\(.*\)$", "", ev.name),
                                         e.start_ns, e.end_ns))
                elif host and ev.name.startswith(SPAN_PREFIX):
                    spans.append(e)
                elif host:
                    mod = _stats(ev).get("hlo_module")
                    if mod:  # CPU backend: ops run on host threads
                        ops.append(e)
                        modules.append(Event(str(mod), e.start_ns, e.end_ns))
    return ops, modules, spans


def xplane_file(trace_dir: str) -> str:
    files = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _clip(evs: list[Event], lo: float, hi: float) -> list[Event]:
    out = []
    for e in evs:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.append(Event(e.name, s, t))
    return out


def union(evs: list[Event]) -> list[tuple[float, float]]:
    """Merged, sorted busy intervals."""
    merged: list[list[float]] = []
    for e in sorted(evs, key=lambda e: e.start_ns):
        if merged and e.start_ns <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e.end_ns)
        else:
            merged.append([e.start_ns, e.end_ns])
    return [(a, b) for a, b in merged]


def reduce(ops: list[Event], modules: list[Event], spans: list[Event]) -> Reduced:
    windows = [e for e in spans if e.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(windows)}")
    lo, hi = windows[0].start_ns, windows[0].end_ns
    ops, modules = _clip(ops, lo, hi), _clip(modules, lo, hi)
    spans = [e for e in _clip(spans, lo, hi) if e.name != WINDOW_SPAN]
    busy = union(ops)
    module_s: dict[str, float] = collections.defaultdict(float)
    for e in modules:
        module_s[e.name] += (e.end_ns - e.start_ns) / 1e9
    op_s: dict[str, float] = collections.defaultdict(float)
    for e in ops:
        op_s[e.name] += (e.end_ns - e.start_ns) / 1e9
    idle: dict[str, float] = collections.defaultdict(float)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    pending = sorted(spans, key=lambda e: e.start_ns)
    active: list[Event] = []  # spans started before the midpoint, in order
    i = 0
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        while i < len(pending) and pending[i].start_ns <= mid:
            active.append(pending[i])
            i += 1
        active = [e for e in active if e.end_ns >= mid]
        # innermost: the covering span that started last
        name = active[-1].name if active else "no host span"
        idle[name] += (b - a) / 1e9
    return Reduced(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(b - a for a, b in busy) / 1e9,
        module_s=dict(module_s), op_s=dict(op_s), idle_by_span=dict(idle),
        n_ops=len(ops),
    )


def reduce_dir(trace_dir: str) -> Reduced:
    return reduce(*events_from_xplane(xplane_file(trace_dir)))
