"""End-to-end metrics of one measured window, from the host clock.

Each request record holds when it was due (open loop) or sent (closed
loop) and the host time at which each of its tokens reached the client,
the first from the prefill program and the rest from decode segments.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Record:
    due: float  # due (open loop) or sent (closed loop), host seconds
    prompt_len: int
    max_new: int
    emit_t: list[float] = dataclasses.field(default_factory=list)
    handle: object = None  # the scheduler's live request


def p95(values) -> float | None:
    return float(np.percentile(np.asarray(values, float), 95)) if len(values) else None


def output_tok_s(recs: list[Record], t_open: float, t_close: float) -> float:
    """Tokens that reached clients inside the window, per second of it."""
    n = sum(sum(t_open <= t <= t_close for t in r.emit_t) for r in recs)
    return n / (t_close - t_open)


def ttft_ms(recs: list[Record], t_open: float, t_close: float) -> list[float]:
    """Due/sent -> first token of every request due or sent inside the
    window; one with no first token by the close counts its wait so far."""
    out = []
    for r in recs:
        if t_open <= r.due <= t_close:
            first = r.emit_t[0] if r.emit_t and r.emit_t[0] <= t_close else t_close
            out.append((first - r.due) * 1e3)
    return out


def tpot_ms(recs: list[Record], t_open: float, t_close: float) -> list[float]:
    """Per request with >= 2 tokens inside the window: (its last token in
    the window - its first token) / (tokens between them)."""
    out = []
    for r in recs:
        inside = [i for i, t in enumerate(r.emit_t) if t_open <= t <= t_close]
        if len(inside) >= 2 and inside[-1] >= 1:
            last = inside[-1]
            out.append((r.emit_t[last] - r.emit_t[0]) / last * 1e3)
    return out


def failed(handle) -> bool:
    """Ended without finishing: cancelled or expired by the scheduler."""
    return handle.terminal and not handle.done


def summarize(recs: list[Record], t_open: float, t_close: float) -> dict:
    ttft, tpot = ttft_ms(recs, t_open, t_close), tpot_ms(recs, t_open, t_close)
    return {
        "output_tok_s": output_tok_s(recs, t_open, t_close),
        "ttft_p95_ms": p95(ttft),
        "tpot_p95_ms": p95(tpot),
        "n_ttft": len(ttft),
        "n_tpot": len(tpot),
        "attempted": len(ttft),
        "failed": sum(1 for r in recs if t_open <= r.due <= t_close
                      and r.handle is not None and failed(r.handle)),
    }
