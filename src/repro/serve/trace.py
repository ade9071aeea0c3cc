"""Serving observability: host spans of the serving loop, and the energy
of a run priced from the scheduler's counters.

Spans (``Phases``): every ``ContinuousScheduler.run_segment`` call opens
``serve.*`` ``jax.profiler.TraceAnnotation`` spans around its phases and
adds each phase's host seconds to one flat counter of the scheduler's
``stats`` (``host_s_*``, ``dispatch_s_*``).  The annotations land in the
profiler's own trace, on the device trace's clock; with no profiler running
they cost two clock reads and a no-op annotation each.  docs/serving.md
("Observability") lists the spans and counters.

``useful_tokens`` reads the tokens a run computed for its requests off the
same ``stats``; ``token_prices`` prices one token of the model's linear
layers (``photonic.mapper.lm_workload``; attention score/PV work and KV
traffic are NOT priced by the photonic model, see docs/energy_model.md) on
SONIC and the electronic baselines, and ``trace_energy`` scales that by the
run's useful tokens.
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax


class Phases:
    """Host spans of one scheduler's serving loop.

    ``phases(name, key, **args)`` is a context manager: it opens the
    profiler span ``serve.<name>`` tagged with ``segment`` (the segment the
    current ``run_segment`` call launches, 0-based) and ``args``, and on exit
    adds the span's SELF time — its seconds less those of the spans opened
    inside it — to ``stats[key]``.  ``key`` None times nothing (a parent or
    a device wait).  Span arguments must be host values: reading a device
    value would sync."""

    def __init__(self, stats: dict, clock: Callable[[], float]):
        self.stats, self.clock = stats, clock
        self.segment = 0
        self._inner = [0.0]  # per open span: seconds of the spans inside it

    def __call__(self, name: str, key: str | None = None, **args) -> "_Span":
        return _Span(self, name, key, args)


class _Span:
    __slots__ = ("phases", "key", "ann", "t0")

    def __init__(self, phases: Phases, name: str, key: str | None,
                 args: dict):
        self.phases, self.key = phases, key
        self.ann = jax.profiler.TraceAnnotation(
            "serve." + name, segment=phases.segment, **args)

    def __enter__(self) -> jax.profiler.TraceAnnotation:
        self.ann.__enter__()
        self.phases._inner.append(0.0)
        self.t0 = self.phases.clock()
        return self.ann  # ``set_metadata(**host_values)`` tags it late

    def __exit__(self, *exc) -> None:
        p = self.phases
        dt = p.clock() - self.t0
        inner = p._inner.pop()
        p._inner[-1] += dt
        if self.key is not None:
            p.stats[self.key] += dt - inner
        self.ann.__exit__(*exc)


# the SONIC operating point the energy reports price at: block-pruned
# weights and ReLU-like activation zeros (docs/energy_model.md)
WEIGHT_SPARSITY, ACT_SPARSITY = 0.75, 0.5


def useful_tokens(stats: dict) -> int:
    """Tokens of a scheduler's ``stats`` that some request asked for: real
    prompt tokens prefilled, plain decode emissions and speculative
    emissions.  Tokens replayed after a recompute readmission count (the
    device computed them again); padding and masked slot-steps do not.  A
    live draft-and-verify step adds one to ``slot_steps_live`` as well as
    to ``spec_steps``, so it is taken out once."""
    return int(stats["prefill_tokens_real"] + stats["slot_steps_live"]
               - stats["spec_steps"] + stats["spec_emitted"])


def spec_accept_len(stats: dict) -> float | None:
    """Mean tokens emitted per live draft-and-verify step (1..k+1), or None
    when no speculative step ran: the acceptance length
    ``roofline/autotune.predict`` prices speculation with."""
    if stats["spec_steps"] <= 0:
        return None
    return stats["spec_emitted"] / stats["spec_steps"]


def token_prices(cfg, weight_sparsity: float = 0.0, act_sparsity: float = 0.0,
                 platforms: Sequence[str] = ("SONIC", "NullHop")) -> dict:
    """Energy and perf-per-watt of one token on each named platform of
    ``photonic.baselines.BASELINES``.

    Prices one token's worth of the model's LINEAR layers (qkv/o + ffn +
    lm_head via ``lm_workload(seq_len=1)``: energy is linear in tokens, so
    prefill and decode tokens price identically).  ``weight_sparsity`` is
    the SONIC-style pruned fraction, ``act_sparsity`` the runtime
    activation zero fraction (both also honored by the zero-skipping
    electronic baselines).  Depends on nothing but its arguments, so a
    long-lived caller prices once and scales with :func:`trace_energy`.
    """
    from repro.photonic.baselines import BASELINES
    from repro.photonic.mapper import lm_workload

    work = lm_workload(cfg, weight_sparsity=weight_sparsity,
                       act_sparsity=act_sparsity, seq_len=1)
    out = {"weight_sparsity": weight_sparsity, "act_sparsity": act_sparsity,
           "platforms": {}}
    for name in platforms:
        rep = BASELINES[name]().evaluate(work)
        out["platforms"][name] = {
            "j_per_token": rep.power_w / rep.fps,  # one frame == one token
            "tok_per_s_model": rep.fps,
            "power_w": rep.power_w,
            "tok_per_s_per_w": rep.fps_per_w,
        }
    return out


def trace_energy(stats: dict, prices: dict) -> dict:
    """Energy of a scheduler's run: ``prices`` (a :func:`token_prices`
    result) scaled by the run's :func:`useful_tokens`."""
    tokens = useful_tokens(stats)
    return {
        "tokens": tokens,
        "weight_sparsity": prices["weight_sparsity"],
        "act_sparsity": prices["act_sparsity"],
        "platforms": {
            name: {**p, "trace_energy_j": p["j_per_token"] * tokens}
            for name, p in prices["platforms"].items()},
    }


def tenant_report(stats: dict, energy: dict | None = None,
                  wall_s: float | None = None,
                  platform: str = "SONIC") -> dict:
    """Per-tenant pricing view: each tenant's share of the run's emitted
    tokens (``stats["tenant_tokens"]``, replays excluded), with priced
    tok/s (``wall_s`` given) and J/token (``energy`` = a ``trace_energy``
    result).

    Billing model: the platform's TOTAL energy over the run, prompt tokens
    and replays included, is apportioned to tenants by their share of
    emissions, so each tenant's J/token carries its share of the serving
    overhead rather than the bare marginal token price.
    """
    billed = dict(stats["tenant_tokens"])  # the scheduler thread adds keys
    total = sum(billed.values())
    plat = (energy or {}).get("platforms", {}).get(platform)
    out: dict = {}
    for tenant, tokens in sorted(billed.items()):
        share = tokens / total if total else 0.0
        row = {"tokens": tokens, "share": share}
        if wall_s is not None and wall_s > 0:
            row["tok_s"] = tokens / wall_s
        if plat is not None and tokens:
            energy_j = plat["trace_energy_j"] * share
            row["energy_j"] = energy_j
            row["j_per_token"] = energy_j / tokens
        out[tenant] = row
    return out
