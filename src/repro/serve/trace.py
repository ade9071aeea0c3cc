"""Serving observability: host spans of the serving loop, and the opt-in
per-launch trace recorder.

Spans (``Phases``): every ``ContinuousScheduler.run_segment`` call opens
``serve.*`` ``jax.profiler.TraceAnnotation`` spans around its phases and
adds each phase's host seconds to one flat counter of the scheduler's
``stats`` (``host_s_*``, ``dispatch_s_*``).  The annotations land in the
profiler's own trace, on the device trace's clock; with no profiler running
they cost two clock reads and a no-op annotation each.  docs/serving.md
("Observability") lists the spans and counters.

Recorder (``TraceRecorder``): opt-in via ``ServeConfig.trace=True``.  The
scheduler then owns one and calls its ``record_*`` hooks from the launch
sites (prefill dispatch, decode/spec segment, preemption/swap).  With
tracing off the scheduler's ``trace`` attribute is ``None`` and every hook
site is a single ``is not None`` check.  ``tokens`` counts USEFUL tokens:
real prompt tokens prefilled, live decode emissions (replayed tokens
included: the device computed them).

``trace_energy`` bridges a finished trace to the photonic energy model:
per-token Joules from ``photonic.mapper.lm_workload`` (linear layers only —
attention score/PV work and KV traffic are NOT priced by the photonic
model; see docs/energy_model.md) evaluated on SONIC and the electronic
baselines, scaled by the trace's token count.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax

PHASES = ("prefill", "decode", "spec", "preempt", "brownout")


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


@dataclasses.dataclass(frozen=True)
class PhaseRecord:
    phase: str  # one of PHASES
    segment: int  # scheduler segment counter when recorded
    batch: int  # rows the launch executed (padded width / n_slots)
    steps: int  # loop steps (decode/spec) or chunk length (prefill)
    tokens: int  # useful tokens (see module docstring)


class TraceRecorder:
    """Accumulates per-launch :class:`PhaseRecord` events + running totals."""

    def __init__(self, engine):
        self.cfg = engine.cfg
        self.events: list[PhaseRecord] = []
        # per-tenant emitted-token counters (PR 8): the billing basis —
        # the scheduler calls note_tenant_tokens once per live emission
        # (replays excluded), keyed by the request's tenant label
        self.tenant_tokens: dict[str, int] = {}
        self.totals: dict[str, int] = {
            "prefill_tokens": 0, "prefill_launches": 0,
            "decode_tokens": 0, "decode_segments": 0, "decode_steps": 0,
            "spec_tokens": 0, "spec_segments": 0, "spec_live_steps": 0,
            "preemptions": 0, "swap_bytes": 0,
            "brownout_changes": 0, "brownout_level_peak": 0,
        }

    # -- hooks (called by ContinuousScheduler) ----------------------------
    def record_prefill(self, segment: int, width: int, chunk: int,
                       real_tokens: int) -> None:
        """One prefill launch: ``width`` rows × ``chunk`` tokens,
        ``real_tokens`` of which are real."""
        self.totals["prefill_tokens"] += real_tokens
        self.totals["prefill_launches"] += 1
        self.events.append(
            PhaseRecord("prefill", segment, width, chunk, real_tokens))

    def record_decode(self, segment: int, batch: int, steps: int,
                      tokens: int) -> None:
        """One plain decode segment: ``steps`` executed loop steps over
        ``batch`` slot rows, ``tokens`` live emissions."""
        self.totals["decode_tokens"] += tokens
        self.totals["decode_segments"] += 1
        self.totals["decode_steps"] += steps
        self.events.append(PhaseRecord("decode", segment, batch, steps, tokens))

    def record_spec(self, segment: int, batch: int, steps: int,
                    live_steps: int, tokens: int) -> None:
        """One speculative segment: ``steps`` draft-and-verify rounds,
        ``live_steps`` of them on live slots, ``tokens`` accepted+bonus
        emissions."""
        self.totals["spec_tokens"] += tokens
        self.totals["spec_segments"] += 1
        self.totals["spec_live_steps"] += live_steps
        self.events.append(PhaseRecord("spec", segment, batch, steps, tokens))

    def record_preempt(self, segment: int, emitted: int,
                       swap_bytes: int = 0) -> None:
        """A slot eviction; ``emitted`` tokens at eviction time, plus the
        device→host KV payload when the swap path was taken."""
        self.totals["preemptions"] += 1
        self.totals["swap_bytes"] += swap_bytes
        self.events.append(PhaseRecord("preempt", segment, 1, 0, emitted))

    def record_swap_in(self, segment: int, swap_bytes: int) -> None:
        """Host→device KV re-upload at readmission of a swapped request."""
        self.totals["swap_bytes"] += swap_bytes
        self.events.append(PhaseRecord("preempt", segment, 1, 0, 0))

    def record_brownout(self, segment: int, level: int) -> None:
        """A brownout-ladder transition (PR 9): the new level rides in the
        ``steps`` field — the event marks WHEN the overload controller
        moved, for correlating energy/goodput phases."""
        self.totals["brownout_changes"] += 1
        self.totals["brownout_level_peak"] = max(
            self.totals["brownout_level_peak"], level)
        self.events.append(PhaseRecord("brownout", segment, 0, level, 0))

    def note_tenant_tokens(self, tenant: str, n: int = 1) -> None:
        """One (or ``n``) live emissions billed to ``tenant``."""
        self.tenant_tokens[tenant] = self.tenant_tokens.get(tenant, 0) + n

    # -- views ------------------------------------------------------------
    @property
    def tokens_total(self) -> int:
        t = self.totals
        return int(t["prefill_tokens"] + t["decode_tokens"] + t["spec_tokens"])

    def spec_accept_len(self) -> float | None:
        """Measured mean emitted tokens per live speculative step (1..k+1),
        or None when no speculative step ran.  This is the acceptance length
        ``roofline/autotune.predict`` prices speculation with — feeding the
        trace's measurement back closes the loop that PR 7 left open (the
        default acceptance of 1.0 makes speculation never recommendable)."""
        steps = self.totals["spec_live_steps"]
        if steps <= 0:
            return None
        return float(self.totals["spec_tokens"]) / float(steps)

    def summary(self) -> dict:
        out = dict(self.totals)
        out["tokens_total"] = self.tokens_total
        out["events"] = len(self.events)
        if self.tenant_tokens:
            out["tenant_tokens"] = dict(self.tenant_tokens)
        return out


def trace_energy(trace, cfg=None, weight_sparsity: float = 0.0,
                 act_sparsity: float = 0.0,
                 platforms: Sequence[str] = ("SONIC", "NullHop")) -> dict:
    """Energy-per-token + perf-per-watt for a finished trace.

    Prices one token's worth of the model's LINEAR layers (qkv/o + ffn +
    lm_head via ``lm_workload(seq_len=1)`` — energy is linear in tokens, so
    prefill and decode tokens price identically) on each named platform
    from ``photonic.baselines.BASELINES``, then scales by the trace's total
    token count.  ``weight_sparsity`` is the SONIC-style pruned fraction,
    ``act_sparsity`` the runtime activation zero fraction (both also honored
    by the zero-skipping electronic baselines).
    """
    from repro.photonic.baselines import BASELINES
    from repro.photonic.mapper import lm_workload

    cfg = cfg if cfg is not None else trace.cfg
    work = lm_workload(cfg, weight_sparsity=weight_sparsity,
                       act_sparsity=act_sparsity, seq_len=1)
    tokens = trace.tokens_total
    out = {
        "tokens": tokens,
        "weight_sparsity": weight_sparsity,
        "act_sparsity": act_sparsity,
        "platforms": {},
    }
    for name in platforms:
        rep = BASELINES[name]().evaluate(work)
        j_tok = rep.power_w / rep.fps  # one frame == one token at seq_len=1
        out["platforms"][name] = {
            "j_per_token": j_tok,
            "tok_per_s_model": rep.fps,
            "power_w": rep.power_w,
            "tok_per_s_per_w": rep.fps_per_w,
            "trace_energy_j": j_tok * tokens,
        }
    return out


def tenant_report(trace, energy: dict | None = None, wall_s: float | None = None,
                  platform: str = "SONIC") -> dict:
    """Per-tenant pricing view (PR 8): each tenant's emitted-token share of
    the traced run, with priced tok/s (``wall_s`` given) and J/token
    (``energy`` = a ``trace_energy`` result).

    Billing model: the platform's TOTAL traced energy — including the
    masked/padded work no single request asked for — is apportioned to
    tenants by their share of live emissions, so each tenant's J/token
    carries its share of the serving overhead rather than the bare
    marginal token price.
    """
    billed = dict(trace.tenant_tokens)
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
