"""Compiled-HLO analysis → the three roofline terms.

Sources (per the brief):
  * ``compiled.cost_analysis()``  — HLO FLOPs / bytes accessed (per device;
    while-loop bodies counted ONCE — corrected here with parsed trip counts).
  * ``compiled.as_text()``        — collective ops: every all-gather /
    all-reduce / reduce-scatter / all-to-all / collective-permute, with
    operand sizes, replica-group sizes, and the loop nest it lives in.
  * ``compiled.memory_analysis()`` — bytes-per-device (fits-in-HBM proof).

Collective cost model (per-device wire bytes, bidirectional-ring):
  all-reduce       2 · bytes · (g−1)/g
  all-gather       out_bytes · (g−1)/g
  reduce-scatter   in_bytes · (g−1)/g
  all-to-all       bytes · (g−1)/g
  collective-permute  bytes
with g = replica-group size parsed from the op.

Loop handling: HLO while bodies are separate computations; their trip count
is recovered from the constant bound in the condition computation (lax.scan
emits a counted loop).  Collectives and flops inside a body are multiplied by
the product of enclosing trip counts.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any

import numpy as np

from repro.roofline.hw import HWTarget

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
)
_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|s64|u64|s32|u32|s16|u16|s8|u8|pred)\[([\d,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


@dataclasses.dataclass
class CollectiveOp:
    kind: str
    wire_bytes: float  # per-device ring cost, already × trip count
    group_size: int
    trip_count: int
    computation: str


def _parse_computations(hlo: str) -> dict[str, list[str]]:
    """computation name → its lines.

    Header lines start at column 0 (optionally prefixed ``ENTRY``), contain
    ``->`` and end with ``{``; argument lists may hold nested tuple parens,
    so the name is taken as the first token rather than regex-matching the
    whole signature.
    """
    comps: dict[str, list[str]] = {}
    cur = None
    for line in hlo.splitlines():
        stripped = line.rstrip()
        if (
            stripped.endswith("{")
            and "->" in stripped
            and line[:1] not in (" ", "\t")
        ):
            m = re.match(r"^(?:ENTRY\s+)?(%?[\w\.\-]+)", stripped)
            if m:
                cur = m.group(1).lstrip("%")
                comps[cur] = []
                continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is not None:
            comps[cur].append(line)
    return comps


def _while_trip_counts(comps: dict[str, list[str]]) -> dict[str, int]:
    """body-computation name → trip count (propagating nesting)."""
    # map body → cond from while ops
    body_cond: dict[str, str] = {}
    parent: dict[str, str] = {}  # body → computation containing the while
    for cname, lines in comps.items():
        for line in lines:
            m = re.search(
                r"while\(.*?\).*condition=([%\w\.\-]+).*body=([%\w\.\-]+)", line
            )
            if m:
                cond = m.group(1).lstrip("%")
                body = m.group(2).lstrip("%")
                body_cond[body] = cond
                parent[body] = cname

    def cond_bound(cond: str) -> int:
        """Trip count = the constant referenced by the loop-bound compare.

        jax's counted loops emit ``compare(%i, %c), direction=LT`` in the
        condition; taking an arbitrary max constant instead would pick up
        dimension-size constants (measured 25–50× overcount)."""
        lines = comps.get(cond, [])
        consts: dict[str, int] = {}
        for line in lines:
            m = re.match(r"\s*(%?[\w\.\-]+)\s*=.*constant\((\d+)\)", line)
            if m:
                consts[m.group(1).lstrip("%")] = int(m.group(2))
        for line in lines:
            if "compare(" not in line:
                continue
            m = re.search(r"compare\(([^)]*)\)", line)
            if not m:
                continue
            for op in m.group(1).split(","):
                name = op.strip().split(" ")[-1].lstrip("%")
                if name in consts:
                    return max(consts[name], 1)
        return 1

    trips: dict[str, int] = {}

    def total_trips(body: str, seen=()) -> int:
        if body in seen:
            return 1
        own = cond_bound(body_cond.get(body, ""))
        p = parent.get(body)
        outer = 1
        if p is not None and p in body_cond:  # parent is itself a loop body
            outer = total_trips(p, seen + (body,))
        return own * outer

    for body in body_cond:
        trips[body] = total_trips(body)
    return trips


def parse_collectives(hlo: str) -> list[CollectiveOp]:
    comps = _parse_computations(hlo)
    trips = _while_trip_counts(comps)
    out: list[CollectiveOp] = []
    for cname, lines in comps.items():
        trip = trips.get(cname, 1)
        for line in lines:
            m = _COLL_RE.search(line)
            if not m or "-start" in line or "-done" in line:
                if not m:
                    continue
            kind = m.group(1)
            shapes = _SHAPE_RE.findall(line)
            if not shapes:
                continue
            # result shape is the first; operand shapes follow inside parens
            res_bytes = _shape_bytes(*shapes[0])
            op_bytes = (
                sum(_shape_bytes(d, s) for d, s in shapes[1:])
                if len(shapes) > 1
                else res_bytes
            )
            g = 16
            mi = _GROUPS_IOTA_RE.search(line)
            if mi:
                g = int(mi.group(2))
            else:
                ml = _GROUPS_LIST_RE.search(line)
                if ml:
                    g = len([x for x in ml.group(1).split(",") if x.strip() != ""])
            g = max(g, 1)
            ring = (g - 1) / g
            if kind == "all-reduce":
                wire = 2 * op_bytes * ring
            elif kind == "all-gather":
                wire = res_bytes * ring
            elif kind == "reduce-scatter":
                wire = op_bytes * ring
            elif kind == "all-to-all":
                wire = op_bytes * ring
            else:  # collective-permute
                wire = op_bytes
            out.append(
                CollectiveOp(
                    kind=kind,
                    wire_bytes=wire * trip,
                    group_size=g,
                    trip_count=trip,
                    computation=cname,
                )
            )
    return out


@dataclasses.dataclass
class CompiledStats:
    hlo_flops_per_dev: float  # raw cost_analysis (loop bodies once)
    hlo_bytes_per_dev: float
    collective_bytes_per_dev: float  # trip-corrected wire bytes
    collective_counts: dict[str, int]
    collective_bytes_by_kind: dict[str, float]
    argument_bytes: float
    output_bytes: float
    temp_bytes: float
    alias_bytes: float
    peak_bytes_est: float


def analyze_compiled(compiled) -> CompiledStats:
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    colls = parse_collectives(hlo)
    counts: dict[str, int] = {}
    by_kind: dict[str, float] = {}
    for c in colls:
        counts[c.kind] = counts.get(c.kind, 0) + 1
        by_kind[c.kind] = by_kind.get(c.kind, 0.0) + c.wire_bytes
    arg = float(ma.argument_size_in_bytes)
    out = float(ma.output_size_in_bytes)
    tmp = float(ma.temp_size_in_bytes)
    alias = float(ma.alias_size_in_bytes)
    return CompiledStats(
        hlo_flops_per_dev=float(ca.get("flops", 0.0)),
        hlo_bytes_per_dev=float(ca.get("bytes accessed", 0.0)),
        collective_bytes_per_dev=sum(c.wire_bytes for c in colls),
        collective_counts=counts,
        collective_bytes_by_kind=by_kind,
        argument_bytes=arg,
        output_bytes=out,
        temp_bytes=tmp,
        alias_bytes=alias,
        peak_bytes_est=arg + out + tmp - alias,
    )


@dataclasses.dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    useful_fraction: float  # MODEL_FLOPS / executed FLOPs
    step_time_est_s: float

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def roofline_terms(
    model_flops: float,
    exec_flops: float,
    hbm_bytes: float,
    collective_bytes_per_dev: float,
    n_chips: int,
    hw: HWTarget,
) -> RooflineTerms:
    compute = exec_flops / (n_chips * hw.peak_flops_bf16)
    memory = hbm_bytes / (n_chips * hw.hbm_bw)
    collective = collective_bytes_per_dev / hw.ici_bw
    terms = {"compute": compute, "memory": memory, "collective": collective}
    dominant = max(terms, key=terms.get)
    return RooflineTerms(
        compute_s=compute,
        memory_s=memory,
        collective_s=collective,
        dominant=dominant,
        useful_fraction=model_flops / max(exec_flops, 1.0),
        step_time_est_s=max(terms.values()),
    )
