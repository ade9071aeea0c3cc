"""Everything a cell needs, found by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; their
files sit at fixed places under ``bench/``, and so does each per-layer
metric's reader.  A later change adds a configuration, a mix or a metric by
adding files and entries, never by editing a file that is there.

    configuration   bench/configs/<name>.json
    reference       bench/references/<config["reference"]>.py
    traffic mix     bench/traffic/<name>.json
    cell's check    bench/checks/<cell>.json  (the number compared, its limit)
    metric reader   bench/metrics/<name>.py   (``read(ctx) -> float | None``)

The architecture is the reference module's, not the harness's: it provides
``model_from_config``, ``make_params`` and ``gaps`` (the weights and the
check), ``program_fields`` and ``PROGRAM_REQUIRES`` (the program's
``ModelConfig`` for the file, and what that config must hold), and
``work_shapes`` (the work the roofline and MFU metrics count); the header
of ``bench/references/gqa_rope_swiglu.py`` gives each signature.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
from types import ModuleType

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    check: dict  # {"number": "max_gap" | "mean_gap", "limit": float}
    end_to_end: list[dict]  # the cell's end-to-end metric entries
    per_layer: list[dict]  # the cell's per-layer metric entries
    bench_dir: pathlib.Path  # where its files were found


def load_module(path: pathlib.Path) -> ModuleType:
    """Import a Python file by path (metric names carry dots, so they are
    not importable module names)."""
    name = "bench_file_" + path.stem.replace(".", "_").replace("-", "_")
    if name in sys.modules and sys.modules[name].__file__ == str(path):
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up by name
    spec.loader.exec_module(mod)
    return mod


def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT,
              bench_dir: pathlib.Path = BENCH_DIR) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its configuration
    and traffic files read."""
    spec = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    config = _json(bench_dir / "configs" / f"{w['config']}.json")
    config["name"] = w["config"]
    traffic = _json(bench_dir / "traffic" / f"{w['traffic']}.json")
    traffic["name"] = w["traffic"]
    return Cell(
        name=name, config=config, traffic=traffic, chips=int(w["chips"]),
        check=_json(bench_dir / "checks" / f"{name}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        bench_dir=bench_dir,
    )


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric's ``read(ctx)`` may read, all of the traced
    window: the reduced device trace, the scheduler's counters at its start
    and end, the benchmark's log of program launches, the request records
    (host clock, ``t0``..``t1``), the work shapes of the configuration's
    reference (``work_shapes``) and the device's peaks."""

    reduced: object  # trace_reduce.Reduced
    stats0: dict
    stats1: dict
    launches: object  # serve_loop.LaunchLog
    records: list
    t0: float
    t1: float
    shapes: object  # work.Shapes
    peaks: dict


def reference_module(config: dict, bench_dir: pathlib.Path = BENCH_DIR
                     ) -> ModuleType:
    return load_module(bench_dir / "references" / f"{config['reference']}.py")


def metric_reader(name: str, bench_dir: pathlib.Path = BENCH_DIR):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    return load_module(bench_dir / "metrics" / f"{name}.py").read
