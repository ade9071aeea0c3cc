"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (a configuration under a traffic mix) and everything it needs is
found by name from ``BENCHMARK.json`` (see ``bench/spec.py``).  One process
holds the chip throughout:

  device   JAX must find a TPU, and as many chips as the cell asks for;
           otherwise exit non-zero before any work.
  set-up   weights made on the device from the seed in one jitted call, the
           engine and scheduler built, every prefill shape of the cell
           compiled, then the cell's own traffic served until it is steady.
  window   ``--seconds`` of the traffic through
           ``ContinuousScheduler.run_segment``; a compile inside it fails
           the run.  With ``--trace 1`` a few seconds of it are traced.
  check    once the window has closed and the program's state is freed: a
           sample of the requests it finished against the plain reference
           (``bench/check.py``).

Diagnostics go to standard error; the numbers compared, each beside its
limit, are its last lines.  The last line of standard output is the result
as one JSON object.  ``--control`` runs the configuration's control in the
program's place (it must come out not correct); the benchmark's own runs do
not use it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import spec  # noqa: E402

OUT_DIR = ROOT / ".bench_out"
TRACE_START_S = 2.0  # traced window: from this far into the window...
TRACE_LEN_S = 4.0  # ...for this long


def log(*a) -> None:
    print("bench:", *a, file=sys.stderr, flush=True)


def seed_key(seed: int):
    """A PRNG key for any whole seed (past 32 bits too)."""
    import jax

    s = abs(int(seed))
    return jax.random.fold_in(jax.random.PRNGKey(s & 0x7FFFFFFF),
                              (s >> 31) & 0x7FFFFFFF)


def program_config(config: dict, ref):
    """The program's ``ModelConfig`` for a configuration file: its arch with
    every field that the configuration's reference module ``ref`` maps the
    file to, refused unless the fields ``ref.PROGRAM_REQUIRES`` names hold
    what it computes."""
    from repro.configs.base import get_config

    cfg = get_config(config["arch_id"]).replace(**ref.program_fields(config))
    wrong = {k: getattr(cfg, k) for k, v in ref.PROGRAM_REQUIRES.items()
             if getattr(cfg, k) != v}
    if wrong:
        raise ValueError(f"{config['arch_id']} is not the model "
                         f"{config['reference']} computes: it has {wrong}, "
                         f"the reference needs {ref.PROGRAM_REQUIRES}")
    return cfg


def serving_weights(config: dict, control: bool) -> dict:
    """The weight format the program serves: the configuration's own, or
    its control's where the control is a path of the program."""
    w = config["weights"]
    if control and config["control"]["kind"] == "program":
        w = {**w, **config["control"]["weights"]}
    return w


class CompileWatch:
    """Programs traced, compiled or loaded from the persistent cache,
    counted from JAX's monitoring events."""

    PREFIXES = ("/jax/core/compile/", "/jax/compilation_cache/")

    def __init__(self):
        import jax

        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event.startswith(self.PREFIXES):
            self.n += 1
            self.seconds += secs


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             control: bool = False, t_start: float | None = None,
             trace_dir: pathlib.Path = OUT_DIR / "trace",
             trace_span: tuple[float, float] = (TRACE_START_S, TRACE_LEN_S),
             peaks: dict | None = None) -> dict:
    """One run of ``cell``; returns the result line as a dict (its last key,
    ``checks``, holds each number compared with its limit).  ``peaks``
    stands in for the device's row of ``bench/peaks.json`` off the chip."""
    import jax

    from bench import check, trace_reduce, window, work
    from bench import serve_loop as sl
    from bench import traffic as traffic_mod
    from repro.models.registry import get_arch

    t_start = time.perf_counter() if t_start is None else t_start
    watch = CompileWatch()
    config, traffic, serving = cell.config, cell.traffic, cell.traffic["serving"]
    dev = jax.devices()[0]
    pk = peaks if peaks is not None else work.peaks(dev.device_kind)

    ref = spec.reference_module(config, cell.bench_dir)
    cfg = program_config(config, ref)
    arch = dataclasses.replace(get_arch(config["arch_id"]), cfg=cfg)
    model = ref.model_from_config(config)
    make = jax.jit(ref.make_params, static_argnums=0)
    params = jax.block_until_ready(make(model, seed_key(seed)))
    log(f"weights made in {time.perf_counter() - t_start:.3f} s")

    eng, sched = sl.build(arch, params, serving_weights(config, control),
                          serving, seed)
    del params
    gc.collect()
    log(f"engine built at {time.perf_counter() - t_start:.3f} s")
    launches = sl.LaunchLog()
    if trace:
        sl.instrument(eng, sched.n_slots, launches)
    n_warm = sl.warm_programs(sched, cfg.vocab_size)
    log(f"{n_warm} prefill shapes warmed at {time.perf_counter() - t_start:.3f} s "
        f"({watch.n} compile events, {watch.seconds:.3f} s)")

    reqs = traffic_mod.generate(traffic, cfg.vocab_size, seed)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    tracer = sl.Tracer(str(trace_dir) if trace else None, *trace_span,
                       launches, sched)
    opened: dict = {}

    def on_open():
        opened["setup_s"] = time.perf_counter() - t_start
        opened["compiles"] = watch.n
        opened["queue"] = len(sched.queue)
        opened["resident"] = (dev.memory_stats() or {}).get("bytes_in_use")

    loop = sl.run_closed if traffic["kind"] == "closed" else sl.run_open
    win = loop(sched, traffic, reqs, seconds, tracer, trace, on_open)
    compiles = watch.n - opened["compiles"]
    summary = window.summarize(win.recs, win.t_open, win.t_close)
    log(f"window {seconds} s: {summary}; set-up {opened['setup_s']:.3f} s; "
        f"{compiles} compile events inside the window; queue {opened['queue']} "
        f"at the open, {len(sched.queue)} at the close")
    if win.lateness_s:
        late = sorted(win.lateness_s)
        log(f"generator lateness: median {late[len(late) // 2] * 1e3:.3f} ms, "
            f"max {late[-1] * 1e3:.3f} ms over {len(late)} requests")
    stats = dict(sched.stats)
    log(f"scheduler: segments {stats['segments']} admitted {stats['admitted']} "
        f"retired {stats['retired']} prefill launches {stats['prefill_launches']} "
        f"preemptions {stats['preemptions']}")
    mem = dev.memory_stats() or {}
    peak = mem.get("peak_bytes_in_use")
    log(f"device memory peak {peak} bytes; {opened['resident']} bytes in use "
        f"when the window opened")

    result_device = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(jax.devices()), "memory_peak_bytes": peak}
    per_layer: dict = {}
    breakdown = None
    if trace and tracer.state != "done":
        raise RuntimeError(f"the window closed before the traced window "
                           f"({trace_span[0]} s + {trace_span[1]} s) ended")
    if trace:
        red = trace_reduce.reduce_dir(str(trace_dir))
        result_device.update(busy_s=red.busy_s, window_s=red.window_s)
        ctx = spec.MetricContext(
            reduced=red, stats0=tracer.stats0, stats1=tracer.stats1,
            launches=launches, records=win.recs, t0=tracer.t0, t1=tracer.t1,
            shapes=ref.work_shapes(config), peaks=pk)
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"])(ctx)
            if v is not None:
                per_layer[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": red.top(red.op_s),
                     "idle_gaps": red.top(red.idle_by_span)}
        log(f"trace: window {red.window_s:.6f} s busy {red.busy_s:.6f} s; "
            f"modules {red.top(red.module_s)}")

    # the check: finished requests of the window, the program's state freed
    done = [check.Served(r.handle.prompt, list(r.handle.tokens)) for r in win.recs
            if r.handle.done and win.t_open <= r.handle.finish_t <= win.t_close]
    picked = check.sample(done, seed)
    n_stalled = sl.stalled(win)
    del eng, sched, tracer, win
    gc.collect()
    params = make(model, seed_key(seed))
    bits = config["control"].get("bits", 0) if (
        control and config["control"]["kind"] == "reference") else 0
    t_check = time.perf_counter()
    got = check.served_gaps(ref, model, params, picked, serving["max_len"], bits)
    del params
    log(f"check: {got} in {time.perf_counter() - t_check:.3f} s "
        f"({len(done)} requests finished in the window)")

    number, limit = cell.check["number"], cell.check["limit"]
    gap = got[f"control_{number}"] if bits else got[number]
    checks = {
        number: {"value": gap, "limit": limit},
        "compiles_in_window": {"value": compiles, "limit": 0},
        "stalled_requests": {"value": n_stalled, "limit": 0},
        "sampled_requests_min": {"value": len(picked), "limit": 1},
    }
    correct = (gap <= limit and compiles == 0
               and n_stalled == 0 and len(picked) >= 1
               and summary["failed"] == 0)
    metrics: dict = {}
    if not trace:
        values = {"output_tok_s": summary["output_tok_s"],
                  "ttft_p95_ms": summary["ttft_p95_ms"],
                  "tpot_p95_ms": summary["tpot_p95_ms"],
                  "setup_s": opened["setup_s"]}
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        for name, unit in units.items():
            if values.get(name) is not None:
                metrics[name] = {"value": values[name], "unit": unit}
    else:
        metrics = per_layer
    out = {"correct": bool(correct), "attempted": summary["attempted"],
           "failed": summary["failed"], "metrics": metrics,
           "device": result_device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="serve the configuration's control in the program's place")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    cell = spec.load_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"needs {cell.chips} TPU chip(s); JAX found {len(devices)} "
            f"{devices[0].platform} device(s)")
        return 2
    from repro.utils.compile_cache import enable_compile_cache

    log(f"compile cache {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   control=args.control, t_start=T_START)
    for name, c in out["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
