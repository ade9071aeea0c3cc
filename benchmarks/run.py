"""Benchmark harness — one function per paper table/figure + roofline bench.

Prints ``name,us_per_call,derived`` CSV rows (derived = the table's headline
metric), with the full tables printed between.  ``us_per_call`` is a
steady-state number: every bench gets one untimed warmup call (absorbing JIT
compile time), then the median of ``BENCH_REPEATS`` timed repeats (default 3,
env-overridable), each fenced with ``jax.block_until_ready``.  Repeat calls
run with stdout suppressed so tables print once.

``serve_decode``, ``serve_continuous``, ``serve_paged``, ``serve_prefill``,
``serve_spec``, ``serve_robust``, ``serve_http`` (in ``serve_http.py``),
``serve_slo`` (in ``serve_slo.py``), and ``serve_energy`` additionally record
into machine-readable ``BENCH_serve.json`` (each under its own section —
compiled-vs-python decode tok/s per batch size, continuous-vs-static
aggregate tok/s + p50/p95 request latency, paged-vs-dense KV tok/s + peak
cache bytes, batched/chunked-vs-per-request admission TTFT + prefill trace
counts, speculative-vs-plain decode tok/s + mean accepted length,
overcommitted-vs-uncontended goodput under preemption, closed-loop vs
overload goodput + client-observed TTFT through the HTTP front door,
SLO-controlled vs uncontrolled interactive TTFT + goodput under
saturation, and
energy-per-token photonic-vs-electronic + the autotune sweep gate) so
the serving-perf trajectory
is tracked across PRs; CI's perf gate (``benchmarks/perf_gate.py``) compares
a fresh run against the committed copy.  Select a subset with
``--only name1,name2``.

  table1_table3   — CNN zoo: our vs paper parameter counts; sparsify+cluster
                    accuracy retention on the MNIST teacher task   (§V.A)
  fig6_dse        — sparsity × clusters design-space sweep          (Fig. 6)
  fig7_layerwise  — per-layer weight + activation sparsity          (Fig. 7)
  fig8_power      — accelerator power comparison                    (Fig. 8)
  fig9_fps_per_w  — FPS/W comparison + paper-ratio check            (Fig. 9)
  fig10_epb       — EPB comparison                                  (Fig. 10)
  kernel_traffic  — Pallas kernels: HBM weight-traffic reduction
  roofline_table  — roofline summary of every dry-run cell
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import os
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils.compile_cache import enable_compile_cache

ROWS: list[tuple[str, float, str]] = []

BENCH_REPEATS = max(int(os.environ.get("BENCH_REPEATS", "3")), 1)


def _block(out) -> None:
    """Fence device work (handles pytrees, ignores non-array leaves)."""
    jax.block_until_ready(out)


def _timed(name: str, fn: Callable, derived_fmt: Callable[[object], str],
           self_timing: bool = False):
    if self_timing:
        # fn does its own warmup/repeat discipline (e.g. serve_decode's
        # best-of-N) — run it once and record that single wall time
        t0 = time.perf_counter()
        out = fn()
        _block(out)
        ROWS.append((name, (time.perf_counter() - t0) * 1e6, derived_fmt(out)))
        return out
    out = fn()  # warmup: JIT compile + first tables print
    _block(out)
    times = []
    for _ in range(BENCH_REPEATS):
        with contextlib.redirect_stdout(io.StringIO()):
            t0 = time.perf_counter()
            out = fn()
            _block(out)
            times.append(time.perf_counter() - t0)
    ROWS.append((name, float(np.median(times)) * 1e6, derived_fmt(out)))
    return out


# ---------------------------------------------------------------- Table 1/3


def table1_table3():
    from repro.core.clustering import ClusteringConfig, cluster_params
    from repro.core.sparsity import SparsityConfig, apply_masks, build_masks
    from repro.data.teacher import TeacherTask
    from repro.models import cnn as cnn_lib

    print("\n== Table 1 / Table 3: CNN zoo + sparsify/cluster accuracy ==")
    print(f"{'model':9s} {'ours params':>12s} {'paper params':>13s} {'Δ%':>6s}")
    for name, cfg in cnn_lib.PAPER_CNNS.items():
        p = cnn_lib.init_params(cfg, jax.random.PRNGKey(0))
        n = cnn_lib.param_count(p)
        d = 100 * (n - cfg.paper_params) / cfg.paper_params
        print(f"{name:9s} {n:12,d} {cfg.paper_params:13,d} {d:6.1f}")

    # accuracy retention on the MNIST teacher task (Table 3 regime: the
    # sparsified+clustered model stays comparable to the dense baseline)
    cfg = cnn_lib.MNIST_CNN
    task = TeacherTask(cfg)
    params = cnn_lib.init_params(cfg, jax.random.PRNGKey(0))

    def loss_fn(p, x, y):
        lg = cnn_lib.forward(p, cfg, x)
        return -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(lg), y[:, None], 1))

    @jax.jit
    def step(p, x, y):
        l, g = jax.value_and_grad(loss_fn)(p, x, y)
        return jax.tree_util.tree_map(lambda w, gw: w - 3e-3 * gw, p, g), l

    for i in range(150):
        x, y = task.batch(i)
        params, _ = step(params, x, y)
    acc0 = task.accuracy(params, n_batches=4)
    masks = build_masks(params, SparsityConfig(0.5, block=(1, 1), exclude=("bias",)))
    sparse = apply_masks(params, masks)
    clustered, _ = cluster_params(sparse, ClusteringConfig(64, exclude=("bias",)))
    acc1 = task.accuracy(clustered, n_batches=4)
    print(f"mnist teacher-task acc: dense={acc0:.3f}  sparse50%+64clusters={acc1:.3f}")
    return {"acc_dense": acc0, "acc_sonic": acc1}


# ------------------------------------------------------------------- Fig 6


def fig6_dse():
    from repro.core.clustering import ClusteringConfig, clustering_error
    from repro.photonic.accelerator import SonicAccelerator, SonicHWConfig
    from repro.photonic.mapper import cnn_workload
    from repro.models import cnn as cnn_lib

    print("\n== Fig 6: sparsity × clusters design space (CIFAR10) ==")
    cfg = cnn_lib.CIFAR10_CNN
    params = cnn_lib.init_params(cfg, jax.random.PRNGKey(0))
    kprobe = params["conv"][3]["kernel"]
    w_probe = kprobe.reshape(-1, kprobe.shape[-1])
    print(f"{'sparsity':>8s} {'clusters':>8s} {'w-recon-err':>11s} {'FPS/W':>8s} {'EPB pJ/b':>9s}")
    rows = []
    for sp in (0.3, 0.5, 0.7):
        for c in (16, 64):
            ws = {f"conv{i}": sp for i in range(6)} | {"fc0": min(sp + 0.3, 0.9)}
            work = cnn_workload(cfg, params, ws)
            acc = SonicAccelerator(SonicHWConfig(weight_bits=int(np.ceil(np.log2(c)))))
            rep = acc.evaluate(work)
            err = clustering_error(w_probe, ClusteringConfig(num_clusters=c))
            rows.append((sp, c, err, rep.fps_per_w, rep.epb * 1e12))
            print(f"{sp:8.1f} {c:8d} {err:11.4f} {rep.fps_per_w:8.1f} {rep.epb*1e12:9.3f}")
    best = max(rows, key=lambda r: r[3])
    print(f"best (FPS/W): sparsity={best[0]} clusters={best[1]} — the paper's "
          "'max sparsity + min clusters, accuracy permitting' frontier")
    return {"best_sparsity": best[0], "best_clusters": best[1]}


# ------------------------------------------------------------------- Fig 7


def fig7_layerwise():
    from repro.models import cnn as cnn_lib
    from repro.photonic.mapper import cnn_workload

    print("\n== Fig 7: layer-wise weight/activation sparsity (all 4 CNNs) ==")
    out = {}
    for name, cfg in cnn_lib.PAPER_CNNS.items():
        params = cnn_lib.init_params(cfg, jax.random.PRNGKey(0))
        n_conv = len(cfg.conv_channels)
        ws = {f"conv{i}": 0.5 for i in range(n_conv)}
        ws |= {f"fc{j}": 0.7 for j in range(len(cfg.fc_dims) + 1)}
        work = cnn_workload(cfg, params, ws)
        print(f"  {name}:")
        for w in work:
            print(f"    {w.name:6s} weight_sp={w.weight_sparsity:.2f} "
                  f"act_sp={w.act_sparsity:.2f} veclen={w.vec_len}")
        out[name] = [(w.name, w.weight_sparsity, w.act_sparsity) for w in work]
    return out


# --------------------------------------------------------------- Figs 8-10

_REPORTS_CACHE: dict = {}


def _reports():
    if _REPORTS_CACHE:
        return _REPORTS_CACHE
    from repro.models import cnn as cnn_lib
    from repro.photonic.baselines import evaluate_all
    from repro.photonic.mapper import cnn_workload

    ws = {
        "mnist": {f"conv{i}": 0.6 for i in range(2)} | {f"fc{j}": 0.8 for j in range(2)},
        "cifar10": {f"conv{i}": 0.5 for i in range(6)} | {"fc0": 0.8},
        "stl10": {f"conv{i}": 0.5 for i in range(6)} | {f"fc{j}": 0.7 for j in range(2)},
        "svhn": {f"conv{i}": 0.5 for i in range(4)} | {f"fc{j}": 0.7 for j in range(3)},
    }
    for name, cfg in cnn_lib.PAPER_CNNS.items():
        params = cnn_lib.init_params(cfg, jax.random.PRNGKey(0))
        _REPORTS_CACHE[name] = evaluate_all(cnn_workload(cfg, params, ws[name]))
    return _REPORTS_CACHE


def fig8_power():
    reports = _reports()
    print("\n== Fig 8: power (W) ==")
    plats = list(next(iter(reports.values())).keys())
    print(f"{'model':9s} " + " ".join(f"{p:>10s}" for p in plats))
    for m, r in reports.items():
        print(f"{m:9s} " + " ".join(f"{r[p].power_w:10.2f}" for p in plats))
    return {m: r["SONIC"].power_w for m, r in reports.items()}


def fig9_fps_per_w():
    reports = _reports()
    print("\n== Fig 9: FPS/W ==")
    plats = list(next(iter(reports.values())).keys())
    print(f"{'model':9s} " + " ".join(f"{p:>10s}" for p in plats))
    for m, r in reports.items():
        print(f"{m:9s} " + " ".join(f"{r[p].fps_per_w:10.2f}" for p in plats))
    paper = {"NullHop": 5.81, "RSNN": 4.02, "LightBulb": 3.08,
             "CrossLight": 2.94, "HolyLight": 13.8}
    print("\naverage SONIC advantage (ours vs paper):")
    ratios = {}
    for p, expect in paper.items():
        r = float(np.mean([rr["SONIC"].fps_per_w / rr[p].fps_per_w
                           for rr in reports.values()]))
        ratios[p] = r
        print(f"  vs {p:11s}: {r:5.2f}x   (paper: {expect}x)")
    return ratios


def fig10_epb():
    reports = _reports()
    print("\n== Fig 10: EPB (pJ / task bit) ==")
    plats = list(next(iter(reports.values())).keys())
    print(f"{'model':9s} " + " ".join(f"{p:>10s}" for p in plats))
    for m, r in reports.items():
        print(f"{m:9s} " + " ".join(f"{r[p].epb*1e12:10.3f}" for p in plats))
    paper = {"NullHop": 8.4, "RSNN": 5.78, "LightBulb": 19.4,
             "CrossLight": 18.4, "HolyLight": 27.6}
    print("\naverage SONIC EPB advantage (ours vs paper — see EXPERIMENTS.md "
          "§Paper-repro on the paper's unpublished EPB bit accounting):")
    ratios = {}
    for p, expect in paper.items():
        r = float(np.mean([rr[p].epb / rr["SONIC"].epb for rr in reports.values()]))
        ratios[p] = r
        print(f"  vs {p:11s}: {r:5.2f}x   (paper: {expect}x)")
    return ratios


# ----------------------------------------------------------------- kernels


def kernel_traffic():
    from repro.core.sonic_layers import make_block_sparse

    print("\n== Pallas kernels: HBM weight-traffic per 4096×4096 layer ==")
    k = n = 4096
    dense_b = k * n * 2  # bf16
    w = jax.random.normal(jax.random.PRNGKey(0), (1024, 1024))
    bs = make_block_sparse(w, 0.75, (128, 128))
    idx_overhead = bs.indices.size * 4 * (k * n) / (1024 * 1024)
    cl_b = k * n * 1 + 64 * 4  # int8 indices + codebook
    bs_b = int(dense_b * 0.25 + idx_overhead)
    sonic_b = int(k * n * 0.25 * 1 + idx_overhead)
    print(f"  dense bf16:          {dense_b/1e6:8.2f} MB   1.0x")
    print(f"  clustered int8:      {cl_b/1e6:8.2f} MB   {dense_b/cl_b:.1f}x   "
          f"(6-bit pack: {dense_b/(cl_b*0.75):.1f}x)")
    print(f"  block-sparse s=.75:  {bs_b/1e6:8.2f} MB   {dense_b/bs_b:.1f}x")
    print(f"  sonic fused:         {sonic_b/1e6:8.2f} MB   {dense_b/sonic_b:.1f}x")
    return {"clustered_x": dense_b / cl_b, "sonic_x": dense_b / sonic_b}


# ------------------------------------------------------------ serve decode


def _split_bench_sections(raw: str) -> dict[str, str] | None:
    """Top-level key -> the EXACT raw text of its value.  Returns None when
    ``raw`` is not a plain JSON object (caller falls back to a rewrite)."""
    dec = json.JSONDecoder()
    out: dict[str, str] = {}
    i = raw.find("{")
    if i < 0:
        return None
    i += 1
    try:
        while True:
            while i < len(raw) and raw[i] in ", \t\r\n":
                i += 1
            if i >= len(raw) or raw[i] == "}":
                return out
            key, i = dec.raw_decode(raw, i)
            while raw[i] in " \t\r\n":
                i += 1
            if raw[i] != ":":
                return None
            i += 1
            while raw[i] in " \t\r\n":
                i += 1
            _, j = dec.raw_decode(raw, i)
            out[str(key)] = raw[i:j]
            i = j
    except (ValueError, IndexError):
        return None


def _merge_bench_json(section: str, payload: dict) -> str:
    """Merge one bench's payload under its section key in BENCH_serve.json
    (env BENCH_SERVE_JSON), preserving the other sections — every serve
    bench records here and any can run alone via --only.

    Untouched sections are preserved BYTE-FOR-BYTE: the file is spliced
    section-wise (raw value slices) rather than re-serialized, so a --only
    re-run of one bench leaves every other section's text — and the git
    diff — untouched."""
    path = os.environ.get("BENCH_SERVE_JSON", "BENCH_serve.json")
    sections: dict[str, str] = {}
    if os.path.exists(path):
        with open(path) as f:
            raw = f.read()
        parsed = _split_bench_sections(raw)
        if parsed is None:
            try:
                parsed = {k: json.dumps(v, indent=2).replace("\n", "\n  ")
                          for k, v in json.loads(raw).items()}
            except (ValueError, AttributeError):
                parsed = {}
        if "batch" in parsed and "serve_decode" not in parsed:
            # migrate the PR 1 flat layout: the whole object moves under
            # its own section (re-indented one level)
            body = "{\n" + ",\n".join(
                f'  {json.dumps(k)}: {v}' for k, v in parsed.items()) + "\n}"
            parsed = {"serve_decode": body.replace("\n", "\n  ")}
        sections = parsed
    # indent continuation lines to nesting depth 1, matching what
    # json.dump(data, indent=2) produced before this splice existed
    sections[section] = json.dumps(payload, indent=2).replace("\n", "\n  ")
    with open(path, "w") as f:
        f.write("{\n" + ",\n".join(
            f'  {json.dumps(k)}: {v}' for k, v in sections.items()) + "\n}")
    print(f"wrote {path} [{section}]")
    return path


def serve_decode():
    """Compiled-loop vs python-loop serving engine: prefill + decode tok/s
    per batch size, written to BENCH_serve.json (env BENCH_SERVE_JSON)."""
    from repro.models.registry import get_arch
    from repro.serve.engine import ServeConfig, ServeEngine
    from repro.sharding.mesh import MeshPlan

    arch = get_arch("tinyllama-1.1b", reduced=True)
    params = arch.init_params(jax.random.PRNGKey(0))
    plan = MeshPlan()
    s_prompt, n_new = 16, 33
    reps = max(BENCH_REPEATS, 5)
    key = jax.random.PRNGKey(0)

    def best(fn, setup=lambda: None):
        # best-of-reps: scheduler noise on shared CPU runners only ever adds
        # time, so min is the faithful steady-state estimator here
        ts = []
        for _ in range(reps):
            args = setup()
            _block(args)
            t0 = time.perf_counter()
            _block(fn(args))
            ts.append(time.perf_counter() - t0)
        return min(ts)

    print("\n== serve_decode: compiled loop vs python loop (CPU smoke) ==")
    print(f"{'batch':>5s} {'prefill tok/s':>13s} {'decode tok/s':>12s} "
          f"{'python tok/s':>12s} {'speedup':>7s}")
    out = {"arch": "tinyllama-1.1b (reduced)", "prompt_len": s_prompt,
           "new_tokens": n_new, "repeats": reps, "batch": {}}
    for b in (1, 4):
        prompts = jax.random.randint(
            jax.random.PRNGKey(b), (b, s_prompt), 0, arch.cfg.vocab_size
        ).astype(jnp.int32)
        sc = dict(max_len=s_prompt + n_new + 1, temperature=0.0)
        eng = ServeEngine(arch, params, plan, ServeConfig(**sc, loop="scan"))
        eng_py = ServeEngine(arch, params, plan, ServeConfig(**sc, loop="python"))

        _block(eng.generate(prompts, n_new, key))  # compile both programs
        _block(eng_py.generate(prompts, n_new, key))

        prefill_t = best(lambda _: eng._prefill(params, prompts, key))
        decode_t = best(
            lambda st: eng._decode_loop(n_new - 1, params, *st),
            setup=lambda: (lambda t, c, p, d: (c, t, p, d, key))(
                *eng._prefill(params, prompts, key)
            ),
        )
        python_total = best(lambda _: eng_py.generate(prompts, n_new, key))
        python_decode_t = max(python_total - prefill_t, 1e-9)

        row = {
            "prefill_tok_s": b * s_prompt / prefill_t,
            "decode_tok_s_compiled": b * (n_new - 1) / decode_t,
            "decode_tok_s_python": b * (n_new - 1) / python_decode_t,
        }
        row["decode_speedup"] = (
            row["decode_tok_s_compiled"] / row["decode_tok_s_python"]
        )
        out["batch"][str(b)] = row
        print(f"{b:5d} {row['prefill_tok_s']:13.1f} "
              f"{row['decode_tok_s_compiled']:12.1f} "
              f"{row['decode_tok_s_python']:12.1f} "
              f"{row['decode_speedup']:6.1f}x")

    _merge_bench_json("serve_decode", out)
    out["min_speedup"] = min(r["decode_speedup"] for r in out["batch"].values())
    return out


# -------------------------------------------------------- serve continuous


def serve_continuous():
    """Continuous batching (slot scheduler) vs static batching on a mixed
    prompt/output-length workload: aggregate tok/s + p50/p95 request latency,
    recorded under "serve_continuous" in BENCH_serve.json.

    The static baseline is the PR 1 engine doing what static batching must
    do: pad every prompt to the longest and run each batch of ``n_slots``
    until its slowest request finishes.  The continuous path prefills each
    request at its own length and refills freed slots between segments.
    """
    from repro.models.registry import get_arch
    from repro.serve import ContinuousScheduler, ServeConfig, ServeEngine
    from repro.sharding.mesh import MeshPlan

    arch = get_arch("tinyllama-1.1b", reduced=True)
    params = arch.init_params(jax.random.PRNGKey(0))
    plan = MeshPlan()
    # heavy-tailed output lengths (the realistic serving regime): static
    # batching runs every batch to its slowest member, continuous batching
    # retires early finishers and refills their slots mid-flight
    n_slots, seg_len, max_len = 4, 16, 192
    lens = [4, 16, 8, 12, 4, 16, 6, 10, 14, 8, 4, 12]
    news = [144, 8, 16, 4, 120, 12, 4, 144, 8, 4, 16, 108]
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, arch.cfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]
    useful = sum(news)
    sc = ServeConfig(max_len=max_len, temperature=0.0)
    eng_c = ServeEngine(arch, params, plan, sc)
    eng_s = ServeEngine(arch, params, plan, sc)

    def run_continuous():
        t0 = time.perf_counter()
        sched = ContinuousScheduler(eng_c, n_slots=n_slots,
                                    segment_len=seg_len, segment_mode="while")
        handles = [sched.submit(p, n) for p, n in zip(prompts, news)]
        sched.run()
        total = time.perf_counter() - t0
        return total, [h.latency for h in handles], sched.stats

    pmax = max(lens)
    padded = np.zeros((len(prompts), pmax), np.int32)
    for i, p in enumerate(prompts):
        padded[i, :len(p)] = p  # dead padded rows — the static-batching tax

    def run_static():
        t0 = time.perf_counter()
        lat = []
        for lo in range(0, len(prompts), n_slots):
            hi = min(lo + n_slots, len(prompts))
            n_new = max(news[lo:hi])  # batch runs until its slowest request
            out = eng_s.generate(jnp.asarray(padded[lo:hi]), n_new)
            _block(out)
            lat += [time.perf_counter() - t0] * (hi - lo)
        return time.perf_counter() - t0, lat

    run_continuous()  # warmup: compiles slot programs (per prompt length)
    run_static()  # warmup: compiles per (batch, n_new) loop programs
    # interleave the timed reps so both modes sample the same box state —
    # back-to-back phases skew the speedup by whatever the CPU was doing
    # during one phase (observed ±0.3x on a 2-core runner)
    reps = max(BENCH_REPEATS, 3)
    cont_runs, stat_runs = [], []
    for _ in range(reps):
        cont_runs.append(run_continuous())
        stat_runs.append(run_static())
    ct, cl, cstats = min(cont_runs, key=lambda r: r[0])
    st, sl = min(stat_runs, key=lambda r: r[0])

    def pct(xs, q):
        return float(np.percentile(np.asarray(xs), q))

    out = {
        "arch": "tinyllama-1.1b (reduced)",
        "workload": {"n_requests": len(prompts), "prompt_lens": lens,
                     "new_tokens": news, "n_slots": n_slots,
                     "segment_len": seg_len, "segment_mode": "while"},
        "continuous": {
            "tok_s": useful / ct,
            "p50_latency_s": pct(cl, 50),
            "p95_latency_s": pct(cl, 95),
            "slot_steps_live": cstats["slot_steps_live"],
            "slot_steps_masked": cstats["slot_steps_masked"],
        },
        "static": {
            "tok_s": useful / st,
            "p50_latency_s": pct(sl, 50),
            "p95_latency_s": pct(sl, 95),
        },
    }
    out["speedup_tok_s"] = out["continuous"]["tok_s"] / out["static"]["tok_s"]
    print("\n== serve_continuous: slot scheduler vs static batching ==")
    print(f"{'mode':>11s} {'tok/s':>9s} {'p50 lat':>9s} {'p95 lat':>9s}")
    for mode in ("continuous", "static"):
        r = out[mode]
        print(f"{mode:>11s} {r['tok_s']:9.1f} {r['p50_latency_s']:9.3f} "
              f"{r['p95_latency_s']:9.3f}")
    print(f"aggregate speedup: {out['speedup_tok_s']:.2f}x  (live slot-steps "
          f"{cstats['slot_steps_live']}, masked {cstats['slot_steps_masked']})")
    _merge_bench_json("serve_continuous", out)
    return out


# ------------------------------------------------------------- serve paged


def serve_paged():
    """Paged-KV vs dense slot layout on the heavy-tailed continuous-batching
    workload: aggregate tok/s and PEAK CACHE BYTES (the paged win — pool
    bytes track the live-context sum instead of n_slots × max_len), recorded
    under "serve_paged" in BENCH_serve.json.  Greedy outputs are asserted
    bit-identical between the two layouts before timing.
    """
    from repro.models.registry import get_arch
    from repro.serve import ContinuousScheduler, ServeConfig, ServeEngine
    from repro.sharding.mesh import MeshPlan

    arch = get_arch("tinyllama-1.1b", reduced=True)
    params = arch.init_params(jax.random.PRNGKey(0))
    plan = MeshPlan()
    # the serve_continuous heavy-tailed workload; the paged pool is sized to
    # the worst concurrent block demand (36 blocks), well under the
    # dense-equivalent 4 slots × 192/16 = 48
    n_slots, seg_len, max_len, block_len, n_blocks = 4, 16, 192, 16, 36
    lens = [4, 16, 8, 12, 4, 16, 6, 10, 14, 8, 4, 12]
    news = [144, 8, 16, 4, 120, 12, 4, 144, 8, 4, 16, 108]
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, arch.cfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]
    useful = sum(news)
    engines = {
        "dense": ServeEngine(arch, params, plan,
                             ServeConfig(max_len=max_len, temperature=0.0)),
        "paged": ServeEngine(arch, params, plan,
                             ServeConfig(max_len=max_len, temperature=0.0,
                                         kv_layout="paged",
                                         block_len=block_len)),
    }

    def cache_bytes(sched) -> int:
        state = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(sched.cache))
        if sched.paged:
            state += sched.block_table.nbytes
        return state

    def run(layout):
        t0 = time.perf_counter()
        sched = ContinuousScheduler(
            engines[layout], n_slots=n_slots, segment_len=seg_len,
            segment_mode="while",
            n_blocks=n_blocks if layout == "paged" else None,
        )
        handles = [sched.submit(p, n) for p, n in zip(prompts, news)]
        sched.run()
        total = time.perf_counter() - t0
        return total, cache_bytes(sched), [h.tokens for h in handles], sched.stats

    # warmup (compiles every slot program) + output-equivalence assertion
    _, dense_bytes, dense_toks, _ = run("dense")
    _, paged_bytes, paged_toks, _ = run("paged")
    assert dense_toks == paged_toks, "paged outputs diverged from dense"
    # interleave timed reps so both layouts sample the same box state
    reps = max(BENCH_REPEATS, 3)
    runs = {"dense": [], "paged": []}
    for _ in range(reps):
        for layout in ("dense", "paged"):
            runs[layout].append(run(layout))
    out = {
        "arch": "tinyllama-1.1b (reduced)",
        "workload": {"n_requests": len(prompts), "prompt_lens": lens,
                     "new_tokens": news, "n_slots": n_slots,
                     "segment_len": seg_len, "segment_mode": "while",
                     "block_len": block_len, "n_blocks": n_blocks},
    }
    for layout in ("dense", "paged"):
        t, nbytes, _, stats = min(runs[layout], key=lambda r: r[0])
        out[layout] = {"tok_s": useful / t, "cache_bytes": nbytes}
        if layout == "paged":
            out[layout]["blocks_in_use_peak"] = stats["blocks_in_use_peak"]
            out[layout]["admit_deferred"] = stats["admit_deferred"]
    out["tok_s_ratio"] = out["paged"]["tok_s"] / out["dense"]["tok_s"]
    out["cache_bytes_saved_x"] = (out["dense"]["cache_bytes"]
                                  / out["paged"]["cache_bytes"])
    print("\n== serve_paged: paged KV pool vs dense slot rows ==")
    print(f"{'layout':>7s} {'tok/s':>9s} {'cache MB':>9s}")
    for layout in ("dense", "paged"):
        r = out[layout]
        print(f"{layout:>7s} {r['tok_s']:9.1f} {r['cache_bytes']/1e6:9.2f}")
    print(f"tok/s ratio {out['tok_s_ratio']:.2f}x at "
          f"{out['cache_bytes_saved_x']:.2f}x smaller cache "
          f"(peak blocks {out['paged']['blocks_in_use_peak']}/{n_blocks})")
    _merge_bench_json("serve_paged", out)
    return out


# ------------------------------------------------------------- serve quant


def serve_quant():
    """Quantized sparse serving (ISSUE 10): int8 block-sparse weights
    (dequantized inside the kernel against per-block scales) + int8 KV
    cache, versus the SAME block-pruned model served as densified fp32
    weights with an fp32 cache.  Records aggregate decode tok/s for both
    engines (gate: quant >= dense — the pruned blocks are skipped entirely
    on the quant path), the greedy token-match rate vs the fp32 oracle
    (pure int8 noise: both engines share one pruning support), actual
    weight/cache bytes (hard gate: quant < dense on both), and asserts the
    ISSUE 10 composition contracts — chunked prefill AND speculative decode
    under ``cache_quant_int8`` run first-class, bit-identical to the quant
    engine's own sequential generation.  Recorded under "serve_quant" in
    BENCH_serve.json.
    """
    from repro.core.sonic_layers import make_block_sparse
    from repro.models.registry import get_arch
    from repro.serve import (
        ContinuousScheduler, ServeConfig, ServeEngine, SpecConfig,
    )
    from repro.sharding.mesh import MeshPlan

    arch = get_arch("tinyllama-1.1b", reduced=True)
    params = arch.init_params(jax.random.PRNGKey(0))
    # block-pruning sparsity shared by both engines (the SONIC operating
    # point — see serve_energy); the block must be explicit here: at the
    # reduced arch's dims the auto block covers a whole projection (one
    # block ⇒ the one-block-per-column pruning floor keeps everything)
    sp, blk = 0.75, (16, 16)

    def densify_pruned(node):
        # fp32 baseline with the SAME pruning support the quant path uses:
        # mirror quantize_serve_params' walk, but densify instead of
        # quantizing, so token mismatches measure int8 noise alone
        if not isinstance(node, dict):
            return node
        out = {}
        for key, val in node.items():
            if key == "kernel" and getattr(val, "ndim", 0) in (2, 3):
                if val.ndim == 2:
                    out[key] = make_block_sparse(val, sp, blk).dense()
                else:
                    out[key] = jnp.stack([
                        make_block_sparse(val[i], sp, blk).dense()
                        for i in range(val.shape[0])
                    ])
            else:
                out[key] = densify_pruned(val)
        return out

    n_slots, seg_len, max_len = 4, 8, 96
    lens = [4, 12, 8, 6, 10, 8, 4, 12]
    news = [48, 24, 40, 16, 32, 40, 24, 48]
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, arch.cfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]
    useful = sum(news)
    qplan = MeshPlan(cache_quant_int8=True)
    engines = {
        "dense": ServeEngine(arch, densify_pruned(params), MeshPlan(),
                             ServeConfig(max_len=max_len, temperature=0.0)),
        "quant": ServeEngine(arch, params, qplan,
                             ServeConfig(max_len=max_len, temperature=0.0,
                                         weight_quant="int8",
                                         weight_quant_sparsity=sp,
                                         weight_quant_block=blk)),
    }

    def run(name, **kw):
        t0 = time.perf_counter()
        sched = ContinuousScheduler(engines[name], n_slots=n_slots,
                                    segment_len=seg_len,
                                    segment_mode="while", **kw)
        handles = [sched.submit(p, n) for p, n in zip(prompts, news)]
        sched.run()
        total = time.perf_counter() - t0
        cbytes = sum(leaf.nbytes
                     for leaf in jax.tree_util.tree_leaves(sched.cache))
        return total, cbytes, [h.tokens for h in handles], sched.stats

    def oracle(name):
        return [
            list(np.asarray(
                engines[name].generate(jnp.asarray(p)[None, :], n))[0])
            for p, n in zip(prompts, news)
        ]

    # warmup (compiles every slot program) + the correctness contracts
    _, dense_cbytes, dense_toks, _ = run("dense")
    _, quant_cbytes, quant_toks, _ = run("quant")
    fp32_oracle = oracle("dense")
    assert dense_toks == fp32_oracle, "dense scheduler diverged from oracle"
    quant_oracle = oracle("quant")
    assert quant_toks == quant_oracle, (
        "quant scheduler diverged from its sequential int8 oracle")
    # greedy token-match vs fp32: same pruning support on both sides, so
    # every mismatch is int8 quantization noise compounding through decode
    matched = sum(int(a == b) for qs, ds in zip(quant_toks, fp32_oracle)
                  for a, b in zip(qs, ds))
    match_rate = matched / useful

    # ISSUE 10 composition contracts: chunked prefill and speculation under
    # the int8 cache run first-class AND stay bitwise-sequential-equal
    _, _, chunk_toks, chunk_stats = run("quant", prefill_chunk=8,
                                        prefill_buckets=2)
    assert chunk_stats["chunks_prefilled"] >= len(prompts)
    assert chunk_toks == quant_oracle, (
        "chunked prefill under int8 KV diverged from sequential")
    spec_eng = ServeEngine(arch, params, qplan,
                           ServeConfig(max_len=max_len, temperature=0.0,
                                       weight_quant="int8",
                                       weight_quant_sparsity=sp,
                                       weight_quant_block=blk,
                                       spec=SpecConfig(k=2,
                                                       draft="truncate:1")))
    engines["quant_spec"] = spec_eng
    _, _, spec_toks, spec_stats = run("quant_spec")
    assert spec_stats["spec_steps"] > 0, "spec fell back under int8 KV"
    assert spec_toks == quant_oracle, (
        "speculative decode under int8 KV diverged from sequential")

    # interleaved best-of timed reps, both engines on the same box state
    reps = max(BENCH_REPEATS, 3)
    best = {"dense": math.inf, "quant": math.inf}
    for _ in range(reps):
        for name in ("dense", "quant"):
            best[name] = min(best[name], run(name)[0])

    wbytes = {
        name: sum(leaf.nbytes for leaf in
                  jax.tree_util.tree_leaves(engines[name].params))
        for name in ("dense", "quant")
    }
    out = {
        "arch": "tinyllama-1.1b (reduced)",
        "workload": {"n_requests": len(prompts), "prompt_lens": lens,
                     "new_tokens": news, "n_slots": n_slots,
                     "segment_len": seg_len, "segment_mode": "while"},
        "weight_sparsity": sp,
        "weight_quant_block": list(blk),
        # the floor the gate holds token_match_rate against: both engines
        # share one pruning support, so a rate collapsing below this means
        # the int8 dequant path itself broke, not the pruning (a broken
        # path measures ~1/vocab; healthy runs land well above 0.5)
        "token_match_floor": 0.4,
        "dense": {"tok_s": useful / best["dense"],
                  "weight_bytes": wbytes["dense"],
                  "cache_bytes": dense_cbytes},
        "quant": {"tok_s": useful / best["quant"],
                  "weight_bytes": wbytes["quant"],
                  "cache_bytes": quant_cbytes},
        "token_match_rate": match_rate,
        "chunked_bit_identical": True,
        "spec_bit_identical": True,
    }
    out["tok_s_ratio"] = out["quant"]["tok_s"] / out["dense"]["tok_s"]
    out["weight_bytes_saved_x"] = wbytes["dense"] / wbytes["quant"]
    out["cache_bytes_saved_x"] = dense_cbytes / quant_cbytes
    print("\n== serve_quant: int8 weights + int8 KV vs fp32-dense pruned ==")
    print(f"{'engine':>7s} {'tok/s':>9s} {'weight MB':>10s} {'cache MB':>9s}")
    for name in ("dense", "quant"):
        r = out[name]
        print(f"{name:>7s} {r['tok_s']:9.1f} {r['weight_bytes']/1e6:10.2f} "
              f"{r['cache_bytes']/1e6:9.2f}")
    print(f"tok/s ratio {out['tok_s_ratio']:.2f}x (gate >= 1.0), weights "
          f"{out['weight_bytes_saved_x']:.2f}x smaller, cache "
          f"{out['cache_bytes_saved_x']:.2f}x smaller")
    print(f"greedy token match vs fp32 oracle: {match_rate:.2f} "
          f"(chunked+spec bitwise-sequential-equal under int8 KV)")
    _merge_bench_json("serve_quant", out)
    return out


# ------------------------------------------------------------ serve prefill


def serve_prefill():
    """Batched/bucketed + chunked admission vs per-request admission on a
    bursty workload with a heavy-tailed prompt-length mix: TTFT p50/p95,
    admit-round cost, and compiled prefill program counts, recorded under
    "serve_prefill" in BENCH_serve.json.

    Cold runs use FRESH engines, so TTFT includes what a cold serving
    process actually pays at admission — on the per-request path that is
    one compiled prefill program per DISTINCT prompt length, on the
    bucketed path at most ``n_buckets`` programs; the trace bound is the
    headline win and is asserted deterministic.  Steady-state tok/s is
    measured warm (programs compiled) so the ratio isolates the chunking
    overhead on decode throughput.  Greedy outputs are asserted identical
    between the two admission paths before anything is recorded.
    """
    from repro.models.registry import get_arch
    from repro.serve import ContinuousScheduler, ServeConfig, ServeEngine
    from repro.sharding.mesh import MeshPlan

    arch = get_arch("tinyllama-1.1b", reduced=True)
    params = arch.init_params(jax.random.PRNGKey(0))
    plan = MeshPlan()
    n_slots, seg_len, max_len = 4, 8, 192
    chunk, n_buckets = 64, 4  # buckets (8, 16, 32, 64)
    # bursty arrival (everything queued at t=0) over a heavy-tailed length
    # mix: 20 distinct prompt lengths; the two tail prompts need 2-3
    # prefill chunks and arrive first, so their chunk rounds interleave
    # with the short requests' decode segments
    lens = [130, 96, 3, 4, 5, 6, 7, 9, 10, 11,
            13, 14, 17, 19, 21, 23, 25, 29, 38, 45]
    rng = np.random.RandomState(0)
    news = [int(n) for n in rng.randint(8, 33, len(lens))]
    prompts = [rng.randint(0, arch.cfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]
    useful = sum(news)

    def build(mode):
        eng = ServeEngine(arch, params, plan,
                          ServeConfig(max_len=max_len, temperature=0.0))
        kw = (dict(prefill_chunk=chunk, prefill_buckets=n_buckets)
              if mode == "batched" else {})
        return eng, kw

    def run(eng, kw):
        t0 = time.perf_counter()
        sched = ContinuousScheduler(eng, n_slots=n_slots,
                                    segment_len=seg_len,
                                    segment_mode="while", **kw)
        handles = [sched.submit(p, n) for p, n in zip(prompts, news)]
        sched.run()
        return time.perf_counter() - t0, handles, sched

    def pct(xs, q):
        return float(np.percentile(np.asarray(xs, np.float64), q))

    reps = max(BENCH_REPEATS, 4)
    out = {
        "arch": "tinyllama-1.1b (reduced)",
        "workload": {"n_requests": len(prompts), "prompt_lens": lens,
                     "new_tokens": news, "n_slots": n_slots,
                     "segment_len": seg_len, "segment_mode": "while",
                     "prefill_chunk": chunk},
        "n_buckets": n_buckets,
    }
    # cold phase: 2 interleaved runs per mode on FRESH engines, best p50
    # kept — single cold samples swing with whatever the shared box is
    # doing to compile times, and the gate floors need the steady signal
    modes = ("per_request", "batched")
    colds: dict[str, list] = {m: [] for m in modes}
    streams = {}
    for _ in range(2):
        for mode in modes:
            eng, kw = build(mode)
            cold_t, handles, sched = run(eng, kw)
            streams[mode] = [h.tokens for h in handles]
            colds[mode].append((pct([h.ttft for h in handles], 50),
                                pct([h.ttft for h in handles], 95),
                                cold_t, sched, eng, kw))
    best = {m: min(colds[m], key=lambda r: r[0]) for m in modes}
    # warm phase: interleave the timed reps so both modes sample the same
    # box state (same reasoning as serve_continuous — back-to-back phases
    # skew the ratio by whatever the CPU was doing during one phase)
    warm: dict[str, list[float]] = {m: [] for m in modes}
    for _ in range(reps):
        for mode in modes:
            _, _, _, _, eng, kw = best[mode]
            warm[mode].append(run(eng, kw)[0])
    for mode in modes:
        p50, p95, cold_t, sched, eng, kw = best[mode]
        st = sched.stats
        traces = (eng.trace_counts["prefill_slot"]
                  + eng.trace_counts["prefill_slots"])
        if mode == "batched":
            out["prefill_trace_bound"] = sched.max_prefill_traces
        out[mode] = {
            "ttft_p50_s": p50,
            "ttft_p95_s": p95,
            "cold_total_s": cold_t,
            "tok_s": useful / min(warm[mode]),
            "admit_round_ms": (1e3 * (st["host_s_admit"]
                                      + st["dispatch_s_prefill"])
                               / st["admit_rounds"]),
            "prefill_traces": traces,
        }
        if mode == "batched":
            out[mode]["prefill_launches"] = st["prefill_launches"]
            out[mode]["prefill_batch_hist"] = {
                str(k): v
                for k, v in sorted(st["prefill_batch_hist"].items())
            }
    assert streams["batched"] == streams["per_request"], (
        "chunked admission diverged from per-request outputs"
    )
    out["ttft_p50_ratio"] = (out["per_request"]["ttft_p50_s"]
                             / out["batched"]["ttft_p50_s"])
    out["ttft_p95_ratio"] = (out["per_request"]["ttft_p95_s"]
                             / out["batched"]["ttft_p95_s"])
    out["tok_s_ratio"] = out["batched"]["tok_s"] / out["per_request"]["tok_s"]
    print("\n== serve_prefill: batched/chunked vs per-request admission ==")
    print(f"{'mode':>12s} {'ttft p50':>9s} {'ttft p95':>9s} {'tok/s':>8s} "
          f"{'admit ms':>9s} {'traces':>6s}")
    for mode in ("per_request", "batched"):
        r = out[mode]
        print(f"{mode:>12s} {r['ttft_p50_s']:9.3f} {r['ttft_p95_s']:9.3f} "
              f"{r['tok_s']:8.1f} {r['admit_round_ms']:9.2f} "
              f"{r['prefill_traces']:6d}")
    print(f"ttft p50 {out['ttft_p50_ratio']:.2f}x lower, tok/s ratio "
          f"{out['tok_s_ratio']:.2f}x, prefill traces "
          f"{out['batched']['prefill_traces']} <= bound "
          f"{out['prefill_trace_bound']} "
          f"(vs {out['per_request']['prefill_traces']} per-request)")
    _merge_bench_json("serve_prefill", out)
    return out


# --------------------------------------------------------------- serve spec


def serve_spec():
    """Speculative decoding (draft-and-verify) vs plain decode through the
    continuous scheduler: aggregate tok/s, mean accepted length per
    draft-and-verify step, and the compiled spec-program count, recorded
    under "serve_spec" in BENCH_serve.json.

    High-acceptance smoke construction: acceptance is a MODEL-QUALITY
    property (how well the drafter approximates the verifier), which a
    random-init smoke box cannot measure honestly — real deployments get it
    from sparsity-aware training / layer distillation of the served
    checkpoint (the SONIC premise).  So the gated workload constructs one
    deliberately: an 8-layer verifier whose deep layers' output projections
    are scaled by 0.03 — a stand-in for a checkpoint whose first 2 layers
    carry most of the signal — with the first-2-layers prefix as the
    drafter (``SpecConfig(draft="truncate:2")``, 4x fewer layer-flops per
    draft, reading the verifier's own KV).  The verifier still pays full
    8-layer compute per step, so the spec/plain ratio measures exactly what
    the serving stack controls: window-verify amortization minus draft
    overhead at a given acceptance rate.  Greedy outputs are asserted
    bit-identical between the two schedulers before anything is timed; a
    natural-acceptance datapoint (75%-sparse self-drafter on unmodified
    random weights — weak by construction) is recorded un-gated alongside.
    """
    import dataclasses

    from repro.models.registry import get_arch
    from repro.serve import (
        ContinuousScheduler, ServeConfig, ServeEngine, SpecConfig,
    )
    from repro.sharding.mesh import MeshPlan

    arch0 = get_arch("tinyllama-1.1b", reduced=True)
    n_layers, n_draft, alpha, spec_k = 8, 2, 0.03, 4
    cfg = arch0.cfg.replace(n_layers=n_layers)
    arch = dataclasses.replace(arch0, cfg=cfg)
    params = arch.init_params(jax.random.PRNGKey(0))
    scale = np.ones(n_layers, np.float32)
    scale[n_draft:] = alpha  # deep layers contribute weakly (see docstring)
    sc_vec = jnp.asarray(scale)
    layers = dict(params["layers"])
    for blk in ("attn", "ffn"):
        sub = dict(layers[blk])
        wo = dict(sub["wo"])
        wo["kernel"] = wo["kernel"] * sc_vec[:, None, None].astype(
            wo["kernel"].dtype)
        sub["wo"] = wo
        layers[blk] = sub
    params = dict(params)
    params["layers"] = layers
    plan = MeshPlan()

    # decode-heavy mixed workload: short prompts, long-ish outputs (spec
    # attacks the per-token decode bottleneck, not prefill)
    n_slots, max_len = 4, 96
    lens = [5, 9, 7, 12, 5, 9, 7, 5, 12, 9]
    news = [40, 24, 48, 32, 40, 16, 48, 24, 32, 40]
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]
    useful = sum(news)

    spec = SpecConfig(k=spec_k, draft=f"truncate:{n_draft}")
    engines = {
        "plain": ServeEngine(arch, params, plan,
                             ServeConfig(max_len=max_len, temperature=0.0)),
        "spec": ServeEngine(arch, params, plan,
                            ServeConfig(max_len=max_len, temperature=0.0,
                                        spec=spec)),
    }
    # segment lengths chosen for comparable host-interaction cadence per
    # emitted token: a spec step emits up to k+1 tokens
    seg_len = {"plain": 16, "spec": 4}

    def run(mode):
        t0 = time.perf_counter()
        sched = ContinuousScheduler(engines[mode], n_slots=n_slots,
                                    segment_len=seg_len[mode],
                                    segment_mode="while")
        handles = [sched.submit(p, n) for p, n in zip(prompts, news)]
        sched.run()
        total = time.perf_counter() - t0
        return total, [h.tokens for h in handles], sched.stats

    # warmup (compiles every program) + output-equivalence assertion
    _, plain_toks, _ = run("plain")
    _, spec_toks, _ = run("spec")
    assert spec_toks == plain_toks, "speculative outputs diverged from plain"
    # interleave timed reps so both modes sample the same box state
    reps = max(BENCH_REPEATS, 3)
    runs = {"plain": [], "spec": []}
    for _ in range(reps):
        for mode in ("plain", "spec"):
            runs[mode].append(run(mode))
    out = {
        "arch": f"tinyllama-1.1b (reduced, {n_layers} layers, deep-layer "
                f"scale {alpha})",
        "workload": {"n_requests": len(prompts), "prompt_lens": lens,
                     "new_tokens": news, "n_slots": n_slots,
                     "segment_len": seg_len, "segment_mode": "while"},
        "spec_config": {"k": spec_k, "draft": f"truncate:{n_draft}"},
    }
    for mode in ("plain", "spec"):
        t, _, stats = min(runs[mode], key=lambda r: r[0])
        out[mode] = {"tok_s": useful / t}
        if mode == "spec":
            hist = stats["accepted_hist"]
            steps = sum(hist.values())
            out[mode]["mean_accepted_len"] = (
                stats["spec_emitted"] / max(steps, 1)
            )
            out[mode]["accepted_hist"] = {
                str(k): v for k, v in sorted(hist.items())
            }
            eng = engines["spec"]
            out[mode]["spec_traces"] = sum(
                v for k, v in eng.trace_counts.items() if "spec" in k
            )
    # one compiled draft-and-verify program per (mode × layout) in use
    out["spec_trace_bound"] = 1
    out["tok_s_ratio"] = out["spec"]["tok_s"] / out["plain"]["tok_s"]
    out["mean_accepted_len"] = out["spec"]["mean_accepted_len"]

    # un-gated natural-acceptance datapoint: sparse self-draft on the
    # UNMODIFIED random-init weights (what conversion alone buys with no
    # training signal — reported for the record, weak by construction)
    params0 = arch.init_params(jax.random.PRNGKey(0))
    eng_nat = ServeEngine(
        arch, params0, plan,
        ServeConfig(max_len=max_len, temperature=0.0,
                    spec=SpecConfig(k=2, draft="self", draft_sparsity=0.75)),
    )
    sched = ContinuousScheduler(eng_nat, n_slots=n_slots, segment_len=4,
                                segment_mode="while")
    for p, n in zip(prompts[:4], news[:4]):
        sched.submit(p, n)
    sched.run()
    st = sched.stats
    out["self_sparse_075"] = {
        "k": 2,
        "mean_accepted_len": st["spec_emitted"] / max(st["spec_steps"], 1),
    }

    print("\n== serve_spec: speculative draft-and-verify vs plain decode ==")
    print(f"{'mode':>6s} {'tok/s':>9s} {'acc len':>8s}")
    for mode in ("plain", "spec"):
        r = out[mode]
        acc = f"{r.get('mean_accepted_len', float('nan')):8.2f}" \
            if mode == "spec" else "       -"
        print(f"{mode:>6s} {r['tok_s']:9.1f} {acc}")
    print(f"speculative speedup {out['tok_s_ratio']:.2f}x at mean accepted "
          f"length {out['mean_accepted_len']:.2f} tok/step "
          f"(hist {out['spec']['accepted_hist']}, "
          f"{out['spec']['spec_traces']} spec traces <= "
          f"{out['spec_trace_bound']}); "
          f"untrained self-sparse drafter: "
          f"{out['self_sparse_075']['mean_accepted_len']:.2f} tok/step")
    _merge_bench_json("serve_spec", out)
    return out


# ------------------------------------------------------------ serve robust


def serve_robust():
    """Overcommitted serving under memory pressure: the heavy-tailed paged
    workload on a pool cut to ~60% of its uncontended peak usage with an
    overcommitted admission gate, so mid-flight preemption + on-demand
    block growth must carry the load.  Records GOODPUT (useful tok/s) for both pools and
    their ratio under "serve_robust" in BENCH_serve.json; greedy outputs
    are asserted bit-identical between the contended and uncontended runs
    before timing, and the contended run must actually preempt.
    """
    from repro.models.registry import get_arch
    from repro.serve import ContinuousScheduler, ServeConfig, ServeEngine
    from repro.sharding.mesh import MeshPlan

    arch = get_arch("tinyllama-1.1b", reduced=True)
    params = arch.init_params(jax.random.PRNGKey(0))
    plan = MeshPlan()
    # the serve_paged workload on 6 slots: the uncontended pool covers the
    # sum of every request's full budget (49 blocks — admission never
    # gates), whose measured peak usage is 34 blocks; the contended pool is
    # ~60% of that peak, so overcommit + preemption must carry the load
    n_slots, seg_len, max_len, block_len = 6, 16, 192, 16
    # overcommit 2.0: the four long requests commit 36 blocks of budget —
    # a tighter factor makes the commitment gate serialize them (deferrals)
    # even though on-demand growth could run them all concurrently
    pools = {"uncontended": 49, "contended": 20}
    overcommit = 2.0
    lens = [4, 16, 8, 12, 4, 16, 6, 10, 14, 8, 4, 12]
    news = [144, 8, 16, 4, 120, 12, 4, 144, 8, 4, 16, 108]
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, arch.cfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]
    useful = sum(news)
    engine = ServeEngine(arch, params, plan,
                         ServeConfig(max_len=max_len, temperature=0.0,
                                     kv_layout="paged",
                                     block_len=block_len))

    def run(pool):
        t0 = time.perf_counter()
        sched = ContinuousScheduler(
            engine, n_slots=n_slots, segment_len=seg_len,
            segment_mode="while", n_blocks=pools[pool],
            overcommit=overcommit if pool == "contended" else 1.0,
        )
        handles = [sched.submit(p, n) for p, n in zip(prompts, news)]
        sched.run()
        total = time.perf_counter() - t0
        return total, [h.tokens for h in handles], sched.stats

    # warmup (compiles every slot program) + output-equivalence assertion
    _, base_toks, _ = run("uncontended")
    _, cont_toks, cont_stats = run("contended")
    assert base_toks == cont_toks, "contended outputs diverged"
    assert cont_stats["preemptions"] >= 1, "contended pool never preempted"
    reps = max(BENCH_REPEATS, 3)
    runs = {"uncontended": [], "contended": []}
    for _ in range(reps):
        for pool in ("uncontended", "contended"):
            runs[pool].append(run(pool))
    out = {
        "arch": "tinyllama-1.1b (reduced)",
        "workload": {"n_requests": len(prompts), "prompt_lens": lens,
                     "new_tokens": news, "n_slots": n_slots,
                     "segment_len": seg_len, "segment_mode": "while",
                     "block_len": block_len, "n_blocks": pools,
                     "overcommit": overcommit},
    }
    for pool in ("uncontended", "contended"):
        t, _, stats = min(runs[pool], key=lambda r: r[0])
        out[pool] = {"goodput_tok_s": useful / t,
                     "preemptions": stats["preemptions"],
                     "readmits": stats["readmits"],
                     "replayed_tokens": stats["replayed_tokens"],
                     "blocks_grown": stats["blocks_grown"],
                     "blocks_in_use_peak": stats["blocks_in_use_peak"],
                     "admit_deferred": stats["admit_deferred"]}
        if stats["readmit_penalty_n"]:
            out[pool]["readmit_penalty_mean_s"] = (
                stats["readmit_penalty_s"] / stats["readmit_penalty_n"])
    out["goodput_ratio"] = (out["contended"]["goodput_tok_s"]
                            / out["uncontended"]["goodput_tok_s"])
    print("\n== serve_robust: overcommitted pool vs uncontended ==")
    print(f"{'pool':>12s} {'tok/s':>9s} {'preempt':>8s} {'grown':>6s}")
    for pool in ("uncontended", "contended"):
        r = out[pool]
        print(f"{pool:>12s} {r['goodput_tok_s']:9.1f} "
              f"{r['preemptions']:8d} {r['blocks_grown']:6d}")
    c = out["contended"]
    print(f"goodput ratio {out['goodput_ratio']:.2f}x on a "
          f"{pools['contended']}/{pools['uncontended']}-block pool "
          f"({c['preemptions']} preemptions, {c['readmits']} readmits, "
          f"{c['replayed_tokens']} replayed tokens, "
          f"mean readmit penalty "
          f"{c.get('readmit_penalty_mean_s', 0.0) * 1e3:.1f} ms)")
    _merge_bench_json("serve_robust", out)
    return out


# ------------------------------------------------------------ serve energy


def serve_energy():
    """SONIC's headline metric on the living system (ISSUE 7).

    Part 1 — energy accounting: runs the serve_robust paged workload with
    ``ServeConfig.trace=True``, then prices the recorded trace through the
    photonic energy model vs the electronic baselines (energy-per-token,
    perf-per-watt).  The electronic/photonic J-per-token ratio is the CI
    hard floor (photonic must not cost MORE energy than NullHop, the
    paper's primary sparse electronic baseline).

    Part 2 — autotune sweep gate: sweeps a small scheduler-knob grid on a
    dense workload, measuring tok/s per candidate, and checks the analytic
    autotuner's pick against the sweep optimum ("pick_ratio", CI hard
    floor >= 0.9).
    """
    from repro.models.registry import get_arch
    from repro.roofline.autotune import KnobConfig, WorkloadSpec, autotune
    from repro.roofline.hw import device_peaks
    from repro.serve import ContinuousScheduler, ServeConfig, ServeEngine
    from repro.serve.trace import trace_energy
    from repro.sharding.mesh import MeshPlan

    arch = get_arch("tinyllama-1.1b", reduced=True)
    params = arch.init_params(jax.random.PRNGKey(0))
    plan = MeshPlan()
    rng = np.random.RandomState(0)

    # ---- part 1: traced serve_robust workload -> energy per token ------
    n_slots, seg_len, max_len, block_len = 6, 16, 192, 16
    lens = [4, 16, 8, 12, 4, 16, 6, 10, 14, 8, 4, 12]
    news = [144, 8, 16, 4, 120, 12, 4, 144, 8, 4, 16, 108]
    prompts = [rng.randint(0, arch.cfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]
    eng = ServeEngine(arch, params, plan,
                      ServeConfig(max_len=max_len, temperature=0.0,
                                  kv_layout="paged", block_len=block_len,
                                  trace=True))
    sched = ContinuousScheduler(eng, n_slots=n_slots, segment_len=seg_len,
                                segment_mode="while", n_blocks=49)
    for p, n in zip(prompts, news):
        sched.submit(p, n)
    sched.run()
    tr = sched.trace
    # SONIC's operating point: 75% weight sparsity from the conversion
    # pipeline; ~50% runtime activation zeros (the zero-skipping electronic
    # baselines are credited for both — see docs/energy_model.md)
    w_sp, a_sp = 0.75, 0.5
    rep = trace_energy(tr, arch.cfg, weight_sparsity=w_sp, act_sparsity=a_sp,
                       platforms=("SONIC", "NullHop", "NP100"))
    sonic, nullhop = rep["platforms"]["SONIC"], rep["platforms"]["NullHop"]
    ratio = nullhop["j_per_token"] / sonic["j_per_token"]
    assert ratio >= 1.0, f"photonic lost on energy/token: {ratio:.3f}"
    out = {
        "arch": "tinyllama-1.1b (reduced)",
        "workload": {"n_requests": len(prompts), "prompt_lens": lens,
                     "new_tokens": news, "n_slots": n_slots,
                     "segment_len": seg_len, "block_len": block_len},
        "assumptions": {"weight_sparsity": w_sp, "act_sparsity": a_sp,
                        "linear_layers_only": True},
        "trace": {k: tr.totals[k] for k in
                  ("prefill_tokens", "decode_tokens", "prefill_launches",
                   "decode_segments", "decode_steps", "preemptions")},
        "photonic": {"platform": "SONIC", **sonic},
        "electronic": {"platform": "NullHop", **nullhop},
        "electronic_gpu": {"platform": "NP100", **rep["platforms"]["NP100"]},
        "energy_ratio_electronic_over_photonic": ratio,
    }
    print("\n== serve_energy: energy/token from a real scheduler trace ==")
    print(f"trace: {tr.totals['prefill_tokens']} prefill + "
          f"{tr.totals['decode_tokens']} decode tokens")
    print(f"{'platform':>10s} {'J/token':>12s} {'tok/s/W':>10s} {'W':>8s}")
    for name in ("SONIC", "NullHop", "NP100"):
        r = rep["platforms"][name]
        print(f"{name:>10s} {r['j_per_token']:12.3e} "
              f"{r['tok_per_s_per_w']:10.1f} {r['power_w']:8.2f}")
    print(f"electronic/photonic energy ratio: {ratio:.2f}x  (gate >= 1.0)")

    # ---- part 2: autotune pick vs measured knob sweep ------------------
    sw_slots, sw_max_len = 4, 192
    sw_lens = [4, 16, 8, 12, 4, 16, 6, 10, 14, 8, 4, 12]
    sw_news = [72, 8, 16, 4, 60, 12, 4, 72, 8, 4, 16, 54]
    sw_prompts = [rng.randint(0, arch.cfg.vocab_size, (n,)).astype(np.int32)
                  for n in sw_lens]
    sw_useful = sum(sw_news)
    cands = [KnobConfig(segment_len=1),
             KnobConfig(segment_len=8, prefill_chunk=64),
             KnobConfig(segment_len=16, prefill_chunk=64),
             KnobConfig(segment_len=32)]
    wspec = WorkloadSpec(tuple(sw_lens), tuple(sw_news),
                         n_slots=sw_slots, max_len=sw_max_len)
    res = autotune(arch.cfg, wspec, device_peaks(jax.devices()[0]),
                   candidates=cands)
    predicted = {p.knobs: p for p in res.ranked}
    eng_sw = ServeEngine(arch, params, plan,
                         ServeConfig(max_len=sw_max_len, temperature=0.0))

    def run_cand(kc):
        t0 = time.perf_counter()
        s = ContinuousScheduler(
            eng_sw, n_slots=sw_slots, segment_len=kc.segment_len,
            segment_mode="while", prefill_chunk=kc.prefill_chunk,
            prefill_buckets=kc.prefill_buckets)
        for p, n in zip(sw_prompts, sw_news):
            s.submit(p, n)
        s.run()
        return sw_useful / (time.perf_counter() - t0)

    for kc in cands:  # warmup: compile every candidate's programs
        run_cand(kc)
    reps = max(BENCH_REPEATS, 2)
    measured = {kc: 0.0 for kc in cands}
    for _ in range(reps):  # interleaved best-of across candidates
        for kc in cands:
            measured[kc] = max(measured[kc], run_cand(kc))
    best_measured = max(measured.values())
    pick = res.best
    pick_ratio = measured[pick] / best_measured
    out["autotune"] = {
        "candidates": {
            kc.label(): {"tok_s": measured[kc],
                         "predicted_tok_s": predicted[kc].tok_s}
            for kc in cands},
        "pick": pick.label(),
        "pick_tok_s": measured[pick],
        "best_tok_s": best_measured,
        "pick_ratio": pick_ratio,
    }
    print("\n== serve_energy: autotune pick vs measured sweep ==")
    print(f"{'config':<16s} {'measured tok/s':>15s} {'predicted tok/s':>16s}")
    for kc in cands:
        mark = " <- pick" if kc == pick else ""
        print(f"{kc.label():<16s} {measured[kc]:>15.1f} "
              f"{predicted[kc].tok_s:>16.1f}{mark}")
    print(f"pick achieves {pick_ratio:.2f}x of the sweep optimum "
          f"(gate >= 0.9)")
    _merge_bench_json("serve_energy", out)
    return out


# ---------------------------------------------------------------- roofline


def roofline_table(path: str = "results/dryrun3.jsonl"):
    if not os.path.exists(path):
        path = "results/dryrun.jsonl"
    if not os.path.exists(path):
        print(f"\n== Roofline: {path} missing — run repro.launch.dryrun first ==")
        return {"cells": 0}
    latest: dict[tuple, dict] = {}
    with open(path) as f:
        for line in f:
            r = json.loads(line)
            latest[(r["arch"], r["shape"], r["mesh"])] = r
    print("\n== Roofline (single-pod cells; terms in ms; dominant term) ==")
    print(f"{'arch':22s} {'shape':12s} {'comp':>9s} {'mem':>9s} {'coll':>9s} "
          f"{'useful%':>8s} {'bottleneck':>10s}")
    n_ok = 0
    for (a, s, m), r in sorted(latest.items()):
        if "single" not in m or r["status"] != "ok":
            continue
        t = r["roofline"]
        n_ok += 1
        print(f"{a:22s} {s:12s} {t['compute_s']*1e3:9.3f} {t['memory_s']*1e3:9.3f} "
              f"{t['collective_s']*1e3:9.3f} {t['useful_fraction']*100:8.1f} "
              f"{t['dominant']:>10s}")
    print(f"({n_ok} single-pod cells)")
    return {"cells": n_ok}


def main() -> None:
    # sibling modules (HTTP front-door + overload-control benches)
    from serve_http import serve_http
    from serve_slo import serve_slo

    benches = [
        ("table1_table3", table1_table3, lambda o: f"acc_sonic={o['acc_sonic']:.3f}"),
        ("fig6_dse", fig6_dse, lambda o: f"best_sp={o['best_sparsity']}"),
        ("fig7_layerwise", fig7_layerwise, lambda o: f"models={len(o)}"),
        ("fig8_power", fig8_power, lambda o: f"sonic_w={np.mean(list(o.values())):.1f}"),
        ("fig9_fps_per_w", fig9_fps_per_w,
         lambda o: f"vs_nullhop={o['NullHop']:.2f}x"),
        ("fig10_epb", fig10_epb, lambda o: f"vs_nullhop={o['NullHop']:.2f}x"),
        ("kernel_traffic", kernel_traffic, lambda o: f"sonic={o['sonic_x']:.1f}x"),
        ("serve_decode", serve_decode,
         lambda o: f"decode_speedup={o['min_speedup']:.1f}x"),
        ("serve_continuous", serve_continuous,
         lambda o: f"speedup={o['speedup_tok_s']:.2f}x"),
        ("serve_paged", serve_paged,
         lambda o: f"bytes_saved={o['cache_bytes_saved_x']:.2f}x"),
        ("serve_quant", serve_quant,
         lambda o: f"quant_ratio={o['tok_s_ratio']:.2f}x"),
        ("serve_prefill", serve_prefill,
         lambda o: f"ttft_p50={o['ttft_p50_ratio']:.2f}x"),
        ("serve_spec", serve_spec,
         lambda o: f"spec_speedup={o['tok_s_ratio']:.2f}x"),
        ("serve_robust", serve_robust,
         lambda o: f"goodput_ratio={o['goodput_ratio']:.2f}x"),
        ("serve_http", serve_http,
         lambda o: f"overload_ratio={o['overload_goodput_ratio']:.2f}x"),
        ("serve_slo", serve_slo,
         lambda o: f"int_p99_ratio={o['interactive_p99_ratio']:.2f}x"),
        ("serve_energy", serve_energy,
         lambda o: (f"energy_ratio="
                    f"{o['energy_ratio_electronic_over_photonic']:.2f}x")),
        ("roofline_table", roofline_table, lambda o: f"cells={o.get('cells', 0)}"),
    ]
    self_timed = {"serve_decode", "serve_continuous", "serve_paged",
                  "serve_quant", "serve_prefill", "serve_spec",
                  "serve_robust", "serve_http", "serve_slo", "serve_energy"}
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated bench names (default: all)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.only:
        want = set(args.only.split(","))
        unknown = want - {n for n, *_ in benches}
        if unknown:
            raise SystemExit(f"unknown bench(es): {sorted(unknown)}")
        benches = [b for b in benches if b[0] in want]
    for name, fn, fmt in benches:
        _timed(name, fn, fmt, self_timing=name in self_timed)
    print("\nname,us_per_call,derived")
    for name, us, derived in ROWS:
        print(f"{name},{us:.0f},{derived}")


if __name__ == "__main__":
    main()
