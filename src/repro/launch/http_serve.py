"""HTTP serving launcher: the continuous scheduler behind a front door.

Boots a ``ServeEngine`` + ``ContinuousScheduler`` (same knobs as the
poisson workload in ``launch/serve.py``), wraps them in the asyncio
``FrontDoor`` (SSE streaming, disconnect-cancel propagation, bounded
admission with 429 backpressure, graceful drain on Ctrl-C), and serves
``POST /v1/generate`` / ``GET /healthz`` / ``GET /v1/stats``.

Usage (CPU smoke):
    PYTHONPATH=src python -m repro.launch.http_serve --arch tinyllama-1.1b \
        --reduced --kv-layout paged --port 8777
    # multi-tenant: weighted DRR shares + a rate-limited batch tenant
    PYTHONPATH=src python -m repro.launch.http_serve --arch tinyllama-1.1b \
        --reduced --tenant acme:3 --tenant hobby:1:0.5:batch
    # self-test: serve, drive N seeded in-process clients, print a
    # summary, drain, and exit nonzero on any mismatch
    PYTHONPATH=src python -m repro.launch.http_serve --arch tinyllama-1.1b \
        --reduced --smoke 8

Request body (see docs/serving.md for the full contract):
    {"prompt": [1, 2, 3], "max_new_tokens": 16,
     "tenant": "acme", "priority": "interactive", "stream": true}
"""
from __future__ import annotations

import argparse
import asyncio
import math
import time

import jax
import numpy as np

from repro.configs.base import ALL_ARCH_IDS
from repro.models.registry import get_arch
from repro.serve import (ContinuousScheduler, FrontDoor, HttpConfig,
                         ServeConfig, ServeEngine, TenantPolicy, TenantSpec)
from repro.sharding.mesh import MeshPlan
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.logging import get_logger

log = get_logger("launch.http_serve")


def _parse_tenant(spec: str) -> tuple[str, TenantSpec]:
    """``name[:weight[:rate[:priority]]]`` — empty fields inherit defaults
    (e.g. ``hobby:1:0.5:batch``, ``acme:3``, ``spot:::batch``)."""
    parts = spec.split(":")
    if not parts[0]:
        raise SystemExit(f"--tenant '{spec}': empty tenant name")
    try:
        weight = float(parts[1]) if len(parts) > 1 and parts[1] else 1.0
        rate = float(parts[2]) if len(parts) > 2 and parts[2] else None
        priority = parts[3] if len(parts) > 3 and parts[3] else "standard"
        return parts[0], TenantSpec(weight=weight, rate=rate,
                                    default_priority=priority)
    except ValueError as e:
        raise SystemExit(f"--tenant '{spec}': {e}") from e


async def _smoke(fd: FrontDoor, args, vocab: int) -> int:
    """Seeded in-process client sweep: N concurrent streaming requests
    round-robined over the configured tenants; returns a process exit
    code (0 = every stream reached a clean terminal event)."""
    from repro.serve.http import generate

    rng = np.random.RandomState(args.seed)
    tenants = [t.split(":")[0] for t in args.tenant] or [None]
    payloads = []
    for i in range(args.smoke):
        plen = int(rng.randint(4, max(args.prompt_len, 5)))
        payloads.append({
            "prompt": [int(t) for t in rng.randint(0, vocab, plen)],
            "max_new_tokens": int(rng.randint(4, args.new_tokens + 1)),
            "tenant": tenants[i % len(tenants)],
        })
    t0 = time.perf_counter()
    outs = await asyncio.gather(*[
        generate(fd.cfg.host, fd.port, p) for p in payloads])
    dt = time.perf_counter() - t0
    bad = 0
    tokens = 0
    for i, (p, o) in enumerate(zip(payloads, outs)):
        body = o.get("body") or {}
        ok = (o["status"] == 200 and body.get("finish_reason") == "length"
              and len(body.get("tokens", ())) == p["max_new_tokens"])
        bad += not ok
        tokens += len(body.get("tokens", ()))
        log.info("smoke r%-2d status=%s finish=%s tokens=%d ttft=%s",
                 i, o["status"], body.get("finish_reason"),
                 len(body.get("tokens", ())),
                 f"{o['ttft_s']:.3f}s" if o["ttft_s"] else "-")
    log.info("smoke: %d/%d clean, %d tokens in %.2fs (%.1f tok/s)",
             args.smoke - bad, args.smoke, tokens, dt, tokens / dt)
    return 1 if bad else 0


async def _serve(fd: FrontDoor, args, vocab: int) -> int:
    await fd.start()
    log.info("serving on http://%s:%d  (POST /v1/generate, GET /healthz, "
             "GET /v1/stats)", fd.cfg.host, fd.port)
    code = 0
    try:
        if args.smoke:
            code = await _smoke(fd, args, vocab)
        else:
            while True:  # Ctrl-C drains below
                await asyncio.sleep(3600)
    except (KeyboardInterrupt, asyncio.CancelledError):
        log.info("interrupt — draining")
    finally:
        await fd.stop()
        st = fd.stats
        log.info("front door: %d requests — %d accepted, %d completed, "
                 "%d disconnects, %d backpressure / %d rate 429s",
                 st["http_requests"], st["accepted"], st["completed"],
                 st["disconnects"], st["rejected_backpressure"],
                 st["rejected_rate"])
        if fd.sched.policy is not None:
            for name, row in fd.sched.policy.snapshot().items():
                log.info("tenant %-12s weight=%.1f submitted=%d admitted=%d "
                         "tokens=%d rate-rejections=%d", name, row["weight"],
                         row["submitted"], row["admitted"],
                         row["served_tokens"], row["rate_rejections"])
    return code


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=ALL_ARCH_IDS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8777,
                    help="listen port (0 = ephemeral, printed at startup)")
    ap.add_argument("--seed", type=int, default=0)
    # capacity: the prompt/new-token bounds a request may ask for
    ap.add_argument("--prompt-len", type=int, default=64,
                    help="largest prompt the server accepts")
    ap.add_argument("--new-tokens", type=int, default=64,
                    help="largest generation budget the server accepts")
    # scheduler knobs (the poisson-workload subset that matters online)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--segment-len", type=int, default=16)
    ap.add_argument("--segment-mode", default="while",
                    choices=("scan", "while"))
    ap.add_argument("--kv-layout", default="dense",
                    choices=("dense", "paged"))
    ap.add_argument("--block-len", type=int, default=16)
    ap.add_argument("--n-blocks", type=int, default=None)
    ap.add_argument("--prefill-chunk", type=int, default=0)
    ap.add_argument("--prefill-buckets", type=int, default=4)
    ap.add_argument("--prefill-token-budget", type=int, default=0)
    ap.add_argument("--overcommit", type=float, default=1.0)
    ap.add_argument("--preempt-mode", default="recompute",
                    choices=("recompute", "swap"))
    # multi-tenant policy
    ap.add_argument("--tenant", action="append", default=[],
                    metavar="NAME[:WEIGHT[:RATE[:PRIORITY]]]",
                    help="register a tenant (repeatable): DRR weight "
                         "(default 1), token-bucket rate in req/s (default "
                         "unlimited), default priority class (interactive/"
                         "standard/batch)")
    ap.add_argument("--quantum", type=int, default=64,
                    help="DRR quantum in tokens per scheduling visit")
    # front-door knobs
    ap.add_argument("--max-pending", type=int, default=64,
                    help="admission bound: queued submissions past this get "
                         "429 + Retry-After")
    ap.add_argument("--heartbeat", type=float, default=10.0,
                    help="SSE keepalive seconds under token silence")
    ap.add_argument("--drain-timeout", type=float, default=30.0)
    ap.add_argument("--smoke", type=int, default=0, metavar="N",
                    help="self-test: drive N seeded in-process clients, "
                         "print a summary, drain, exit (0 = serve forever)")
    args = ap.parse_args()
    enable_compile_cache()

    arch = get_arch(args.arch, reduced=args.reduced)
    if arch.cfg.encoder_only:
        raise SystemExit(f"{args.arch} is encoder-only: no decode step")
    if args.overcommit != 1.0 and args.kv_layout != "paged":
        raise SystemExit("--overcommit requires --kv-layout paged")
    if args.prefill_token_budget and not args.prefill_chunk:
        raise SystemExit("--prefill-token-budget requires --prefill-chunk")

    policy = None
    if args.tenant or args.quantum != 64:
        policy = TenantPolicy(
            tenants=dict(_parse_tenant(t) for t in args.tenant),
            quantum=args.quantum)

    max_len = args.prompt_len + args.new_tokens + 1
    quantum = 1
    if args.kv_layout == "paged":
        quantum = args.block_len
    if args.prefill_chunk:
        quantum = math.lcm(quantum, args.prefill_chunk)
    max_len += (-max_len) % quantum

    params = arch.init_params(jax.random.PRNGKey(args.seed))
    sc = ServeConfig(max_len=max_len, kv_layout=args.kv_layout,
                     block_len=args.block_len)
    eng = ServeEngine(arch, params, MeshPlan(), sc)
    sched = ContinuousScheduler(
        eng, n_slots=args.slots, segment_len=args.segment_len,
        segment_mode=args.segment_mode, n_blocks=args.n_blocks,
        prefill_chunk=args.prefill_chunk,
        prefill_buckets=args.prefill_buckets,
        prefill_token_budget=args.prefill_token_budget,
        overcommit=args.overcommit, preempt_mode=args.preempt_mode,
        policy=policy)
    fd = FrontDoor(sched, HttpConfig(
        host=args.host, port=args.port, max_pending=args.max_pending,
        heartbeat_s=args.heartbeat, drain_timeout_s=args.drain_timeout))
    try:
        code = asyncio.run(_serve(fd, args, arch.cfg.vocab_size))
    except KeyboardInterrupt:
        # _serve already drained (asyncio.run cancels the task, delivering
        # CancelledError into it, before re-raising the interrupt here)
        code = 0
    raise SystemExit(code)


if __name__ == "__main__":
    main()
