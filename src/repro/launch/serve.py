"""Serving launcher: batched generation through the SONIC serving engine.

Two workloads:

  batch    (default) one fixed-shape batch through ``ServeEngine.generate``
           — the PR 1 static path.
  poisson  continuous batching: requests arrive on a simulated Poisson
           process with ragged prompt/output lengths and stream through the
           slot scheduler (``repro.serve.scheduler``); per-segment progress
           and request 0's tokens print live, then aggregate tok/s and
           p50/p95 latency.

Usage (CPU smoke):
    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --reduced --batch 4 --prompt-len 16 --new-tokens 32
    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --reduced --workload poisson --n-requests 16 --rate 50
    # speculative decoding with a sparse self-drafter (greedy only):
    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --reduced --workload poisson --n-requests 16 --rate 50 \
        --spec-k 4 --spec-draft self --spec-sparsity 0.5
    # overcommitted paged pool with preemption, deadlines, fault injection:
    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --reduced --workload poisson --kv-layout paged --n-blocks 20 \
        --overcommit 2.0 --deadline 30 --chaos-slot-fail-prob 0.1
    # autotuned knobs (every poisson run ends with its energy report):
    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --reduced --workload poisson --autotune
    # fully quantized serving: int8 block-sparse weights + int8 KV cache
    # (chunked prefill and speculation both run first-class, ISSUE 10):
    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --reduced --workload poisson --cache-quant-int8 \
        --weight-quant int8 --weight-quant-sparsity 0.5
"""
from __future__ import annotations

import argparse
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ALL_ARCH_IDS
from repro.models.registry import get_arch
from repro.roofline.hw import device_peaks
from repro.serve import ContinuousScheduler, ServeConfig, ServeEngine, SubmitRequest
from repro.serve.trace import (ACT_SPARSITY, WEIGHT_SPARSITY, spec_accept_len,
                               token_prices, trace_energy)
from repro.sharding.mesh import MeshPlan
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.logging import get_logger

log = get_logger("launch.serve")


def _run_batch(eng: ServeEngine, args) -> None:
    key = jax.random.PRNGKey(args.seed + 1)
    prompts = jax.random.randint(
        key, (args.batch, args.prompt_len), 0, eng.cfg.vocab_size
    ).astype(jnp.int32)
    t0 = time.time()
    out = eng.generate(prompts, args.new_tokens, key)
    out.block_until_ready()
    dt = time.time() - t0
    tput = args.batch * args.new_tokens / dt
    log.info("generated %s tokens in %.2fs (%.1f tok/s)", out.shape, dt, tput)
    print(jax.device_get(out)[:2])


def _poisson_draws(args, vocab: int):
    """The poisson workload's deterministic draws (seeded) — shared by the
    run itself and the --autotune planning step, so the autotuner optimizes
    exactly the request mix that will be served."""
    if args.rate <= 0:
        raise SystemExit("--rate must be > 0")
    if args.n_requests < 1:
        raise SystemExit("--n-requests must be >= 1")
    if args.prompt_len < 1 or args.new_tokens < 1:
        raise SystemExit("--prompt-len and --new-tokens must be >= 1")
    rng = np.random.RandomState(args.seed)
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, args.n_requests))
    min_plen = min(4, args.prompt_len)  # ragged draw floor, prompt_len cap
    p_lens = rng.randint(min_plen, args.prompt_len + 1, args.n_requests)
    n_news = rng.randint(max(args.new_tokens // 8, 1), args.new_tokens + 1,
                         args.n_requests)
    prompts = [rng.randint(0, vocab, (n,)).astype(np.int32) for n in p_lens]
    return arrivals, p_lens, n_news, prompts


def _run_poisson(eng: ServeEngine, args, draws=None):
    arrivals, p_lens, n_news, prompts = (
        draws if draws is not None else _poisson_draws(args, eng.cfg.vocab_size))

    def stream0(req, tok):  # live token stream for the first request
        print(f"  [r0 stream] +{tok}", flush=True)

    chaos = None
    if (args.chaos_exhaust_prob or args.chaos_cancel_prob
            or args.chaos_slot_fail_prob):
        from repro.serve import ChaosConfig

        chaos = ChaosConfig(seed=args.chaos_seed,
                            exhaust_prob=args.chaos_exhaust_prob,
                            cancel_prob=args.chaos_cancel_prob,
                            slot_fail_prob=args.chaos_slot_fail_prob)
    sched = ContinuousScheduler(eng, n_slots=args.slots,
                                segment_len=args.segment_len,
                                segment_mode=args.segment_mode,
                                n_blocks=args.n_blocks,
                                prefill_chunk=args.prefill_chunk,
                                prefill_buckets=args.prefill_buckets,
                                prefill_token_budget=args.prefill_token_budget,
                                overcommit=args.overcommit,
                                preempt_mode=args.preempt_mode,
                                chaos=chaos)
    handles = []
    t0 = time.perf_counter()
    next_arrival = 0
    while next_arrival < args.n_requests or sched.has_work():
        now = time.perf_counter() - t0
        while next_arrival < args.n_requests and arrivals[next_arrival] <= now:
            i = next_arrival
            handles.append(sched.submit(SubmitRequest(
                prompts[i], int(n_news[i]),
                on_token=stream0 if i == 0 else None,
                ttft_deadline_s=args.ttft_deadline,
                deadline_s=args.deadline,
            )))
            log.info("arrive  r%-3d t=%.3fs prompt=%d max_new=%d",
                     i, now, p_lens[i], n_news[i])
            next_arrival += 1
        if sched.has_work():
            running = sched.run_segment()
            st = sched.stats
            acc = spec_accept_len(st)
            spec_note = "" if acc is None else f" accepted={acc:.2f}tok/step"
            log.info("segment %-3d running=%d queued=%d admitted=%d retired=%d "
                     "steps=%d%s", st["segments"], running, len(sched.queue),
                     st["admitted"], st["retired"], st["steps_total"],
                     spec_note)
        elif next_arrival < args.n_requests:
            time.sleep(max(arrivals[next_arrival] - (time.perf_counter() - t0),
                           0.0))
    total = time.perf_counter() - t0

    useful = sum(len(h.tokens) for h in handles)
    # cancelled/expired requests may never emit: percentile what finished
    lats = np.asarray([h.latency for h in handles if h.latency is not None])
    ttfts = np.asarray([h.ttft for h in handles if h.ttft is not None])
    st = sched.stats
    log.info("served %d requests / %d tokens in %.2fs — %.1f tok/s",
             len(handles), useful, total, useful / total)
    if len(lats) and len(ttfts):
        log.info("latency p50=%.3fs p95=%.3fs   ttft p50=%.3fs p95=%.3fs",
                 np.percentile(lats, 50), np.percentile(lats, 95),
                 np.percentile(ttfts, 50), np.percentile(ttfts, 95))
    log.info("segments=%d slot-steps live=%d masked=%d admissions/slot=%s",
             st["segments"], st["slot_steps_live"], st["slot_steps_masked"],
             st["admissions_per_slot"])
    if st["admit_rounds"]:
        # host admission work + prefill dispatch; the first-token wait is
        # device time and not counted
        log.info("admit rounds=%d (%.2f ms/round)", st["admit_rounds"],
                 1e3 * (st["host_s_admit"] + st["dispatch_s_prefill"])
                 / st["admit_rounds"])
    if sched.chunked:
        hist = " ".join(f"{b}x{c}" for b, c in
                        sorted(sched.stats["prefill_batch_hist"].items()))
        log.info("chunked prefill: chunk=%d buckets=%s launches=%d "
                 "chunks=%d batch-size histogram [%s] traces=%d",
                 sched.prefill_chunk, sched.buckets,
                 st["prefill_launches"], st["chunks_prefilled"], hist,
                 eng.trace_counts["prefill_slots"]
                 + eng.trace_counts["prefill_slots_paged"])
    elif st["chunked_skip_reason"]:
        log.info("chunked prefill disabled: %s", st["chunked_skip_reason"])
    if sched.paged:
        log.info("paged KV: peak blocks %d/%d (block_len=%d, "
                 "overcommit=%.2f), blocks grown on demand: %d, "
                 "admissions deferred on full pool: %d",
                 st["blocks_in_use_peak"], sched.n_blocks, sched.block_len,
                 sched.overcommit, st["blocks_grown"], st["admit_deferred"])
    if st["preemptions"]:
        pen = (st["readmit_penalty_s"] / st["readmit_penalty_n"]
               if st["readmit_penalty_n"] else 0.0)
        log.info("preemption (%s): %d evictions, %d readmits (%d swap-outs, "
                 "%d swap-ins, %d replayed tokens), mean readmit penalty "
                 "%.1f ms", sched.preempt_mode, st["preemptions"],
                 st["readmits"], st["swap_outs"], st["swap_ins"],
                 st["replayed_tokens"], 1e3 * pen)
    if st["cancelled"] or st["expired"]:
        log.info("terminal: %d cancelled (%d blocks reclaimed), %d expired",
                 st["cancelled"], st["blocks_reclaimed_cancel"],
                 st["expired"])
    if sched.chaos is not None and sched.chaos.enabled:
        log.info("chaos: %d forced exhaustions, %d injected cancels, "
                 "%d slot failures", st["chaos_exhausts"],
                 st["chaos_cancels"], st["chaos_slot_failures"])
    if sched.spec is not None:
        hist = st["accepted_hist"]
        bars = " ".join(f"{n}tok:{hist[n]}" for n in sorted(hist))
        log.info("speculative decode: k=%d draft=%s — %d draft-and-verify "
                 "slot-steps, mean accepted length %.2f tok/step, "
                 "acceptance histogram [%s]", sched.spec.k, sched.spec.draft,
                 st["spec_steps"], spec_accept_len(st) or 0.0, bars)
    elif st["spec_skip_reason"]:
        log.info("speculative decode disabled: %s", st["spec_skip_reason"])
    rep = trace_energy(st, token_prices(
        eng.cfg, WEIGHT_SPARSITY, ACT_SPARSITY,
        platforms=("SONIC", "NullHop", "NP100")))
    log.info("useful tokens: %d (%d prompt + %d decode + %d speculative)",
             rep["tokens"], st["prefill_tokens_real"],
             st["slot_steps_live"] - st["spec_steps"], st["spec_emitted"])
    for name, r in rep["platforms"].items():
        log.info("energy [%-7s] %.3e J/token (%.3g J over the run), "
                 "%.1f tok/s/W at %.2f W", name, r["j_per_token"],
                 r["trace_energy_j"], r["tok_per_s_per_w"], r["power_w"])
    return useful, total, sched


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=ALL_ARCH_IDS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--workload", default="batch", choices=("batch", "poisson"),
                    help="batch: one static batch (PR 1 path); poisson: "
                         "simulated arrivals through the slot scheduler")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--loop", default="scan", choices=("scan", "while", "python"),
                    help="decode loop: compiled scan (default), compiled "
                         "while_loop with eos early-exit, or legacy host loop")
    ap.add_argument("--eos-token", type=int, default=-1)
    ap.add_argument("--seed", type=int, default=0)
    # poisson-workload knobs
    ap.add_argument("--n-requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=50.0,
                    help="mean arrival rate, requests/s")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--segment-len", type=int, default=16)
    ap.add_argument("--segment-mode", default="while",
                    choices=("scan", "while"))
    ap.add_argument("--kv-layout", default="dense", choices=("dense", "paged"),
                    help="slot-cache layout: dense max_len rows (default) or "
                         "a paged block pool + block table")
    ap.add_argument("--block-len", type=int, default=16,
                    help="paged layout: tokens per KV block (must divide "
                         "max_len — the launcher rounds max_len up)")
    ap.add_argument("--n-blocks", type=int, default=None,
                    help="paged layout: allocatable pool blocks (default: "
                         "dense-equivalent n_slots x max_len/block_len)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="batched/chunked admission: split prompts into "
                         "chunks of this many tokens (power of two dividing "
                         "max_len; the launcher rounds max_len up) and "
                         "prefill same-bucket chunks for several slots in "
                         "one launch; 0 = per-request admission")
    ap.add_argument("--prefill-buckets", type=int, default=4,
                    help="chunked admission: final chunks pad up to this "
                         "many power-of-two bucket lengths (prefill traces "
                         "are bounded by this count)")
    ap.add_argument("--prefill-token-budget", type=int, default=0,
                    help="Sarathi-style admit rounds: advance up to this "
                         "many real prefill tokens per round (requires "
                         "--prefill-chunk; 0 = one chunk per prefilling "
                         "slot per round)")
    ap.add_argument("--overcommit", type=float, default=1.0,
                    help="paged admission: admit while committed full "
                         "budgets fit overcommit x pool capacity (>1.0 "
                         "enables mid-flight preemption when the pool runs "
                         "dry)")
    ap.add_argument("--preempt-mode", default="recompute",
                    choices=("recompute", "swap"),
                    help="how evicted requests readmit: re-prefill the "
                         "prompt + replay emitted tokens (default), or host "
                         "KV swap-out/swap-in")
    ap.add_argument("--ttft-deadline", type=float, default=None,
                    help="per-request first-token deadline in seconds "
                         "(missed -> status 'expired')")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request total deadline in seconds")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="fault-injection RNG seed (with the --chaos-* "
                         "probabilities below)")
    ap.add_argument("--chaos-exhaust-prob", type=float, default=0.0,
                    help="fault injection: per-segment probability of "
                         "forcing pool exhaustion (paged only)")
    ap.add_argument("--chaos-cancel-prob", type=float, default=0.0,
                    help="fault injection: per-segment probability of "
                         "cancelling a random live request")
    ap.add_argument("--chaos-slot-fail-prob", type=float, default=0.0,
                    help="fault injection: per-segment probability of "
                         "failing a random occupied slot (its request "
                         "retires to the queue and readmits)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft this many tokens per "
                         "step and verify them in one forward of the served "
                         "model (0 = off; greedy only)")
    ap.add_argument("--spec-draft", default="self",
                    help="drafter: 'self' (sparse SONIC conversion of the "
                         "served weights) or 'truncate:N' (first N layers "
                         "reading the verifier's KV)")
    ap.add_argument("--spec-sparsity", type=float, default=0.75,
                    help="weight sparsity of the 'self' drafter conversion "
                         "(0.0 = exact copy, full acceptance)")
    ap.add_argument("--cache-quant-int8", action="store_true",
                    help="store the KV cache as int8 with per-position "
                         "scales; chunked prefill and speculative decoding "
                         "run first-class (bit-identical to the sequential "
                         "int8-KV path)")
    ap.add_argument("--weight-quant", default="none",
                    choices=("none", "int8"),
                    help="serve int8 block-quantized weights, dequantized "
                         "in-kernel against per-block scales")
    ap.add_argument("--weight-quant-sparsity", type=float, default=0.0,
                    help="block-prune the served weights to this sparsity "
                         "before int8 quantization (pruned blocks are "
                         "skipped entirely; requires --weight-quant int8)")
    ap.add_argument("--autotune", action="store_true",
                    help="pick segment_len/prefill_chunk/block_len/spec_k "
                         "from the analytic autotuner before serving "
                         "(poisson only; overrides those flags)")
    args = ap.parse_args()
    enable_compile_cache()

    arch = get_arch(args.arch, reduced=args.reduced)
    if arch.cfg.encoder_only:
        raise SystemExit(f"{args.arch} is encoder-only: no decode step")
    if args.kv_layout == "paged" and args.workload != "poisson":
        raise SystemExit(
            "--kv-layout paged only applies to the slot scheduler: "
            "pass --workload poisson (the batch path always runs dense)"
        )
    if args.n_blocks is not None and args.kv_layout != "paged":
        raise SystemExit("--n-blocks requires --kv-layout paged")
    if args.prefill_chunk and args.workload != "poisson":
        raise SystemExit(
            "--prefill-chunk only applies to the slot scheduler: "
            "pass --workload poisson (the batch path prefills once)"
        )
    if args.prefill_token_budget and not args.prefill_chunk:
        raise SystemExit("--prefill-token-budget requires --prefill-chunk")
    if args.overcommit < 1.0:
        raise SystemExit("--overcommit must be >= 1.0")
    if args.overcommit != 1.0 and args.kv_layout != "paged":
        raise SystemExit("--overcommit requires --kv-layout paged (dense "
                         "slots have no block pool to overcommit)")
    if args.preempt_mode == "swap" and args.kv_layout != "paged":
        raise SystemExit("--preempt-mode swap requires --kv-layout paged")
    if (args.chaos_exhaust_prob or args.chaos_cancel_prob
            or args.chaos_slot_fail_prob) and args.workload != "poisson":
        raise SystemExit("--chaos-* only applies to the slot scheduler: "
                         "pass --workload poisson")
    if args.spec_k and args.workload != "poisson":
        raise SystemExit(
            "--spec-k only applies to the slot scheduler: pass "
            "--workload poisson"
        )
    if args.spec_k and args.temperature > 0:
        raise SystemExit("speculative decoding is greedy-only: --spec-k "
                         "needs --temperature 0")
    if args.autotune and args.workload != "poisson":
        raise SystemExit("--autotune only applies to the slot scheduler: "
                         "pass --workload poisson")
    if args.weight_quant_sparsity and args.weight_quant != "int8":
        raise SystemExit("--weight-quant-sparsity requires "
                         "--weight-quant int8")
    if not 0.0 <= args.weight_quant_sparsity < 1.0:
        raise SystemExit("--weight-quant-sparsity must be in [0, 1)")
    # the autotuner prices against the chip this process serves on
    hw = device_peaks(jax.devices()[0])
    # quantization changes the bytes the roofline moves per element
    cache_bpe = 1.03 if args.cache_quant_int8 else 2.0
    weight_bpe = (1.01 * (1.0 - args.weight_quant_sparsity)
                  if args.weight_quant == "int8" else 2.0)
    draws = None
    predicted_tok_s = None
    if args.autotune:
        from repro.roofline.autotune import WorkloadSpec, autotune

        draws = _poisson_draws(args, arch.cfg.vocab_size)
        _, p_lens, n_news, _ = draws
        w = WorkloadSpec(tuple(int(x) for x in p_lens),
                         tuple(int(x) for x in n_news),
                         n_slots=args.slots,
                         max_len=args.prompt_len + args.new_tokens + 1
                         + args.spec_k)
        res = autotune(arch.cfg, w, hw,
                       paged=(args.kv_layout == "paged"),
                       spec_ks=(0, args.spec_k) if args.spec_k else (0,),
                       cache_bytes_per_elem=cache_bpe,
                       weight_bytes_per_elem=weight_bpe)
        log.info("autotune over %d candidates:\n%s", len(res.ranked),
                 res.report())
        best = res.best
        predicted_tok_s = res.ranked[0].tok_s
        args.segment_len = best.segment_len
        args.prefill_chunk = best.prefill_chunk
        args.prefill_buckets = best.prefill_buckets
        if args.kv_layout == "paged":
            args.block_len = best.block_len
        if args.spec_k and best.spec_k == 0:
            # at the assumed acceptance of 1.0 speculation never pays; keep
            # it on so the run measures the real acceptance and the
            # post-run re-rank can judge it on real numbers
            log.info("autotune ranked spec_k=0 at assumed acceptance 1.0 — "
                     "keeping --spec-k %d to measure the real acceptance",
                     args.spec_k)
        log.info("autotune pick: %s (segment_len=%d prefill_chunk=%d "
                 "prefill_buckets=%d block_len=%d spec_k=%d) — predicted "
                 "%.1f tok/s in model units", best.label(), best.segment_len,
                 best.prefill_chunk, best.prefill_buckets, best.block_len,
                 best.spec_k, predicted_tok_s)
    plan = MeshPlan(cache_quant_int8=args.cache_quant_int8)
    params = arch.init_params(jax.random.PRNGKey(args.seed))
    # spec decoding writes up to spec_k rejected-tail tokens past the cursor
    max_len = args.prompt_len + args.new_tokens + 1 + args.spec_k
    # round up so max_len is whole blocks (paged) and whole prefill chunks
    # (chunked admission) — both constraints at once via the lcm
    quantum = 1
    if args.kv_layout == "paged":
        quantum = args.block_len
    if args.prefill_chunk:
        quantum = math.lcm(quantum, args.prefill_chunk)
    max_len += (-max_len) % quantum
    spec = None
    if args.spec_k:
        from repro.serve import SpecConfig

        spec = SpecConfig(k=args.spec_k, draft=args.spec_draft,
                          draft_sparsity=args.spec_sparsity)
    sc = ServeConfig(
        max_len=max_len,
        temperature=args.temperature,
        loop=args.loop,
        eos_token=args.eos_token,
        kv_layout=args.kv_layout,
        block_len=args.block_len,
        spec=spec,
        weight_quant=args.weight_quant,
        weight_quant_sparsity=args.weight_quant_sparsity,
    )
    eng = ServeEngine(arch, params, plan, sc)
    if args.workload == "poisson":
        useful, total, sched = _run_poisson(eng, args, draws)
        if predicted_tok_s is not None:
            log.info("autotune: predicted %.1f tok/s (model units, ranking "
                     "only) vs measured %.1f tok/s", predicted_tok_s,
                     useful / total if total > 0 else 0.0)
        # close the PR 7 loop: re-rank with the acceptance length this run
        # actually measured, so speculation competes on real numbers
        if args.autotune and sched.spec is not None:
            acc = spec_accept_len(sched.stats)
            if acc is not None:
                from repro.roofline.autotune import WorkloadSpec, autotune

                _, p_lens, n_news, _ = draws
                w = WorkloadSpec(tuple(int(x) for x in p_lens),
                                 tuple(int(x) for x in n_news),
                                 n_slots=args.slots,
                                 max_len=max_len)
                res2 = autotune(arch.cfg, w, hw,
                                paged=(args.kv_layout == "paged"),
                                spec_ks=(0, sched.spec.k),
                                spec_accept_len=acc,
                                cache_bytes_per_elem=cache_bpe,
                                weight_bytes_per_elem=weight_bpe)
                log.info("autotune re-rank with measured acceptance "
                         "%.2f tok/step: pick %s (predicted %.1f tok/s, "
                         "spec_k=%d)", acc, res2.best.label(),
                         res2.ranked[0].tok_s, res2.best.spec_k)
    else:
        _run_batch(eng, args)


if __name__ == "__main__":
    main()
