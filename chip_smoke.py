"""Smoke run on one TPU: serve tinyllama-1.1b at its published width, and run
every SONIC Pallas kernel compiled for the chip.

    python chip_smoke.py        # from the repo root, on a machine with a TPU

Phases, all in this one process (the chip belongs to one process at a time):

  device       JAX must find a TPU; otherwise exit non-zero before any work.
  serve        the continuous-batching path (``ServeEngine`` + paged KV +
               chunked prefill + ``ContinuousScheduler``) at the model's full
               width with seeded random weights: 8 requests, prompts of
               16–256 tokens, 32 greedy tokens each.  Run cold (compiles),
               then again warm; both runs must emit the same tokens.
  correctness  every served token (first token from the scheduler's
               prefill program, the rest from its decode segments) must
               score within TIE_MARGIN of the argmax of the same model
               forward in fp32 at ``highest`` matmul precision, run on the
               served sequence, and agree with that argmax at no less than
               ARGMAX_AGREEMENT_FLOOR of positions; every first divergence
               from the sequential oracle ``ServeEngine.generate`` must be
               such an fp32 near-tie (the CPU tests hold the two
               bit-identical, the chip need not).  The chunk-resume
               prefill forward over a paged block table, run as a copy of
               the scheduler's prefill program, must give finite logits
               within LOGIT_REL_RMS_TOL of the fp32 forward.
  kernels      each SONIC kernel entry point at a tinyllama FFN projection
               (K 2048 x N 5632, 128x128 blocks), decode (M 4) and prefill
               (M 256) rows, against its ``ref.py``; its lowered program must
               hold the Mosaic kernel (``tpu_custom_call``).

Diagnostics go to earlier lines.  The last line is one JSON object, printed
only when every phase passed: {"ok": true, "device": {...}}.
"""
from __future__ import annotations

import json
import math
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.registry import get_arch
from repro.serve import ContinuousScheduler, ServeConfig, ServeEngine, SubmitRequest
from repro.sharding.mesh import MeshPlan
from repro.utils.compile_cache import enable_compile_cache

ARCH = "tinyllama-1.1b"
SEED = 0
N_REQUESTS, MIN_PROMPT, MAX_PROMPT, NEW_TOKENS = 8, 16, 256, 32
N_SLOTS, BLOCK_LEN, PREFILL_CHUNK, PREFILL_BUCKETS = 4, 16, 64, 2
# Served logits (bf16 activations, weights and KV) against the fp32 forward:
# RMS of the difference over RMS of the reference, on prompt positions.
# bf16 keeps 8 significant bits (u = 2^-8 ≈ 0.4%); a forward of 22 layers
# rounds each activation a few dozen times, which stays within a few
# percent, while a wrong cache, mask or position is off by order 100%.
LOGIT_REL_RMS_TOL = 0.05
# A served token's fp32 logit may sit this far below the fp32 argmax.  On a
# TPU v5e at this width the bf16 logits differ from the fp32 forward by up
# to about 0.1, so two logits whose fp32 gap is under about twice that can
# trade places; a wrong cache, mask or position picks tokens whole logits
# below the argmax (random weights spread the logits with a standard
# deviation near 1.3).
TIE_MARGIN = 0.25
# Served tokens that are the fp32 argmax itself, at least (0.957 measured on
# a TPU v5e; each of the others must still be a near-tie, above).
ARGMAX_AGREEMENT_FLOOR = 0.9
# Kernel against its fp32 ref.py at highest precision: max|Δ| over max|ref|.
# Covers one bf16 pass on the MXU and bf16 outputs; a wrong block, scale or
# centroid is off by order 100%.
KERNEL_REL_TOL = 1e-2
# Kernel shapes: tinyllama's FFN up-projection, 75% block sparsity, 64
# clusters (and 256, a codebook past one 128-lane row, for clustered_matmul),
# and a compressed activation of a quarter of K for sparse_matvec.
KERNEL_K, KERNEL_N, KERNEL_BLOCK, KERNEL_SPARSITY = 2048, 5632, 128, 0.75
KERNEL_ROWS = (4, 256)


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


class CompileClock:
    """Seconds the backend spent compiling (or reading the persistent cache),
    and persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[float, int, int]:
        return self.seconds, self.programs, self.cache_hits


def make_prompts(vocab: int) -> list[np.ndarray]:
    rng = np.random.RandomState(SEED)
    lens = rng.randint(MIN_PROMPT, MAX_PROMPT + 1, N_REQUESTS)
    lens[:2] = MIN_PROMPT, MAX_PROMPT  # both ends of the range, every run
    return [rng.randint(0, vocab, n).astype(np.int32) for n in lens]


def serve_max_len() -> int:
    # as launch/serve.py sizes it: prompt + budget + 1, in whole blocks and
    # whole prefill chunks
    q = math.lcm(BLOCK_LEN, PREFILL_CHUNK)
    return -(-(MAX_PROMPT + NEW_TOKENS + 1) // q) * q


def build_engine(arch, params) -> ServeEngine:
    sc = ServeConfig(max_len=serve_max_len(), kv_layout="paged",
                     block_len=BLOCK_LEN)
    return ServeEngine(arch, params, MeshPlan(), sc)


def serve(eng: ServeEngine, prompts) -> tuple[list[list[int]], float, dict]:
    """One closed batch through the continuous scheduler; every request must
    retire with its full budget of in-vocabulary tokens."""
    sched = ContinuousScheduler(eng, n_slots=N_SLOTS,
                                prefill_chunk=PREFILL_CHUNK,
                                prefill_buckets=PREFILL_BUCKETS)
    t0 = time.perf_counter()
    handles = [sched.submit(SubmitRequest(p, NEW_TOKENS)) for p in prompts]
    sched.run()
    wall = time.perf_counter() - t0
    vocab = eng.cfg.vocab_size
    for h in handles:
        if not h.done or len(h.tokens) != NEW_TOKENS:
            fail(f"request {h.rid} ended {h.state} with {len(h.tokens)} of "
                 f"{NEW_TOKENS} tokens")
        if not all(0 <= t < vocab for t in h.tokens):
            fail(f"request {h.rid} emitted a token outside the vocabulary")
    return [list(h.tokens) for h in handles], wall, sched.stats


def launch_rows(seqs: list[np.ndarray], max_len: int):
    """Yield (rows used, (N_SLOTS, max_len) tokens) per launch of N_SLOTS
    rows, each sequence zero-padded after its end."""
    for g in range(0, len(seqs), N_SLOTS):
        group = seqs[g:g + N_SLOTS]
        toks = np.zeros((N_SLOTS, max_len), np.int32)
        for r, s in enumerate(group):
            toks[r, :len(s)] = s
        yield len(group), toks


def chunk_resume_logits(eng: ServeEngine,
                        seqs: list[np.ndarray]) -> jax.Array:
    """(len(seqs), max_len, V) logits of the chunk-resume prefill forward
    over ``seqs``.

    A copy of the forward the scheduler's prefill program runs (that
    program keeps only each row's sampled token): chunks of PREFILL_CHUNK
    tokens over a paged pool through a block table, bf16 compute at default
    matmul precision, N_SLOTS rows per launch, without the program's bucket
    padding, per-row starts or dummy rows.  Padding after a sequence's end
    is causally invisible to its real positions."""
    arch, cfg, plan = eng.arch, eng.cfg, eng.plan
    max_len = eng.sc.max_len
    mb = eng.max_blocks_per_slot
    pool = eng.init_paged_cache(N_SLOTS * mb, N_SLOTS)
    table = jnp.asarray(N_SLOTS + np.arange(N_SLOTS * mb).reshape(N_SLOTS, mb),
                        jnp.int32)
    chunk = jax.jit(
        lambda params, cache, toks, starts: arch.forward(
            params, plan, cfg=cfg, tokens=toks, cache=cache,
            cache_pos=starts, block_table=table),
        donate_argnums=(1,))
    out = []
    for used, toks in launch_rows(seqs, max_len):
        rows = []
        for c in range(0, max_len, PREFILL_CHUNK):
            starts = jnp.full((N_SLOTS,), c, jnp.int32)
            logits, pool = chunk(eng.params, pool,
                                 jnp.asarray(toks[:, c:c + PREFILL_CHUNK]),
                                 starts)
            rows.append(logits.astype(jnp.float32))
        out.append(jnp.concatenate(rows, axis=1)[:used])
    return jnp.concatenate(out, axis=0)


def reference_logits(eng: ServeEngine, seqs: list[np.ndarray]) -> jax.Array:
    """The same model forward in fp32 at ``highest`` matmul precision, no
    cache, one launch per N_SLOTS rows."""
    arch, plan = eng.arch, eng.plan
    cfg32 = eng.cfg.replace(compute_dtype="float32")
    max_len = eng.sc.max_len
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda params, toks: arch.forward(
            params, plan, cfg=cfg32, tokens=toks)[0])
        out = [fwd(eng.params, jnp.asarray(toks))[:used]
               for used, toks in launch_rows(seqs, max_len)]
    return jnp.concatenate(out, axis=0)


@jax.jit
def _logit_stats(chunked, ref, prompt_mask, seq_mask):
    """Per row: all chunk-resume logits finite over the sequence, and over
    the prompt the RMS of chunked − ref, the RMS of ref, max|chunked − ref|
    and max|ref|."""
    finite = jnp.all(jnp.isfinite(chunked) | ~seq_mask[..., None], axis=(1, 2))
    m = prompt_mask[..., None]
    d = jnp.where(m, chunked - ref, 0.0)
    r = jnp.where(m, ref, 0.0)
    n = prompt_mask.sum(1) * chunked.shape[-1]
    return (finite, jnp.sqrt((d * d).sum((1, 2)) / n),
            jnp.sqrt((r * r).sum((1, 2)) / n), jnp.abs(d).max((1, 2)),
            jnp.abs(r).max((1, 2)))


def correctness(eng: ServeEngine, prompts, served_tokens) -> dict:
    """The served tokens against the fp32 forward on the served sequences
    and against ``ServeEngine.generate``, and the chunk-resume prefill
    logits against the fp32 forward; fails on any gate in the module
    docstring."""
    # each request's prompt and the tokens it was served, but the last: the
    # logits at every position that chose a served token
    seqs = [np.concatenate([p, np.asarray(t[:-1], np.int32)])
            for p, t in zip(prompts, served_tokens)]
    chunked = chunk_resume_logits(eng, seqs)
    ref = reference_logits(eng, seqs)
    max_len = eng.sc.max_len
    pos = np.arange(max_len)[None]
    p_len = np.asarray([len(p) for p in prompts])[:, None]
    s_len = np.asarray([len(s) for s in seqs])[:, None]
    finite, d_rms, r_rms, d_max, r_max = jax.device_get(_logit_stats(
        chunked, ref, jnp.asarray(pos < p_len), jnp.asarray(pos < s_len)))
    rel = d_rms / r_rms
    # the fp32 forward at each served token's position: its argmax, and
    # how far below the argmax it scores the served token
    ref_agree, ref_gap = [], []
    for i, (p, t) in enumerate(zip(prompts, served_tokens)):
        at = ref[i, len(p) - 1:len(p) - 1 + NEW_TOKENS]  # (NEW_TOKENS, V)
        tok = jnp.asarray(t, jnp.int32)[:, None]
        top, pick = at.max(-1), at.argmax(-1)
        got = jnp.take_along_axis(at, tok, axis=-1)[:, 0]
        ref_agree.append(float(jnp.mean(pick == tok[:, 0])))
        ref_gap.append(float((top - got).max()))
    oracle = [np.asarray(eng.generate(jnp.asarray(p)[None], NEW_TOKENS))[0]
              for p in prompts]
    same = [int(np.sum(o == np.asarray(t))) for o, t in zip(oracle, served_tokens)]
    first_diff = [int(np.argmax(o != np.asarray(t))) if n < NEW_TOKENS else None
                  for o, t, n in zip(oracle, served_tokens, same)]
    # Up to its first divergence the oracle saw the served prefix, so the fp32
    # forward at that position scores both picks: a gap between them within
    # TIE_MARGIN is a near-tie that rounding decided, not a wrong cache, mask
    # or position.
    ties = []
    for i, (p, t) in enumerate(zip(prompts, first_diff)):
        if t is None:
            continue
        q = len(p) - 1 + t
        gap = float(abs(ref[i, q, served_tokens[i][t]] - ref[i, q, oracle[i][t]]))
        err = float(jnp.abs(chunked[i, q] - ref[i, q]).max())
        ties.append(gap <= TIE_MARGIN)
        print(f"  request {i}: first divergence from generate at token {t}: "
              f"fp32 gap between the two picks {gap:.6f}, chunk-resume "
              f"max|Δ| there {err:.6f}", flush=True)
    del chunked, ref
    for i, p in enumerate(prompts):
        print(f"  request {i}: prompt {len(p):3d}  chunk-resume logits rel-RMS "
              f"{rel[i]:.6f}  max|Δ| {d_max[i]:.6f} (max|ref| {r_max[i]:.4f})"
              f"  finite {bool(finite[i])}  served==generate "
              f"{same[i]}/{NEW_TOKENS}  served==fp32-argmax "
              f"{ref_agree[i]:.3f}  max fp32 gap below argmax "
              f"{ref_gap[i]:.6f}", flush=True)
    if not finite.all():
        fail(f"non-finite chunk-resume logits in requests "
             f"{[i for i, f in enumerate(finite) if not f]}")
    if rel.max() > LOGIT_REL_RMS_TOL:
        fail(f"chunk-resume prefill logits off the fp32 reference: rel-RMS "
             f"{rel.max():.6f} > {LOGIT_REL_RMS_TOL}")
    if max(ref_gap) > TIE_MARGIN:
        fail(f"a served token scores {max(ref_gap):.6f} below the fp32 "
             f"argmax (limit {TIE_MARGIN})")
    if np.mean(ref_agree) < ARGMAX_AGREEMENT_FLOOR:
        fail(f"served tokens match the fp32 argmax at {np.mean(ref_agree):.4f}"
             f" of positions (floor {ARGMAX_AGREEMENT_FLOOR})")
    if sum(ties) != len(ties):
        fail(f"{len(ties) - sum(ties)} of {len(ties)} divergences from "
             f"ServeEngine.generate are not fp32 near-ties")
    return {
        "prefill_rel_rms_max": float(rel.max()),
        "prefill_max_abs_diff": float(d_max.max()),
        "ref_max_abs": float(r_max.max()),
        "generate_agreement": sum(same) / (NEW_TOKENS * len(prompts)),
        "generate_identical_requests": sum(n == NEW_TOKENS for n in same),
        "generate_first_divergence": first_diff,
        "fp32_argmax_agreement": float(np.mean(ref_agree)),
        "fp32_gap_max": max(ref_gap),
        "divergences_at_near_ties": (sum(ties), len(ties)),
    }


def kernel_cases(k=KERNEL_K, n=KERNEL_N):
    """(name, entry point, weight args, ref(x) -> y) for each SONIC kernel
    entry point, from seeded dense weights through the repo's converters."""
    from repro.core.clustering import ClusteringConfig, pack_clustered
    from repro.core.sonic_layers import make_block_sparse, make_block_sparse_int8
    from repro.kernels.block_sparse_matmul import ops as bsm_ops
    from repro.kernels.block_sparse_matmul import ref as bsm_ref
    from repro.kernels.clustered_matmul.ops import clustered_matmul
    from repro.kernels.clustered_matmul.ref import clustered_matmul_ref
    from repro.kernels.sonic_matmul import ops as sonic_ops
    from repro.kernels.sonic_matmul import ref as sonic_ref
    from repro.kernels.sparse_matvec.kernel import row_table
    from repro.kernels.sparse_matvec.ops import sparse_matvec
    from repro.kernels.sparse_matvec.ref import sparse_matvec_ref

    kw, kidx = jax.random.split(jax.random.PRNGKey(SEED))
    w = 0.02 * jax.random.normal(kw, (k, n), jnp.float32)
    bs = (KERNEL_BLOCK, KERNEL_BLOCK)
    sw = sonic_ops.make_sonic_weight(w, KERNEL_SPARSITY, bs, num_clusters=64)
    qw = make_block_sparse_int8(w, KERNEL_SPARSITY, bs)
    bw = make_block_sparse(w, KERNEL_SPARSITY, bs)
    cw = pack_clustered(w, ClusteringConfig(num_clusters=64))
    cw2 = pack_clustered(w, ClusteringConfig(num_clusters=256))
    rows = row_table(w)  # stored once, as a server would keep it
    knz = k // 4
    idx = jnp.sort(jax.random.permutation(kidx, k)[:knz]).astype(jnp.int32)
    s = (sw.idx_values, sw.codebook, sw.indices, sw.k_blocks)
    q = (qw.values, qw.scales, qw.indices, qw.k_blocks)
    return [
        ("sonic_matmul", sonic_ops.sonic_matmul, (sw,),
         lambda x: sonic_ref.sonic_matmul_ref(x, *s), k),
        ("sonic_matvec", sonic_ops.sonic_matvec, (sw,),
         lambda x: sonic_ref.sonic_matvec_ref(x, *s), k),
        ("sonic_matmul_int8", sonic_ops.sonic_matmul_int8, (qw,),
         lambda x: sonic_ref.sonic_matmul_int8_ref(x, *q), k),
        ("sonic_matvec_int8", sonic_ops.sonic_matvec_int8, (qw,),
         lambda x: sonic_ref.sonic_matvec_int8_ref(x, *q), k),
        ("block_sparse_matmul", bsm_ops.block_sparse_matmul, (bw,),
         lambda x: bsm_ref.block_sparse_matmul_ref(
             x, bw.values, bw.indices, bw.k_blocks), k),
        ("block_sparse_matmul_int8", bsm_ops.block_sparse_matmul_int8, (qw,),
         lambda x: bsm_ref.block_sparse_matmul_int8_ref(x, *q), k),
        ("clustered_matmul",
         lambda x, i, c: clustered_matmul(x, i, c), (cw.indices, cw.codebook),
         lambda x: clustered_matmul_ref(x, cw.indices, cw.codebook), k),
        ("clustered_matmul_c256",
         lambda x, i, c: clustered_matmul(x, i, c), (cw2.indices, cw2.codebook),
         lambda x: clustered_matmul_ref(x, cw2.indices, cw2.codebook), k),
        ("sparse_matvec", lambda x, i, wt: sparse_matvec(x, i, wt),
         (idx, rows), lambda x: sparse_matvec_ref(x, idx, w), knz),
    ]


def run_kernels(cases, rows=KERNEL_ROWS):
    """Each case at each row count and activation dtype: max|Δ| against its
    ref (fp32 at highest precision) and whether the lowered program holds a
    Mosaic kernel."""
    results = []
    key = jax.random.PRNGKey(SEED + 1)
    for name, fn, wargs, ref, width in cases:
        entry = jax.jit(fn)
        for m in rows:
            for dt in (jnp.float32, jnp.bfloat16):
                key, sub = jax.random.split(key)
                x = jax.random.normal(sub, (m, width), jnp.float32).astype(dt)
                hlo = entry.lower(x, *wargs).as_text()
                t0 = time.perf_counter()
                y = jax.block_until_ready(entry(x, *wargs))
                secs = time.perf_counter() - t0
                with jax.default_matmul_precision("highest"):
                    want = ref(x.astype(jnp.float32))
                y32 = np.asarray(y, np.float32)
                want = np.asarray(want, np.float32)
                results.append({
                    "name": name, "m": m, "dtype": jnp.dtype(dt).name,
                    "shape_ok": y32.shape == want.shape,
                    "max_abs_diff": float(np.abs(y32 - want).max()),
                    "max_abs_ref": float(np.abs(want).max()),
                    "custom_call": "tpu_custom_call" in hlo,
                    "first_call_s": secs,
                })
    return results


def main() -> None:
    t_start = time.perf_counter()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{dev.platform!r}); nothing was run")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    print(f"device {device}  jax {jax.__version__}  compile cache {cache_dir}",
          flush=True)

    # ---------------------------------------------------------------- serve
    t0 = time.perf_counter()
    arch = get_arch(ARCH)
    cfg = arch.cfg
    print(f"model {ARCH}: {cfg.n_layers} layers, d {cfg.d_model}, heads "
          f"{cfg.n_heads}/{cfg.n_kv_heads}, ffn {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}, params {cfg.param_dtype}, compute "
          f"{cfg.compute_dtype}", flush=True)
    params = jax.block_until_ready(arch.init_params(jax.random.PRNGKey(SEED)))
    eng = build_engine(arch, params)
    prompts = make_prompts(cfg.vocab_size)
    print(f"set-up {time.perf_counter() - t0:.3f} s; prompts "
          f"{[len(p) for p in prompts]}, {NEW_TOKENS} new tokens each, "
          f"max_len {eng.sc.max_len}", flush=True)

    c0 = clock.snapshot()
    tokens, cold_s, stats = serve(eng, prompts)
    c1 = clock.snapshot()
    warm_tokens, warm_s, _ = serve(eng, prompts)
    if warm_tokens != tokens:
        fail("the warm rerun of the same requests emitted other tokens")
    useful = N_REQUESTS * NEW_TOKENS
    print(f"serve: {N_REQUESTS} requests retired, {useful} tokens; cold "
          f"{cold_s:.3f} s (compile {c1[0] - c0[0]:.3f} s over "
          f"{c1[1] - c0[1]} programs, {c1[2] - c0[2]} persistent-cache "
          f"hits); warm {warm_s:.3f} s = {useful / warm_s:.1f} tok/s on "
          f"{device['kind']} (closed batch, {N_SLOTS} slots); segments "
          f"{stats['segments']}, prefill launches "
          f"{stats['prefill_launches']}", flush=True)

    # ---------------------------------------------------------- correctness
    t0 = time.perf_counter()
    c0 = clock.snapshot()
    corr = correctness(eng, prompts, tokens)
    c1 = clock.snapshot()
    print(f"correctness: served vs fp32 argmax "
          f"{corr['fp32_argmax_agreement']:.4f} (floor "
          f"{ARGMAX_AGREEMENT_FLOOR}), largest fp32 gap below the argmax "
          f"{corr['fp32_gap_max']:.6f} (limit {TIE_MARGIN}); served vs "
          f"ServeEngine.generate greedy agreement "
          f"{corr['generate_agreement']:.4f} "
          f"({corr['generate_identical_requests']}/{N_REQUESTS} requests "
          f"identical, first divergence {corr['generate_first_divergence']}); "
          f"divergences at fp32 near-ties {corr['divergences_at_near_ties']}"
          f"; chunk-resume prefill logits rel-RMS max "
          f"{corr['prefill_rel_rms_max']:.6f} (limit {LOGIT_REL_RMS_TOL}), "
          f"max|Δ| {corr['prefill_max_abs_diff']:.6f} against max|ref| "
          f"{corr['ref_max_abs']:.4f}; "
          f"{time.perf_counter() - t0:.3f} s, compile {c1[0] - c0[0]:.3f} s",
          flush=True)
    if corr["generate_agreement"] < 1.0:
        print("NOTE: on this chip the served tokens are not bit-identical to "
              "ServeEngine.generate (the CPU tests hold them identical)",
              flush=True)
    del eng, params

    # -------------------------------------------------------------- kernels
    t0 = time.perf_counter()
    c0 = clock.snapshot()
    results = run_kernels(kernel_cases())
    c1 = clock.snapshot()
    bad = []
    for r in results:
        rel = r["max_abs_diff"] / max(r["max_abs_ref"], 1e-30)
        print(f"  kernel {r['name']:25s} M {r['m']:3d} {r['dtype']:8s} "
              f"max|Δ| {r['max_abs_diff']:.3e} rel {rel:.3e} "
              f"tpu_custom_call {r['custom_call']}  first call "
              f"{r['first_call_s']:.3f} s", flush=True)
        if not (r["shape_ok"] and r["custom_call"] and rel <= KERNEL_REL_TOL):
            bad.append(f"{r['name']} M {r['m']} {r['dtype']}")
    print(f"kernels: {len(results) - len(bad)}/{len(results)} passed "
          f"(limit max|Δ| <= {KERNEL_REL_TOL} max|ref|); "
          f"{time.perf_counter() - t0:.3f} s, compile {c1[0] - c0[0]:.3f} s",
          flush=True)
    if bad:
        fail(f"kernels off their reference or not compiled for the TPU: {bad}")

    peak = dev.memory_stats() or {}
    total = clock.snapshot()
    print(f"total {time.perf_counter() - t_start:.3f} s, compile "
          f"{total[0]:.3f} s over {total[1]} programs, {total[2]} "
          f"persistent-cache hits, peak device bytes "
          f"{peak.get('peak_bytes_in_use', 'not reported')}", flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
