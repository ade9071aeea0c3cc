"""Batched serving engine: fully-compiled generation with per-sequence
stopping and SONIC-compressed weights.

Execution paths (``ServeConfig.loop``):

  "scan"    (default) prefill→decode as TWO compiled programs total:
            one jitted prefill+first-sample, and one jitted ``lax.scan``
            that carries ``(cache, tok, pos, done, key)`` on-device for all
            remaining steps.  Zero host transfers between decode steps; the
            KV cache is **donated** into the loop program (``donate_argnums``)
            so XLA aliases the prefill-built buffers instead of copying the
            full cache at loop entry.
  "while"   same two-program structure but the loop is a ``lax.while_loop``
            that exits as soon as every sequence has emitted ``eos_token``
            (untaken steps come back pinned to ``eos_token``).  Output-
            equivalent to "scan"; pays a dynamic trip count for the early
            exit.
  "python"  the legacy host loop (one jitted decode step per token,
            host-side sampling / key splits).  Kept only as the oracle the
            equivalence tests hold the compiled loops to.

Decode kernel dispatch: when serving SONIC-converted weights
(``core.sonic_layers`` mode "sonic"), ``sonic_matmul`` routes activations
whose flattened row count is below ``DECODE_M_THRESHOLD`` (= 8, the fp32
sublane tile — see ``kernels/sonic_matmul/ops.py``) to the decode-shaped
fused matvec kernel: grid over (N-blocks, kept-K-blocks) only, no M-tiling
and no pad-to-8 of the single decode row, so per-token weight traffic stays
∝ (1 − sparsity)/2 instead of being washed out by padding FLOPs.

Semantics (identical across all three paths, greedy outputs bit-identical):
the first token is sampled from the prefill logits and is never eos-pinned;
every subsequent token is eos-checked, and once a sequence has emitted
``eos_token`` all its later tokens are pinned to ``eos_token``.

Continuous batching (``repro.serve.scheduler``) builds on extra compiled
programs exposed here: ``_prefill_slot`` (prefill one ragged-length request
into one row of a fixed-capacity slot cache), ``_prefill_slots`` (batched /
bucketed admission: ONE launch prefills one chunk for up to ``n_slots``
same-bucket requests at fixed (n_slots, bucket) shapes, resuming each row at
its own cache offset — total prefill traces are bounded by the bucket set,
not by distinct prompt lengths), and ``_slot_segment`` (a ``lax.scan`` of S
masked decode steps over all slots, carry ``(cache, tok, pos, done, key)``
with per-slot ``active``/``limit`` inputs).  All donate the slot cache, so
device state persists across segments without copies.  Every slot program
is emitted by ONE builder parametrized over the cache layout: under
``ServeConfig.kv_layout="paged"`` the same bodies run over a fixed block
pool + host-policy block table instead of per-slot ``max_len`` rows
(``_prefill_slot_paged`` / ``_prefill_slots_paged`` /
``_slot_segment_paged`` / ``_slot_segment_while_paged``) — greedy outputs
stay bit-identical to the dense slot path.

Speculative decoding (``ServeConfig.spec = SpecConfig(k, draft=…)``, PR 5):
the scheduler's segments become draft-and-verify rounds
(``_slot_spec_segment[_while][_paged]``).  Each round drafts ``k`` tokens
with a cheap drafter derived from the served weights (a sparse SONIC
conversion, or a layer-truncated prefix reading the verifier's own KV),
verifies all of them in ONE ``decode_chunk`` forward of the served model —
each window row bitwise the computation sequential decode would do — and
emits the longest matching prefix plus the verifier's bonus token (1..k+1
tokens/step).  Rejected tokens cost nothing to undo: rollback is cursor
truncation, on the dense rows and on the paged block table alike.  Greedy
speculative outputs are bit-identical to the plain scheduler.  See
docs/serving.md.

Held-expert counts: for a model whose expert layer holds a share of the
experts (``ModelConfig.moe_router == "sigmoid_bias"``), every slot program
also returns the (token, held expert) assignments of its real tokens,
summed on the device (a prefill program's ``held``, None for other models;
a segment program's ``counts["held"]``), which the scheduler downloads with
the tokens it already fetches.

KV block counts: a paged segment program's ``counts`` also hold, summed on
the device over its steps, the KV blocks its decode steps (and verify
windows) read (``kv_read``: each slot's walk up to its length, summed over
slots and layers) and what reading every slot's whole ``max_len`` would
(``kv_span``).  Where attention gathers ``max_len`` (latent attention, int8
KV) the two are equal.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention import decode_walk
from repro.sharding.mesh import MeshPlan
from repro.serve.sampling import sample_token, spec_accept
from repro.utils.logging import get_logger

log = get_logger("serve")


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Speculative decoding: draft ``k`` tokens per step with a cheap
    drafter, verify them in ONE ``decode_chunk`` forward of the served
    model, emit the longest matching prefix (+ the verifier's bonus token),
    and roll the KV cursor back over the rejected tail.

    ``draft`` selects the drafter, always derived from the served weights
    (no second checkpoint):

      "self"        sparse-mode conversion of the same weights
                    (``core.sonic_layers.sparse_draft_params``: balanced
                    block pruning at ``draft_sparsity`` + optional
                    ``draft_clusters``-entry codebook) — the SONIC economics
                    applied to drafting: on sparse hardware the drafter
                    moves (1 − sparsity) of the verifier's weight traffic.
                    ``draft_sparsity=0.0`` makes the conversion exact (the
                    full-acceptance oracle used in tests).
      "truncate:N"  the first N layers of the served stack + the shared
                    final norm / LM head (layer-skipping self-drafter).
                    Because the prefix weights are identical, the drafter
                    reads a slice of the verifier's own KV cache — no
                    drafter prefill, no second cache to roll back.

    Greedy only (``temperature == 0``): acceptance is exact-match, so the
    emitted stream is bit-identical to non-speculative decoding.
    """

    k: int = 4
    draft: str = "self"  # "self" | "truncate:N"
    draft_sparsity: float = 0.75
    draft_clusters: int = 0  # 0 ⇒ no codebook quantization of the drafter

    def __post_init__(self):
        assert self.k >= 1, self.k
        assert 0.0 <= self.draft_sparsity < 1.0, self.draft_sparsity
        if self.draft != "self":
            assert self.draft.startswith("truncate:") and int(
                self.draft.split(":", 1)[1]
            ) >= 1, f"draft must be 'self' or 'truncate:N', got {self.draft!r}"


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_len: int = 512
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    eos_token: int = -1  # -1 ⇒ never stop early
    jit: bool = True
    loop: str = "scan"  # "scan" | "while" | "python"
    # continuous-batching cache layout: "dense" = one max_len row per slot
    # (PR 2); "paged" = fixed pool of block_len-sized KV blocks + block table
    # (greedy outputs bit-identical; admission gated on free blocks).
    kv_layout: str = "dense"  # "dense" | "paged"
    block_len: int = 16
    # speculative decoding for the continuous scheduler (PR 5); None = plain
    # one-token-per-step decode.  Families without chunk-resume fall back
    # with ``engine.spec_skip_reason``.
    spec: SpecConfig | None = None
    # int8 block-sparse weight quantization (ISSUE 10): "int8" rewrites every
    # linear projection (attention q/k/v/o, FFN, LM head) at engine
    # construction via ``core.sonic_layers.quantize_serve_params`` — weights
    # then live int8 with one fp32 scale per kept block, and every slot
    # program runs through the same quantized tree (no new compiled traces:
    # program shapes are unchanged).  ``weight_quant_sparsity`` > 0 also
    # block-prunes (balanced top-|L1|, the SONIC C1 structure); block=None
    # picks the largest power-of-two block dividing each dim.
    weight_quant: str = "none"  # "none" | "int8"
    weight_quant_sparsity: float = 0.0
    weight_quant_block: tuple[int, int] | None = None
    # run the scheduler's allocator/table/commitment invariant checks at
    # the end of every segment (PR 6) — on by default in the stress suites,
    # off in production paths (it walks host dicts, never the device)
    debug_invariants: bool = False


def _scoped(name: str, fn):
    """``fn`` traced under ``jax.named_scope(name)``, with its own name and
    signature."""

    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope(name):
            return fn(*args, **kwargs)

    return scoped


_SLOT_PROGRAMS = ("prefill_slot", "prefill_slots", "slot_segment",
                  "slot_segment_while", "prefill_slot_paged",
                  "prefill_slots_paged", "slot_segment_paged",
                  "slot_segment_while_paged", "slot_spec_segment",
                  "slot_spec_segment_while", "slot_spec_segment_paged",
                  "slot_spec_segment_while_paged")


class ServeEngine:
    def __init__(self, arch, params, plan: MeshPlan, sc: ServeConfig, cfg=None):
        assert sc.loop in ("scan", "while", "python"), sc.loop
        assert sc.kv_layout in ("dense", "paged"), sc.kv_layout
        if sc.kv_layout == "paged":
            # max_blocks·block_len == max_len keeps the gathered virtual
            # cache the exact shape of the dense slot row — the bit-identical
            # greedy contract depends on it (see docs/serving.md)
            assert sc.max_len % sc.block_len == 0, (
                f"max_len {sc.max_len} not a multiple of block_len "
                f"{sc.block_len}"
            )
            # single-device only for now: the paged branch does not apply
            # plan.cache_spec() constraints, so under a mesh GSPMD would be
            # free to replicate the pool — defeating the memory ceiling
            assert plan.mesh is None, (
                "kv_layout='paged' is not wired for meshed serving yet "
                "(pool sharding constraints missing — see ROADMAP)"
            )
        assert sc.weight_quant in ("none", "int8"), sc.weight_quant
        model_cfg = cfg or arch.cfg
        if sc.kv_layout == "dense" and arch.dense_layout_skip_reason():
            raise NotImplementedError(
                f"{arch.arch_id}: {arch.dense_layout_skip_reason()}")
        if plan.cache_quant_int8 and arch.kv_int8_skip_reason():
            raise NotImplementedError(
                f"{arch.arch_id}: {arch.kv_int8_skip_reason()}")
        if sc.weight_quant == "int8" and (model_cfg.attention == "mla"
                                          or model_cfg.moe_router != "softmax"):
            raise NotImplementedError(
                f"{arch.arch_id}: int8 weights are not wired for latent "
                f"attention or the held-expert layer, which read their raw "
                f"matrices (absorption, grouped expert matmuls, router)")
        raw_params = params  # pre-quantization tree (drafter derivation)
        if sc.weight_quant == "int8":
            # one-time host-side conversion: every slot program reads
            # ``self.params``, so the whole serving surface (prefill, decode,
            # spec verify, drafters) runs the quantized tree without any new
            # compiled trace shapes
            from repro.core.sonic_layers import quantize_serve_params

            params = quantize_serve_params(
                params, sparsity=sc.weight_quant_sparsity,
                block=sc.weight_quant_block,
            )
        self.arch, self.params, self.plan, self.sc = arch, params, plan, sc
        self.cfg = model_cfg

        # ------------------------- speculative decoding (drafter resolution)
        #
        # ``sc.spec`` attaches a drafter derived from the served weights.
        # Families whose cache cannot chunk-resume / cursor-roll-back fall
        # back to plain decode with the reason in ``spec_skip_reason`` —
        # mirroring the chunked-prefill fallback.  The int8-quantized KV
        # cache is NOT excluded (ISSUE 10): verify rows attend the same
        # dequantized values sequential decode attends, so greedy spec
        # output stays bit-identical to sequential int8-KV decoding.
        self.spec = sc.spec
        self.spec_skip_reason = ""
        self.draft_params = None
        self.draft_cfg = None
        if sc.spec is not None:
            assert sc.temperature <= 0.0, (
                "speculative decoding is greedy-only for now: acceptance is "
                "exact-match against the greedy verifier (rejection-sampling "
                "speculation for temperature > 0 is a ROADMAP item)"
            )
            reason = arch.spec_decode_skip_reason()
            if reason:
                self.spec = None
                self.spec_skip_reason = reason
                log.warning(
                    "speculative decoding disabled — falling back to plain "
                    "decode: %s", reason,
                )
            else:
                if sc.kv_layout == "paged":
                    # a retired slot's whole verify window lands in its one
                    # scratch block; offsets stay distinct (unique_indices)
                    # only while the window fits a block
                    assert sc.spec.k < sc.block_len, (
                        f"spec.k {sc.spec.k} must be < block_len "
                        f"{sc.block_len} (the K+1-token verify window of a "
                        f"masked slot must fit its scratch block)"
                    )
                from repro.core.sonic_layers import (
                    sparse_draft_params, truncated_draft_params,
                )

                if self.spec.draft == "self":
                    # derived from the RAW tree: the sparse conversion
                    # re-densifies 3-D stacked kernels, which the int8
                    # serving representation no longer has.  The drafter
                    # therefore runs fp even under weight_quant — drafting
                    # accuracy is a perf knob, verification is exact either
                    # way.
                    self.draft_cfg = self.cfg
                    self.draft_params = sparse_draft_params(
                        raw_params, self.spec.draft_sparsity,
                        num_clusters=self.spec.draft_clusters,
                    )
                else:  # "truncate:N"
                    n = int(self.spec.draft.split(":", 1)[1])
                    assert 1 <= n <= self.cfg.n_layers, (n, self.cfg.n_layers)
                    self.draft_cfg = self.cfg.replace(n_layers=n)
                    self.draft_params = truncated_draft_params(params, n)
        # traced / called counters: tests assert no-recompile and
        # one-program-per-loop from these.
        self.trace_counts: dict[str, int] = {
            k: 0 for k in ("prefill", "decode", "decode_loop", *_SLOT_PROGRAMS)
        }
        self.call_counts: dict[str, int] = {
            k: 0 for k in ("prefill", "decode", "decode_loop", *_SLOT_PROGRAMS)
        }
        # cache-contract checks run once per engine, not per scheduler: the
        # paged check eval_shape-traces a full forward, which would otherwise
        # tax every scheduler construction
        self._checked_contracts: set[str] = set()

        def sample(logits, key):
            with jax.named_scope("sample"):
                return sample_token(logits, key, sc.temperature, sc.top_k,
                                    sc.top_p)

        counting = self.counts_moe_rows
        mb = self.max_blocks_per_slot
        # decode-style attention reads each slot's blocks up to its length
        # over an unquantized GQA cache; latent attention and int8 KV
        # gather max_len
        own_blocks = (self.cfg.attention != "mla"
                      and not plan.cache_quant_int8)

        def kv_counts(pos, c, live):
            """{kv_read, kv_span}: the KV blocks one decode-style forward of
            ``c`` queries a slot reads over the paged pool, summed over
            slots and layers, and a whole ``max_len`` read's."""
            span = jnp.int32(self.cfg.n_layers * pos.shape[0] * mb)
            if not own_blocks:
                return {"kv_read": span, "kv_span": span}
            walk = jnp.clip(decode_walk(pos, c, live, sc.block_len), 1, mb)
            return {"kv_read": self.cfg.n_layers * jnp.sum(walk),
                    "kv_span": span}

        def forward_counted(params, real, **kw):
            """``arch.forward`` → (logits, cache, held): ``held`` counts the
            held-expert assignments of the tokens ``real`` (B, S) marks, on
            a model that counts them; None on any other."""
            if not counting:
                return (*arch.forward(params, plan, cfg=self.cfg, **kw), None)
            return arch.forward(params, plan, cfg=self.cfg, count_mask=real,
                                **kw)

        def prefill(params, tokens, key):
            self.trace_counts["prefill"] += 1
            b = tokens.shape[0]
            cache = arch.init_cache(b, sc.max_len, plan, cfg=self.cfg)
            logits, cache = arch.forward(
                params, plan, cfg=self.cfg, tokens=tokens, cache=cache
            )
            tok = sample(logits[:, -1], key)
            pos = jnp.full((b,), tokens.shape[1], jnp.int32)
            done = jnp.zeros((b,), bool)
            return tok, cache, pos, done

        def decode(params, cache, token, pos):
            self.trace_counts["decode"] += 1
            logits, cache = arch.forward(
                params, plan, cfg=self.cfg, tokens=token,
                cache=cache, cache_pos=pos,
            )
            return logits[:, 0], cache

        def step(params, cache, tok, pos, done, key):
            """One on-device decode step (shared by scan and while bodies)."""
            key, sub = jax.random.split(key)
            logits, cache = arch.forward(
                params, plan, cfg=self.cfg, tokens=tok[:, None],
                cache=cache, cache_pos=pos,
            )
            nxt = sample(logits[:, 0], sub)
            if sc.eos_token >= 0:
                done = done | (nxt == sc.eos_token)
                nxt = jnp.where(done, sc.eos_token, nxt)
            return cache, nxt, pos + 1, done, key

        def decode_loop(n_steps, params, cache, tok, pos, done, key):
            self.trace_counts["decode_loop"] += 1

            def body(carry, _):
                cache, tok, pos, done, key = carry
                cache, nxt, pos, done, key = step(params, cache, tok, pos,
                                                  done, key)
                return (cache, nxt, pos, done, key), nxt

            carry, toks = jax.lax.scan(
                body, (cache, tok, pos, done, key), length=n_steps
            )
            return toks.T, carry[0]  # (B, n_steps), final cache

        def decode_loop_while(n_steps, params, cache, tok, pos, done, key):
            self.trace_counts["decode_loop"] += 1
            b = tok.shape[0]
            fill = sc.eos_token if sc.eos_token >= 0 else 0
            out0 = jnp.full((b, n_steps), fill, jnp.int32)

            def cond(st):
                i, *_, done, _key, _out = st
                return (i < n_steps) & ~jnp.all(done)

            def body(st):
                i, cache, tok, pos, done, key, out = st
                cache, nxt, pos, done, key = step(params, cache, tok, pos,
                                                  done, key)
                out = jax.lax.dynamic_update_slice(out, nxt[:, None], (0, i))
                return i + 1, cache, nxt, pos, done, key, out

            st = jax.lax.while_loop(
                cond, body, (jnp.int32(0), cache, tok, pos, done, key, out0)
            )
            return st[6], st[1]

        # ---------------- slot programs (continuous batching, scheduler.py)
        #
        # The slot cache is one ordinary cache pytree of batch = n_slots;
        # each request owns one axis-1 row of every leaf for its lifetime
        # (``registry.write_cache_slot`` contract).  All programs donate the
        # slot cache, so the scheduler's device state is updated in place
        # across admissions and segments instead of being copied.
        #
        # Every program below is built ONCE by a builder parametrized over
        # the cache layout (dense slot rows vs paged block pool) — the
        # layout-specific lines are the cache plumbing (gather/scatter vs
        # block table), everything else (sampling, tok/pos/done bookkeeping,
        # segment loops, speculative accept) is shared, so the two layouts
        # cannot drift apart and the speculative programs don't fork the
        # copy-paste a third time.

        def _mk_prefill_slot(paged):
            name = "prefill_slot_paged" if paged else "prefill_slot"

            def prefill_slot(params, cache, tok, pos, done, prompt, slot,
                             *rest):
                """Prefill ONE request (1, P) and install it into ``slot``.

                Runs at the request's own prompt length — ragged workloads
                never pad one prompt against another (one trace per distinct
                P; slot and max_new are traced scalars, so neither
                retraces).  Dense: the batch-1 cache is written into the
                slot row (``write_cache_slot``).  Paged (extra ``bt_row``
                arg before ``key``): a chunk-resume forward from position 0
                writes straight into the physical blocks the row maps.  The
                whole slot state is donated;
                the host only reads the first sampled token back.
                """
                self.trace_counts[name] += 1
                key = rest[-1]
                p_len = prompt.shape[1]
                real = jnp.ones(prompt.shape, bool)
                if paged:
                    # straight into the row's mapped blocks: a chunk-resume
                    # forward from position 0 over the pool
                    logits, cache, held = forward_counted(
                        params, real, tokens=prompt, cache=cache,
                        cache_pos=jnp.zeros((1,), jnp.int32),
                        block_table=rest[0][None, :])
                else:
                    small = arch.init_cache(1, sc.max_len, plan, cfg=self.cfg)
                    logits, small, held = forward_counted(
                        params, real, tokens=prompt, cache=small)
                    from repro.models.registry import write_cache_slot

                    cache = write_cache_slot(cache, small, slot)
                first = sample(logits[:, -1], key)[0]
                return (
                    cache,
                    tok.at[slot].set(first),
                    pos.at[slot].set(p_len),
                    done.at[slot].set(False),
                    held,
                    first,
                )

            return prefill_slot

        def _mk_prefill_slots(paged):
            name = "prefill_slots_paged" if paged else "prefill_slots"

            def prefill_slots(params, cache, tok, pos, done, prompts, slots,
                              starts, last_local, *rest):
                """Prefill ONE chunk for up to B requests into B slot rows
                in one launch (the batched/bucketed admission path).

                ``prompts`` is (B, Cb) with B fixed at the scheduler's slot
                count and Cb drawn from a small geometric bucket set, so
                total prefill traces are bounded by the bucket set instead
                of by distinct prompt lengths.  Per-row vectors: ``slots``
                (target slot; an out-of-range id marks a masked dummy row —
                its gather clips and every one of its writes drops),
                ``starts`` (resume offset), ``last_local`` (index of the
                row's last REAL token inside the chunk — bucket padding sits
                after it and is causally invisible).  Dense: the B slot rows
                are gathered, one chunk-resume forward runs over them, the
                updated rows scatter back (``registry.gather_cache_slots`` /
                ``write_cache_slots``).  Paged (extra ``bt_rows`` before
                ``key``): the chunk scatters straight into each row's mapped
                physical blocks at its block-table offsets — dummy rows
                carry DISTINCT out-of-range physical ids so their writes
                drop without aliasing a live block.  First tokens are
                sampled from each row's last-real-token logits and only
                consumed by the host for final chunks.
                """
                self.trace_counts[name] += 1
                key = rest[-1]
                # real tokens: a real row's chunk up to its last real token
                real = ((slots < tok.shape[0])[:, None]
                        & (jnp.arange(prompts.shape[1])[None, :]
                           <= last_local[:, None]))
                if paged:
                    bt_rows = rest[0]
                    logits, cache, held = forward_counted(
                        params, real, tokens=prompts,
                        cache=cache, cache_pos=starts, block_table=bt_rows,
                    )
                else:
                    from repro.models.registry import (
                        gather_cache_slots, write_cache_slots,
                    )

                    small = gather_cache_slots(cache, slots)
                    logits, small, held = forward_counted(
                        params, real, tokens=prompts,
                        cache=small, cache_pos=starts,
                    )
                    cache = write_cache_slots(cache, small, slots)
                last = jnp.take_along_axis(
                    logits, last_local[:, None, None], axis=1
                )[:, 0]  # (B, V)
                firsts = sample(last, key)
                return (
                    cache,
                    tok.at[slots].set(firsts, mode="drop"),
                    pos.at[slots].set(starts + last_local + 1, mode="drop"),
                    done.at[slots].set(False, mode="drop"),
                    held,
                    firsts,
                )

            return prefill_slots

        def slot_step(params, cache, tok, pos, done, key, active, limit,
                      block_table=None):
            """One masked decode step over all slots (shared by both segment
            flavours — the scan/while bit-identical contract depends on it).

            Slots that are inactive or done still flow through the
            fixed-shape forward but are masked: their pos freezes (no
            cache-row growth), their carried token is held, and their
            emitted entry is −1 so the host scheduler drops it.  Live slots
            follow the exact PR 1 step semantics (eos-check then pin), so
            greedy outputs are bit-identical to ``generate`` on a uniform
            workload.  With ``block_table`` the cache is a paged pool;
            masked slots' frozen-pos writes land in their own mapped block
            (done-but-active) or the scratch block (retired/empty rows are
            zeroed by the scheduler), so no live block is ever clobbered.
            """
            key, sub = jax.random.split(key)
            fkw = {} if block_table is None else {"block_table": block_table}
            live = active & ~done
            logits, cache, held = forward_counted(
                params, live[:, None], tokens=tok[:, None],
                cache=cache, cache_pos=pos, live=live, **fkw,
            )
            counts = {} if held is None else {"held": held}
            if block_table is not None:
                counts.update(kv_counts(pos, 1, live))
            nxt = sample(logits[:, 0], sub)
            if sc.eos_token >= 0:
                done = done | (live & (nxt == sc.eos_token))
            emitted = jnp.where(live, nxt, -1)
            tok = jnp.where(live, nxt, tok)
            pos = jnp.where(live, pos + 1, pos)
            done = done | (active & (pos >= limit))
            return cache, tok, pos, done, key, emitted, counts

        def spec_step(params, draft_params, cache, tok, pos, done, key,
                      active, limit, block_table=None):
            """One speculative draft-and-verify step over all slots.

            Draft: ``spec.k`` sequential decode steps of the drafter.  The
            drafter runs FROM THE VERIFIER'S KV — the self-sparse drafter
            (same topology) threads the slot cache itself, writing its
            in-flight k/v at ``pos .. pos+i``; the truncated drafter reads a
            local slice of the first ``n_draft`` layers (identical prefix
            weights ⇒ identical prefix KV, so the slice IS its correct
            cache) that is dropped after drafting.  Neither needs a prefill
            or a rollback of its own: every position a drafter touches is
            overwritten by the verify window below.

            Verify: ONE ``decode_chunk`` forward of the served model over
            the window ``[tok, d_1 .. d_k]`` at ``pos .. pos+k`` — each row
            bitwise the computation sequential decode would do — then
            greedy longest-prefix acceptance (``sampling.spec_accept``:
            eos and token-budget edges emulate ``slot_step`` exactly).

            Rollback: pure cursor truncation — ``pos`` advances only over
            the accepted prefix; rejected-tail KV stays in the cache (dense
            rows or mapped blocks) but every read masks positions beyond
            the querying token, and the next window overwrites it.  Masked
            slots flow through shape-stably like ``slot_step``: pos frozen,
            token held, emissions −1 (their window writes land at their
            frozen pos / scratch block and are never read).
            """
            k_spec = self.spec.k
            n_draft = self.draft_cfg.n_layers
            fkw = {} if block_table is None else {"block_table": block_table}
            live = active & ~done
            key, _sub = jax.random.split(key)  # keep slot_step's key cadence

            full_depth = n_draft == self.cfg.n_layers
            d_cache = cache if full_depth else jax.tree_util.tree_map(
                lambda a: a[:n_draft], cache
            )
            cur = tok
            window = [tok]
            for i in range(k_spec):
                dlogits, d_cache = arch.forward(
                    draft_params, plan, cfg=self.draft_cfg,
                    tokens=cur[:, None], cache=d_cache, cache_pos=pos + i,
                    live=live, **fkw,
                )
                cur = jnp.argmax(dlogits[:, 0], axis=-1).astype(jnp.int32)
                window.append(cur)
            window = jnp.stack(window, axis=1)  # (B, K+1)
            if full_depth:
                cache = d_cache  # drafter k/v lands in-place; verify overwrites

            logits, cache = arch.forward(
                params, plan, cfg=self.cfg, tokens=window, cache=cache,
                cache_pos=pos, decode_chunk=True, live=live, **fkw,
            )
            # no held-expert family runs speculation
            counts = ({} if block_table is None
                      else kv_counts(pos, k_spec + 1, live))
            verify = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, K+1)
            emitted, n_emit, last = spec_accept(
                window, verify, live, pos, limit, sc.eos_token
            )
            tok = jnp.where(live, last, tok)
            pos = pos + n_emit  # n_emit == 0 where not live → pos frozen
            stop = pos >= limit
            if sc.eos_token >= 0:
                stop = stop | (last == sc.eos_token)
            done = done | (live & stop)
            # emitted (B, K+1)
            return cache, tok, pos, done, key, emitted, counts

        def counts0(paged):
            """The zero of a step's counts: held-expert rows where counted,
            KV blocks on the paged layout."""
            counts = {"held": jnp.int32(0)} if counting else {}
            if paged:
                counts.update(kv_read=jnp.int32(0), kv_span=jnp.int32(0))
            return counts

        def add_counts(total, n):
            return jax.tree_util.tree_map(jnp.add, total, n)

        def segment_scan_impl(n_steps, step, cache, tok, pos, done, key,
                              paged):
            """Shared scan-segment body (dense/paged × plain/speculative):
            one place to change segment semantics, so the four programs
            cannot drift apart.  ``step`` emits (B,) tokens per step on the
            plain path and (B, K+1) on the speculative one — the stacked
            output comes back (n_slots, n_steps[, K+1]) — and the steps'
            counts add up (``counts0``)."""

            def body(carry, _):
                *state, total = carry
                cache, tok, pos, done, key, emitted, n = step(*state)
                return (cache, tok, pos, done, key, add_counts(total, n)), emitted

            (cache, tok, pos, done, key, counts), toks = jax.lax.scan(
                body, (cache, tok, pos, done, key, counts0(paged)),
                length=n_steps
            )
            return jnp.moveaxis(toks, 0, 1), cache, tok, pos, done, key, counts

        def segment_while_impl(n_steps, step, cache, tok, pos, done, key,
                               active, stop_on_free, emit_tail, paged):
            """Shared while-segment body (early exit).

            Same per-step math as the scan flavour (identical ``step``, so
            greedy outputs are bit-identical), but the loop stops as soon
            as (a) every active slot is done, or (b) any slot newly finished
            while ``stop_on_free`` is set (the scheduler passes
            queue-non-empty) — so a freed slot returns to the host for
            refilling immediately instead of riding out the rest of a fixed
            segment masked.  ``n_steps`` is the cap / output width; untaken
            columns come back as −1.
            """
            n_slots = tok.shape[0]
            out0 = jnp.full((n_slots, n_steps) + emit_tail, -1, jnp.int32)

            def cond(st):
                i, _cache, _tok, _pos, done, _key, _out, _counts = st
                any_running = jnp.any(active & ~done)
                freed = jnp.any(active & done)
                return (i < n_steps) & any_running & ~(stop_on_free & freed)

            def loop_body(st):
                i, cache, tok, pos, done, key, out, total = st
                cache, tok, pos, done, key, emitted, n = step(
                    cache, tok, pos, done, key
                )
                upd = emitted.reshape((n_slots, 1) + emit_tail)
                out = jax.lax.dynamic_update_slice(
                    out, upd, (0, i) + (0,) * len(emit_tail)
                )
                return (i + 1, cache, tok, pos, done, key, out,
                        add_counts(total, n))

            st = jax.lax.while_loop(
                cond, loop_body,
                (jnp.int32(0), cache, tok, pos, done, key, out0,
                 counts0(paged)),
            )
            _, cache, tok, pos, done, key, out, counts = st
            return out, cache, tok, pos, done, key, counts

        def _mk_segment(flavor, paged, spec):
            """Build one compiled segment program.

            Plain: ``n_steps`` masked decode steps over every slot, carry
            (cache, tok, pos, done, key) on device; ``active`` (slot holds a
            live request) and ``limit`` (last write position = prompt_len +
            max_new − 1) are host-policy inputs, the while flavour adds
            ``stop_on_free`` and the paged layout appends ``block_table``.
            Speculative: same signature with ``draft_params`` after
            ``params``; each step is a draft-and-verify round emitting
            1..K+1 tokens per live slot.
            """
            scan = flavor == "scan"
            name = (("slot_spec_segment" if spec else "slot_segment")
                    + ("" if scan else "_while") + ("_paged" if paged else ""))

            def segment(n_steps, params, *args):
                self.trace_counts[name] += 1
                if spec:
                    draft_params, args = args[0], args[1:]
                cache, tok, pos, done, key, active, limit, *rest = args
                block_table = rest[-1] if paged else None
                if spec:
                    def step(c, t, p, d, k2):
                        return spec_step(params, draft_params, c, t, p, d,
                                         k2, active, limit, block_table)

                    emit_tail = (self.spec.k + 1,)
                else:
                    def step(c, t, p, d, k2):
                        return slot_step(params, c, t, p, d, k2, active,
                                         limit, block_table)

                    emit_tail = ()
                if scan:
                    return segment_scan_impl(n_steps, step, cache, tok, pos,
                                             done, key, paged)
                stop_on_free = rest[0]
                return segment_while_impl(n_steps, step, cache, tok, pos,
                                          done, key, active, stop_on_free,
                                          emit_tail, paged)

            return segment, name

        # -- build + (optionally) jit every slot program for both layouts.
        # Paged programs run the same admit/segment/retire machine over a
        # block pool instead of per-slot max_len rows; the block table is
        # host policy like ``active``/``limit`` — uploaded per call, never
        # part of the carry.  Speculative segments exist only when a spec
        # config survived drafter resolution.
        slot_progs: dict[str, tuple[Any, dict]] = {}
        for paged in (False, True):
            sfx = "_paged" if paged else ""
            # donate the whole device slot state (cache + tok/pos/done) so
            # admissions and segments update it in place across calls
            slot_progs["prefill_slot" + sfx] = (
                _mk_prefill_slot(paged), dict(donate_argnums=(1, 2, 3, 4))
            )
            slot_progs["prefill_slots" + sfx] = (
                _mk_prefill_slots(paged), dict(donate_argnums=(1, 2, 3, 4))
            )
            for flavor in ("scan", "while"):
                fn, nm = _mk_segment(flavor, paged, spec=False)
                slot_progs[nm] = (
                    fn, dict(static_argnums=(0,), donate_argnums=(2, 3, 4, 5))
                )
                if self.spec is not None:
                    fn, nm = _mk_segment(flavor, paged, spec=True)
                    # draft_params shifts the donated slot state right by one
                    slot_progs[nm] = (
                        fn,
                        dict(static_argnums=(0,), donate_argnums=(3, 4, 5, 6)),
                    )

        # each slot program traces under a named scope of its own name, so
        # its device ops say which flavour ran (the jitted function keeps
        # its name: the XLA modules stay ``jit_segment`` / ``jit_prefill_*``)
        slot_progs = {nm: (_scoped(nm, fn), jkw)
                      for nm, (fn, jkw) in slot_progs.items()}
        if sc.jit:
            self._prefill = jax.jit(prefill)
            self._decode = jax.jit(decode)
            # n_steps static (scan length / trip bound); cache (arg 2) donated
            # so the loop aliases the prefill buffers instead of copying them.
            loop_fn = decode_loop if sc.loop != "while" else decode_loop_while
            self._decode_loop = jax.jit(
                loop_fn, static_argnums=(0,), donate_argnums=(2,)
            )
            for nm, (fn, jkw) in slot_progs.items():
                setattr(self, "_" + nm, jax.jit(fn, **jkw))
        else:
            self._prefill, self._decode = prefill, decode
            self._decode_loop = (
                decode_loop if sc.loop != "while" else decode_loop_while
            )
            for nm, (fn, _) in slot_progs.items():
                setattr(self, "_" + nm, fn)

    # ------------------------------------------------------------- public

    @property
    def counts_moe_rows(self) -> bool:
        """Whether the slot programs return held-expert counts."""
        return self.cfg.n_experts > 0 and self.cfg.moe_router == "sigmoid_bias"

    def moe_rows_launched(self, n_tokens: int) -> int:
        """Rows the expert layers launch for a forward over ``n_tokens``
        tokens, padding included: tokens × experts per token × MoE layers
        for a held-expert model.  A dense transformer's FFN counts as an
        expert layer that holds its one expert: a row per token and layer.
        0 for the models whose layers route otherwise."""
        if self.counts_moe_rows:
            from repro.models.moe import held_rows_launched

            return held_rows_launched(self.cfg, n_tokens)
        if self.cfg.family == "dense":
            return self.cfg.n_layers * n_tokens
        return 0

    def init_slot_cache(self, n_slots: int):
        """Fresh slot cache (batch = n_slots, length = max_len) for the
        continuous-batching scheduler.  Verifies the per-slot write contract
        once (cheap, eval_shape only) before allocating."""
        from repro.models.registry import check_slot_cache_contract

        if "slot" not in self._checked_contracts:
            check_slot_cache_contract(self.arch, plan=self.plan, cfg=self.cfg)
            self._checked_contracts.add("slot")
        return self.arch.init_cache(n_slots, self.sc.max_len, self.plan,
                                    cfg=self.cfg)

    def check_chunked_prefill_contract(self) -> None:
        """Verify the multi-slot scatter + chunk-resume contract once per
        engine (cheap, eval_shape only).  Raises NotImplementedError with
        the family's ``chunked_prefill_skip_reason`` when unsupported —
        the scheduler catches it and falls back to per-request admission."""
        from repro.models.registry import check_slots_cache_contract

        if "slots" not in self._checked_contracts:
            check_slots_cache_contract(self.arch, plan=self.plan, cfg=self.cfg)
            self._checked_contracts.add("slots")

    @property
    def max_blocks_per_slot(self) -> int:
        """Logical blocks a slot can address = max_len / block_len (the
        gathered virtual cache is exactly max_len long — bit-identicality)."""
        return self.sc.max_len // self.sc.block_len

    def init_paged_cache(self, n_blocks: int, n_slots: int = 1):
        """Fresh paged KV pool with ``n_blocks`` allocatable blocks plus
        ``n_slots`` per-slot scratch blocks (physical ids 0..n_slots−1) that
        slot s's unmapped table entries point at — distinct scratch targets
        are what make the decode write a ``unique_indices`` scatter.
        Verifies the paged contract once (cheap, eval_shape only)."""
        from repro.models.registry import check_paged_cache_contract

        if "paged" not in self._checked_contracts:
            check_paged_cache_contract(self.arch, plan=self.plan, cfg=self.cfg)
            self._checked_contracts.add("paged")
        return self.arch.init_paged_cache(
            n_slots + n_blocks, self.sc.block_len, self.plan, cfg=self.cfg
        )

    def generate(
        self, prompts: jax.Array, n_new: int, key: jax.Array | None = None
    ) -> jax.Array:
        """prompts (B, S_prompt) int32 → (B, n_new) generated tokens."""
        sc = self.sc
        b, s_prompt = prompts.shape
        assert s_prompt + n_new <= sc.max_len, "exceeds cache"
        key = key if key is not None else jax.random.PRNGKey(0)
        if sc.loop == "python":
            return self._generate_python(prompts, n_new, key)
        tok, cache, pos, done = self._prefill(self.params, prompts, key)
        self.call_counts["prefill"] += 1
        if n_new == 1:
            return tok[:, None]
        toks, _ = self._decode_loop(
            n_new - 1, self.params, cache, tok, pos, done, key
        )
        self.call_counts["decode_loop"] += 1
        return jnp.concatenate([tok[:, None], toks], axis=1)

    # ------------------------------------------------- legacy python loop

    def _generate_python(
        self, prompts: jax.Array, n_new: int, key: jax.Array
    ) -> jax.Array:
        """Seed-identical host loop: one device round-trip per token."""
        sc = self.sc
        tok, cache, pos, done = self._prefill(self.params, prompts, key)
        self.call_counts["prefill"] += 1
        out = [tok]
        for _ in range(n_new - 1):
            key, sub = jax.random.split(key)
            logits, cache = self._decode(self.params, cache, tok[:, None], pos)
            self.call_counts["decode"] += 1
            tok = sample_token(logits, sub, sc.temperature, sc.top_k, sc.top_p)
            if sc.eos_token >= 0:
                done = done | (tok == sc.eos_token)
                tok = jnp.where(done, sc.eos_token, tok)
            out.append(tok)
            pos = pos + 1
        return jnp.stack(out, axis=1)
