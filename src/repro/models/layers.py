"""Shared model layers: norms, RoPE / M-RoPE, GQA attention (chunked-flash
prefill/train + cached decode), latent attention (MLA) over a paged latent
pool, FFNs.

Conventions:
  * params are nested dicts of arrays; init fns mirror apply fns.
  * activations flow in ``cfg.compute_dtype`` (bf16); norms/softmax in fp32.
  * attention tensors are laid out (B, S, H, Dh).
  * every apply fn is pure and jit/scan-safe.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels.paged_attention import (
    decode_walk,
    dense_decode_attention,
    paged_decode_attention,
)

Params = dict[str, Any]


# ---------------------------------------------------------------- init utils


def _normal(key, shape, dtype, scale):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def dense_init(key, d_in: int, d_out: int, dtype, bias: bool = False) -> Params:
    p = {"kernel": _normal(key, (d_in, d_out), dtype, d_in**-0.5)}
    if bias:
        p["bias"] = jnp.zeros((d_out,), dtype)
    return p


def dense_apply(p: Params, x: jax.Array) -> jax.Array:
    if "qvalues" in p:  # int8 block-sparse serving weights (ISSUE 10):
        # the projection dict was rewritten by ``quantize_serve_params`` —
        # contract only the kept blocks against their per-block scales
        from repro.core.sonic_layers import serve_quant_apply

        y = serve_quant_apply(p, x)
    else:
        y = x @ p["kernel"].astype(x.dtype)
    if "bias" in p:
        y = y + p["bias"].astype(x.dtype)
    return y


def norm_init(cfg: ModelConfig, d: int | None = None) -> Params:
    d = d or cfg.d_model
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["norm_bias"] = jnp.zeros((d,), jnp.float32)
    return p


def norm_apply(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    if "norm_bias" in p:  # layernorm
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["norm_bias"]
    else:  # rmsnorm
        ms = (xf * xf).mean(-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
    return y.astype(x.dtype)


# ----------------------------------------------------------------- RoPE


def rope_angles(cfg: ModelConfig, positions: jax.Array,
                dim: int | None = None) -> jax.Array:
    """positions (B, S) or (B, 3, S) → angles (B, S, dim/2) fp32, ``dim``
    the rotated width (``head_dim`` unless given).

    Standard RoPE for (B, S); M-RoPE (qwen2-vl) for (B, 3, S): the dh/2
    frequency slots are split into ``mrope_sections`` = (t, h, w) groups, each
    driven by its own position row.
    """
    half = (dim or cfg.head_dim) // 2
    inv_freq = cfg.rope_theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 2:  # (B, S)
        return positions[..., None].astype(jnp.float32) * inv_freq
    # M-RoPE: (B, 3, S)
    st, sh, sw = cfg.mrope_sections
    assert st + sh + sw == half, (cfg.mrope_sections, half)
    section = np.concatenate([np.full(st, 0), np.full(sh, 1), np.full(sw, 2)])
    pos_per_slot = jnp.take(positions, jnp.asarray(section), axis=1)  # (B, half, S)
    return pos_per_slot.transpose(0, 2, 1).astype(jnp.float32) * inv_freq


def apply_rope(x: jax.Array, angles: jax.Array) -> jax.Array:
    """x (B, S, H, Dh), angles (B, S, Dh/2) → rotated x (rotate-half conv.)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ------------------------------------------------------------- attention


def kv_repeat_factor(cfg: ModelConfig, tp: int) -> int:
    """Replication of KV heads so the head axis shards over ``tp`` devices
    (MaxText-style kv replication).  1 when no replication is needed."""
    kh = cfg.n_kv_heads
    r = 1
    while (kh * r) % tp and (kh * r) < cfg.n_heads:
        r += 1
    return r if (kh * r) % tp == 0 or (kh * r) == cfg.n_heads else 1


def attention_init(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 4)
    d, h, kh, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "wq": dense_init(ks[0], d, h * dh, dt, cfg.use_bias),
        "wk": dense_init(ks[1], d, kh * dh, dt, cfg.use_bias),
        "wv": dense_init(ks[2], d, kh * dh, dt, cfg.use_bias),
        "wo": dense_init(ks[3], h * dh, d, dt, cfg.use_bias),
    }


def _gqa_scores(q, k, scale):
    """q (B,Sq,KH,G,Dh), k (B,Skv,KH,Dh) → scores (B,KH,G,Sq,Skv) fp32."""
    return jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32) * scale


def flash_attention(
    q: jax.Array,  # (B, Sq, H, Dh)
    k: jax.Array,  # (B, Skv, KH, Dh)
    v: jax.Array,  # (B, Skv, KH, Dh)
    q_positions: jax.Array,  # (B, Sq) int32
    kv_positions: jax.Array,  # (B, Skv) int32
    causal: bool = True,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    scale: float | None = None,
) -> jax.Array:
    """Memory-efficient (online-softmax) attention in pure jnp.

    Scans over KV chunks per Q chunk so the materialized score block is
    (B, KH, G, q_chunk, kv_chunk) — the jnp analogue of flash attention, which
    both bounds VMEM/HBM temp and keeps the dry-run memory analysis honest.
    Masking is position-based: a kv position participates iff
    kv_pos <= q_pos (causal) and kv_pos >= 0 (padding convention: pos < 0).
    Values may be narrower than queries and keys (latent attention); the
    scores scale by ``scale``, Dh^-1/2 unless given.
    """
    b, sq, h, dh = q.shape
    _, skv, kh, _ = k.shape
    dv = v.shape[-1]
    g = h // kh
    scale = dh**-0.5 if scale is None else scale
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    nq, nkv = sq // q_chunk, skv // kv_chunk
    assert sq % q_chunk == 0 and skv % kv_chunk == 0, (sq, q_chunk, skv, kv_chunk)

    qc = q.reshape(b, nq, q_chunk, kh, g, dh)
    kc = k.reshape(b, nkv, kv_chunk, kh, dh)
    vc = v.reshape(b, nkv, kv_chunk, kh, dv)
    qp = q_positions.reshape(b, nq, q_chunk)
    kp = kv_positions.reshape(b, nkv, kv_chunk)

    def per_q_chunk(args):
        qi, qpi = args  # (B, qc, KH, G, Dh), (B, qc)

        # flash-backward memory discipline: recompute the (qc × kvc) score /
        # probability block during the backward pass instead of saving it —
        # without this, scan saves every p block and training temp memory
        # blows up ~n_blocks× (measured 10.8 GB/dev → see EXPERIMENTS §Perf).
        @functools.partial(
            jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable
        )
        def kv_step(carry, kv):
            acc, m, l = carry
            ki, vi, kpi = kv  # (B, kvc, KH, Dh), ..., (B, kvc)
            s = _gqa_scores(qi, ki, scale)  # (B,KH,G,qc,kvc) fp32
            mask = kpi[:, None, None, None, :] >= 0
            if causal:
                mask &= qpi[:, None, None, :, None] >= kpi[:, None, None, None, :]
            s = jnp.where(mask, s, -1e30)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(vi.dtype), vi)
            acc_new = acc * corr[..., None].astype(acc.dtype) + pv
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((b, kh, g, q_chunk, dv), v.dtype)
        m0 = jnp.full((b, kh, g, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, kh, g, q_chunk), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_step,
            (acc0, m0, l0),
            (
                jnp.moveaxis(kc, 1, 0),
                jnp.moveaxis(vc, 1, 0),
                jnp.moveaxis(kp, 1, 0),
            ),
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None].astype(acc.dtype)
        return out  # (B, KH, G, qc, Dv)

    outs = jax.lax.map(
        per_q_chunk, (jnp.moveaxis(qc, 1, 0), jnp.moveaxis(qp, 1, 0))
    )  # (nq, B, KH, G, qc, Dv)
    out = jnp.moveaxis(outs, 0, 1)  # (B, nq, KH, G, qc, Dv)
    out = out.transpose(0, 1, 4, 2, 3, 5).reshape(b, sq, h, dv)
    return out


def decode_attention(
    q: jax.Array,  # (B, C, H, Dh) — C decode-style queries per slot
    k_cache: jax.Array,  # (B, S_max, KH, Dh)
    v_cache: jax.Array,  # (B, S_max, KH, Dh)
    pos: jax.Array,  # (B,) position of the FIRST query token
    scale: float | None = None,  # Dh^-1/2 unless given
) -> jax.Array:
    """Decode-style attention over the whole gathered cache: query i (at
    absolute position ``pos + i``) attends cache positions ``<= pos + i``;
    everything beyond is masked.  C == 1 is the classic single-token decode
    step; C > 1 is the speculative-verify window.  The paths that gather
    their cache run it: int8 KV (after dequantizing) and latent attention.
    An unquantized GQA cache is read in place, each slot up to its length
    (``kernels.paged_attention``)."""
    b, c, h, dh = q.shape
    kh, dv = k_cache.shape[2], v_cache.shape[-1]
    g = h // kh
    qg = q.reshape(b, c, kh, g, dh)
    s = _gqa_scores(qg, k_cache, dh**-0.5 if scale is None else scale)
    # s: (B, KH, G, C, S_max) fp32
    idx = jnp.arange(k_cache.shape[1])
    qpos = pos[:, None] + jnp.arange(c, dtype=pos.dtype)[None, :]  # (B, C)
    mask = idx[None, None, :] <= qpos[:, :, None]  # (B, C, S_max)
    s = jnp.where(mask[:, None, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(v_cache.dtype), v_cache)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, c, h, dv)


def _dus_batch(cache: jax.Array, new: jax.Array, pos: jax.Array) -> jax.Array:
    """Per-batch dynamic_update_slice at (pos, 0, ...)."""

    def upd(c, n, p):
        idx = (p,) + (0,) * (c.ndim - 1)
        return jax.lax.dynamic_update_slice(c, n.astype(c.dtype), idx)

    return jax.vmap(upd)(cache, new, pos)


def update_kv_cache(
    k_cache: jax.Array,  # (B, S_max, KH, Dh)
    v_cache: jax.Array,
    k_new: jax.Array,  # (B, S_new, KH, Dh)
    v_new: jax.Array,
    pos: jax.Array,  # (B,) write offsets
) -> tuple[jax.Array, jax.Array]:
    return _dus_batch(k_cache, k_new, pos), _dus_batch(v_cache, v_new, pos)


# ---------------- paged KV cache (block pool + block table, serving) --------
#
# The paged layout stores KV in a fixed pool of ``(n_blocks, block_len, KH,
# Dh)`` physical blocks shared by every slot; a ``(n_slots, max_blocks)``
# int32 block table maps each slot's logical block j to a physical block id.
# Physical blocks 0..n_slots−1 are per-slot SCRATCH blocks: slot s's
# unmapped table entries point at block s, so masked/retired slots keep
# flowing through the fixed-shape decode step without touching any live
# request's blocks — and, because scratch ids are distinct per slot and the
# allocator never maps one block to two slots, every decode-step write lands
# at a unique (block, offset) pair.  That lets the scatter below carry
# ``unique_indices=True``, which XLA lowers markedly faster than a
# collision-safe scatter (and faster than the dense layout's per-row
# dynamic_update_slice).  Decode-style attention over an unquantized pool
# reads each slot's own blocks in place, up to its length
# (``kernels.paged_attention``); the dense layout's rows run the same
# computation as blocks of an identity table, so the greedy outputs of the
# two layouts are bit-identical.  The gather below rebuilds the per-slot
# virtual cache ``(n_slots, max_blocks·block_len, KH, Dh)`` for the paths
# that still need it — prefill, int8 KV and latent attention; positions
# past ``pos`` read scratch/stale values but are masked to exact zeros,
# exactly as the dense layout's stale rows are.
#
# Every layer's blocks live in ONE stacked pool ``(n_layers, n_blocks, …)``
# that the layer scan carries (``transformer.trunk_apply``); a layer writes
# and reads its own blocks in place through a ``LayerPool`` — the stacked
# pool plus that layer's index — so no step ever slices, updates or copies a
# whole layer of the pool.


class LayerPool(NamedTuple):
    """Layer ``layer`` of a stacked block pool ``pool`` (n_layers, n_blocks,
    block_len, …), addressed in place by (layer, block, offset)."""

    pool: jax.Array
    layer: jax.Array  # () int32


def paged_cache_gather(pool: LayerPool, block_table: jax.Array) -> jax.Array:
    """Layer ``pool.layer``'s blocks, block_table (B, MB) int32 → virtual
    per-slot cache (B, MB·block_len, KH, Dh).

    The layer and block axes merge (a free reshape), so the gather reads
    only the mapped blocks.  Ids clamp into the layer BEFORE the layer
    offset is added: the dummy rows of a fixed-width batched prefill carry
    out-of-range block ids, and clamping hands them finite (masked,
    dropped) values of their own layer instead of NaN fill values or
    another layer's blocks."""
    stack, layer = pool
    n_layers, n_blocks = stack.shape[:2]
    flat = stack.reshape(n_layers * n_blocks, *stack.shape[2:])
    ids = jnp.clip(block_table, 0, n_blocks - 1) + layer * n_blocks
    g = jnp.take(flat, ids, axis=0, mode="clip")  # (B, MB, bl, …)
    b, mb, bl = g.shape[:3]
    return g.reshape(b, mb * bl, *g.shape[3:])


def paged_cache_write(
    pool: LayerPool,  # stacked (n_layers, n_blocks, block_len, KH, Dh)
    block_table: jax.Array,  # (B, MB) int32
    new: jax.Array,  # (B, 1, KH, Dh) — one decode token per slot
    pos: jax.Array,  # (B,) logical write position per slot
) -> LayerPool:
    """Scatter one decode token per slot into its mapped physical block of
    layer ``pool.layer``.

    Slots whose mapping is unset write into their own scratch block (table
    entry = the slot id, per the layout contract above), which is what makes
    ``unique_indices`` sound: no two slots ever write the same (block,
    offset) pair."""
    stack, layer = pool
    bl = stack.shape[2]
    phys = jnp.take_along_axis(block_table, (pos // bl)[:, None], axis=1)[:, 0]
    stack = stack.at[layer, phys, pos % bl].set(
        new[:, 0].astype(stack.dtype), unique_indices=True
    )
    return LayerPool(stack, layer)


def paged_cache_write_chunk(
    pool: LayerPool,  # stacked (n_layers, n_blocks, block_len, KH, Dh)
    block_table: jax.Array,  # (B, MB) int32
    new: jax.Array,  # (B, C, KH, Dh) — one prefill chunk per slot
    pos0: jax.Array,  # (B,) logical start position of the chunk per slot
) -> LayerPool:
    """Scatter a whole prefill chunk per slot at its block-table offsets in
    layer ``pool.layer``.

    The chunk's logical positions ``pos0[b] .. pos0[b]+C-1`` may straddle
    block boundaries: each token resolves its own (physical block, in-block
    offset) pair through the table.  Uniqueness holds for the same reasons
    as the decode write — rows map disjoint physical blocks (allocator
    contract) and within a row every logical position is distinct — BUT
    only if every table entry the chunk touches is distinct per logical
    block: the serving layer therefore passes table rows whose entries
    beyond the row's mapped blocks (bucket-padding spill) and whose masked
    dummy rows hold DISTINCT out-of-range physical ids, so those writes
    drop (``mode="drop"``) without ever aliasing an in-bounds update or
    repeating a (block, offset) pair.  The layer and block axes stay
    separate here: bounds hold per axis, so an out-of-range id drops
    instead of landing in the next layer's blocks."""
    stack, layer = pool
    bl = stack.shape[2]
    c = new.shape[1]
    logical = pos0[:, None] + jnp.arange(c, dtype=pos0.dtype)  # (B, C)
    phys = jnp.take_along_axis(block_table, logical // bl, axis=1)  # (B, C)
    stack = stack.at[layer, phys, logical % bl].set(
        new.astype(stack.dtype), mode="drop", unique_indices=True
    )
    return LayerPool(stack, layer)


# -------- int8 KV cache (SONIC C2 applied to the cache — §Perf A2/C) --------


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(…, Dh) bf16 → (int8 values, (…,) fp32 per-position-per-head scale).

    The same insight as weight clustering (C2): bound the entropy the
    datapath carries per element and move fewer bits.  Per-position scales
    keep it exact to ~0.4% without any rescaling of old entries."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1) / 127.0 + 1e-8
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def attention_apply(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,  # (B, S, D)
    positions: jax.Array,  # (B, S) or (B, 3, S) for mrope
    *,
    plan=None,  # MeshPlan | None
    cache: tuple[jax.Array, jax.Array] | None = None,
    cache_scales: tuple[jax.Array, jax.Array] | None = None,  # int8 cache mode
    cache_pos: jax.Array | None = None,  # (B,)
    block_table: jax.Array | None = None,  # (B, MB) int32 — paged cache mode
    causal: bool = True,
    decode_chunk: bool = False,  # speculative-verify window (serving)
    live: jax.Array | None = None,  # (B,) bool: rows whose output is used
) -> tuple[jax.Array, tuple | None]:
    """Full attention block (no norm/residual).  Returns (out, new_cache).

    Modes:
      * cache is None                    → train/encoder forward (no cache out).
      * cache given, S > 1, no cache_pos → prefill (writes cache at pos 0..S).
      * cache given, S > 1, cache_pos    → chunk-resume prefill: the chunk's
        K/V is written at per-row offsets ``cache_pos`` and the queries
        attend over the UPDATED cache (prefix from earlier chunks + this
        chunk) with absolute-position causal masking.  On an
        order-stable backend this is bitwise-identical to prefilling the
        whole prompt at once (asserted in tests/test_serve_prefill.py).
      * cache given, S > 1, cache_pos, decode_chunk → speculative-verify
        window: same cache writes as chunk-resume, but attention runs
        decode-style (one row per window token over the updated cache)
        instead of the flash path — each row is bitwise the SAME
        computation as the sequential decode step it replaces, which is
        what makes greedy speculative outputs bit-identical to
        non-speculative decoding (docs/serving.md).
      * cache given, S == 1              → decode step at ``cache_pos``.
      * block_table given                → paged cache: ``cache`` is
        this layer's (k_pool, v_pool) ``LayerPool`` views of the stacked
        block pools; decode scatters one token into the
        mapped block (``paged_cache_write``), chunk-resume / verify-window
        scatters the whole chunk at its block-table offsets
        (``paged_cache_write_chunk``).

    Decode steps and verify windows over an unquantized cache read each
    slot's blocks in place up to its length (``kernels.paged_attention``;
    the dense layout's rows as blocks of an identity table); a slot not
    ``live`` reads one block.  Prefill and int8 KV attend a gathered
    (dequantized) cache.

    Sharding (when ``plan`` has a mesh): q/k/v are constrained to head-sharded
    (or head_dim-sharded) layout over the TP axis; KV heads are replicated
    ``plan.kv_repeat``× first so the head axis divides TP (DESIGN.md §5).
    """
    b, s, d = x.shape
    h, kh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    kv_repeat = plan.kv_repeat if plan is not None else 1
    # named scopes (attn.*, kv.*) label the device ops of each kind of work
    # in profiler traces and HLO metadata; they change no computation
    with jax.named_scope("attn.qkv"):
        q = dense_apply(p["wq"], x).reshape(b, s, h, dh)
        k = dense_apply(p["wk"], x).reshape(b, s, kh, dh)
        v = dense_apply(p["wv"], x).reshape(b, s, kh, dh)

    if cfg.pos_enc in ("rope", "mrope"):
        with jax.named_scope("attn.rope"):
            ang = rope_angles(cfg, positions)
            q = apply_rope(q, ang)
            k = apply_rope(k, ang)

    if kv_repeat > 1:  # TP-friendly KV head replication (DESIGN.md §5)
        k = jnp.repeat(k, kv_repeat, axis=2)
        v = jnp.repeat(v, kv_repeat, axis=2)

    if plan is not None and plan.mesh is not None:
        if plan.attn_shard == "heads":
            hspec = (plan.dp, None, plan.tp, None)
            q = plan.constrain(q, *hspec)
            k = plan.constrain(k, *hspec)
            v = plan.constrain(v, *hspec)
        elif plan.attn_shard == "seq" and s > 1:
            # sequence-parallel attention: queries keep their S-shard, K/V
            # replicate over tp (cheap — few KV heads).  Each shard computes
            # its query slice against full K/V: no score psums, no head
            # resharding (§Perf iteration B).
            q = plan.constrain(q, plan.dp, plan.tp, None, None)
            k = plan.constrain(k, plan.dp, None, None, None)
            v = plan.constrain(v, plan.dp, None, None, None)
        elif plan.attn_shard == "head_dim":
            hspec = (plan.dp, None, None, plan.tp)
            q = plan.constrain(q, *hspec)
            k = plan.constrain(k, *hspec)
            v = plan.constrain(v, *hspec)

    new_cache = None
    if block_table is not None:
        assert cache is not None and cache_pos is not None, (
            "paged cache needs a write offset: decode at cache_pos, or "
            "chunk-resume prefill starting at cache_pos (0 for a whole "
            "prompt)"
        )
        k_pool, v_pool = cache
        quant = cache_scales is not None
        write = paged_cache_write if s == 1 else paged_cache_write_chunk
        with jax.named_scope("kv.write"):
            if quant:
                # per-block KV scales ride the SAME block table as the
                # values: scale pools are (L, n_blocks, block_len, KH) — one
                # fp32 per cached position per head — so the write/gather
                # helpers below (which only index leading dims) work on
                # them unchanged
                ks_pool, vs_pool = cache_scales
                k_w, ks_new = quantize_kv(k)
                v_w, vs_new = quantize_kv(v)
            else:
                k_w, v_w = k, v
            k_pool = write(k_pool, block_table, k_w, cache_pos)
            v_pool = write(v_pool, block_table, v_w, cache_pos)
            if quant:
                ks_pool = write(ks_pool, block_table, ks_new, cache_pos)
                vs_pool = write(vs_pool, block_table, vs_new, cache_pos)
        if (s == 1 or decode_chunk) and not quant:
            # decode step / verify window: each slot's own blocks, read in
            # place up to its length
            with jax.named_scope("attn.core"):
                out = paged_decode_attention(
                    q, k_pool.pool, v_pool.pool, k_pool.layer, block_table,
                    cache_pos,
                    decode_walk(cache_pos, s, live, k_pool.pool.shape[2]))
        else:
            with jax.named_scope("kv.gather"):
                k_virt = paged_cache_gather(k_pool, block_table)
                v_virt = paged_cache_gather(v_pool, block_table)
                if quant:
                    k_virt = dequantize_kv(
                        k_virt, paged_cache_gather(ks_pool, block_table),
                        q.dtype)
                    v_virt = dequantize_kv(
                        v_virt, paged_cache_gather(vs_pool, block_table),
                        q.dtype)
            with jax.named_scope("attn.core"):
                if s == 1 or decode_chunk:
                    # int8 KV: one plain-softmax row per query token over
                    # the gathered, dequantized cache
                    out = decode_attention(q, k_virt, v_virt, cache_pos)
                else:  # chunk-resume prefill at block-table offsets
                    kv_pos = jnp.broadcast_to(
                        jnp.arange(k_virt.shape[1], dtype=jnp.int32),
                        (b, k_virt.shape[1]),
                    )
                    pos2d = (positions if positions.ndim == 2
                             else positions[:, 0, :])
                    out = flash_attention(q, k_virt, v_virt, pos2d, kv_pos,
                                          causal=causal)
        with jax.named_scope("attn.out"):
            out = dense_apply(p["wo"], out.reshape(b, s, h * dh))
        new_cache = ((k_pool, v_pool, ks_pool, vs_pool) if quant
                     else (k_pool, v_pool))
        return out, new_cache
    if cache is None:
        pos2d = positions if positions.ndim == 2 else positions[:, 0, :]
        with jax.named_scope("attn.core"):
            out = flash_attention(q, k, v, pos2d, pos2d, causal=causal)
    else:
        k_cache, v_cache = cache
        quant = cache_scales is not None
        # decode and chunk-resume write at the caller's per-row offsets;
        # whole-prompt prefill writes at 0
        write_pos = (cache_pos if cache_pos is not None
                     else jnp.zeros((b,), jnp.int32))
        with jax.named_scope("kv.write"):
            if quant:
                ks_cache, vs_cache = cache_scales
                kq, ks_new = quantize_kv(k)
                vq, vs_new = quantize_kv(v)
                k_cache = _dus_batch(k_cache, kq, write_pos)
                v_cache = _dus_batch(v_cache, vq, write_pos)
                ks_cache = _dus_batch(ks_cache, ks_new, write_pos)
                vs_cache = _dus_batch(vs_cache, vs_new, write_pos)
            else:
                k_cache, v_cache = update_kv_cache(k_cache, v_cache, k, v,
                                                   write_pos)
        if plan is not None and plan.mesh is not None:
            cspec = plan.cache_spec()
            k_cache = plan.constrain(k_cache, *cspec)
            v_cache = plan.constrain(v_cache, *cspec)
            if quant:
                ks_cache = plan.constrain(ks_cache, *cspec[:3])
                vs_cache = plan.constrain(vs_cache, *cspec[:3])
        with jax.named_scope("attn.core"):
            if quant and s > 1 and not decode_chunk:
                # int8-KV bit-exactness recipe (docs/serving.md):
                # EVERY prefill — whole-prompt and chunk-resume alike — attends
                # the dequantized cache it just wrote, never the exact fresh
                # k/v.  Whole-prompt prefill is then literally the write_pos=0
                # case of chunk-resume, so chunked prefill is bitwise identical
                # to whole-prompt under quant, and the decode/verify branch
                # below attends the same dequantized values — one value stream
                # for all paths.  Stale rows past the causal frontier are
                # masked to exact zeros.
                k_att = dequantize_kv(k_cache, ks_cache, q.dtype)
                v_att = dequantize_kv(v_cache, vs_cache, q.dtype)
                kv_pos = jnp.broadcast_to(
                    jnp.arange(k_cache.shape[1], dtype=jnp.int32),
                    (b, k_cache.shape[1]),
                )
                pos2d = positions if positions.ndim == 2 else positions[:, 0, :]
                out = flash_attention(q, k_att, v_att, pos2d, kv_pos,
                                      causal=causal)
            elif s == 1 or (decode_chunk and cache_pos is not None):
                # decode step / speculative-verify window, one row per query
                # token: each row recomputes exactly what the sequential
                # decode step would, so greedy spec outputs stay
                # bit-identical to non-speculative decoding
                assert cache_pos is not None
                if quant:  # over the whole dequantized cache
                    out = decode_attention(
                        q, dequantize_kv(k_cache, ks_cache, q.dtype),
                        dequantize_kv(v_cache, vs_cache, q.dtype), cache_pos)
                else:  # each row up to its length, as the paged layout does
                    out = dense_decode_attention(q, k_cache, v_cache,
                                                 cache_pos, live)
            elif cache_pos is not None:  # chunk-resume: attend over the cache
                # (prefix from earlier chunks + this chunk's freshly written
                # rows); positions past the chunk end are causally masked, so
                # stale tenant rows contribute exact zeros
                kv_pos = jnp.broadcast_to(
                    jnp.arange(k_cache.shape[1], dtype=jnp.int32),
                    (b, k_cache.shape[1]),
                )
                pos2d = positions if positions.ndim == 2 else positions[:, 0, :]
                out = flash_attention(q, k_cache, v_cache, pos2d, kv_pos,
                                      causal=causal)
            else:  # whole-prompt prefill: attend over the fresh (exact) k/v
                pos2d = positions if positions.ndim == 2 else positions[:, 0, :]
                out = flash_attention(q, k, v, pos2d, pos2d, causal=causal)
        new_cache = (
            (k_cache, v_cache, ks_cache, vs_cache) if quant else (k_cache, v_cache)
        )

    with jax.named_scope("attn.out"):
        out = dense_apply(p["wo"], out.reshape(b, s, h * dh))
    return out, new_cache


# ------------------------------------------------- latent attention (MLA)
#
# DeepSeek-V3's attention.  Per token: q = x·W_q split per head into q_nope
# and q_pe; [c | k_pe] = x·W_kva, c = RMSNorm(c) (the kv_rank-wide latent);
# per head [k_nope | v] = c·W_kvb; q_pe and k_pe (one rotary key shared by
# every head) rotate (rotate-half pairing); score = (q_nope·k_nope +
# q_pe·k_pe) / sqrt(nope + rope).  The cache holds [c | k_pe] in place of K
# and V: kv_rank + rope values a token a layer, one pool leaf
# (L, n_blocks, block_len, kv_rank + rope).
#
# Prefill and decode both attend in the ABSORBED form: W_kvb's K half folds
# into the query (q_lat = q_nope·W_uk, so q_nope·k_nope = q_lat·c) and its V
# half into the output (softmax·v = (softmax·c)·W_uv), so attention is
# multi-query attention over the latent itself — keys [c | k_pe], values c —
# and nothing of size heads × positions is expanded.  One form for every
# mode keeps a token's numerics the same whether it was prefilled or
# decoded.


def mla_init(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 4)
    d, h, r = cfg.d_model, cfg.n_heads, cfg.mla_kv_rank
    dqk = cfg.mla_nope_dim + cfg.mla_rope_dim
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "wq": dense_init(ks[0], d, h * dqk, dt),
        "wkv_a": dense_init(ks[1], d, r + cfg.mla_rope_dim, dt),
        "kv_norm": {"scale": jnp.ones((r,), jnp.float32)},
        "wkv_b": dense_init(ks[2], r, h * (cfg.mla_nope_dim + cfg.mla_v_dim), dt),
        "wo": dense_init(ks[3], h * cfg.mla_v_dim, d, dt),
    }


MLA_KV_NORM_EPS = 1e-6  # DeepSeek-V3's kv_a_layernorm keeps its default eps


def mla_apply(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,  # (B, S, D)
    positions: jax.Array,  # (B, S)
    *,
    cache: LayerPool | None = None,
    cache_pos: jax.Array | None = None,  # (B,)
    block_table: jax.Array | None = None,  # (B, MB) int32
) -> tuple[jax.Array, LayerPool | None]:
    """Latent attention block (no norm/residual).  Returns (out, pool).

    Modes: ``cache`` None → full causal forward over ``x``; otherwise
    ``cache`` is this layer's view of the latent pool and ``block_table``
    maps each row's blocks — S == 1 decodes at ``cache_pos``, S > 1 is
    chunk-resume prefill from ``cache_pos``; both scatter [c | k_pe] into
    the pool and attend over the gathered latent."""
    b, s, _ = x.shape
    h, r = cfg.n_heads, cfg.mla_kv_rank
    dn, dr, dv = cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim
    with jax.named_scope("mla.q"):
        q = dense_apply(p["wq"], x).reshape(b, s, h, dn + dr)
    with jax.named_scope("mla.kv_a"):
        kv = dense_apply(p["wkv_a"], x)  # (B, S, r + dr)
        c = norm_apply(p["kv_norm"], kv[..., :r], MLA_KV_NORM_EPS)
    with jax.named_scope("attn.rope"):
        ang = rope_angles(cfg, positions, dim=dr)
        q_pe = apply_rope(q[..., dn:], ang)
        k_pe = apply_rope(kv[:, :, None, r:], ang)[:, :, 0]
    latent = jnp.concatenate([c, k_pe], axis=-1)  # (B, S, r + dr)
    w_kvb = p["wkv_b"]["kernel"].astype(x.dtype).reshape(r, h, dn + dv)
    with jax.named_scope("mla.absorb"):
        q_lat = jnp.einsum("bshn,rhn->bshr", q[..., :dn], w_kvb[..., :dn])
        q_full = jnp.concatenate([q_lat, q_pe], axis=-1)  # (B, S, H, r + dr)

    pool = None
    if cache is None:
        keys = latent
    else:
        assert block_table is not None and cache_pos is not None, (
            "the latent cache is a paged pool: it needs a block table and "
            "a write offset")
        write = paged_cache_write if s == 1 else paged_cache_write_chunk
        with jax.named_scope("kv.write"):
            pool = write(cache, block_table, latent, cache_pos)
        with jax.named_scope("kv.gather"):
            keys = paged_cache_gather(pool, block_table)  # (B, S_max, r + dr)
    scale = (dn + dr) ** -0.5
    k = keys[:, :, None, :]  # one key and value head: multi-query
    v = keys[:, :, None, :r]
    with jax.named_scope("attn.core"):
        if cache is not None and s == 1:
            o_lat = decode_attention(q_full, k, v, cache_pos, scale=scale)
        else:
            kv_pos = jnp.broadcast_to(
                jnp.arange(k.shape[1], dtype=jnp.int32), (b, k.shape[1]))
            o_lat = flash_attention(q_full, k, v, positions, kv_pos,
                                    scale=scale)  # (B, S, H, r)
    with jax.named_scope("mla.v_up"):
        o = jnp.einsum("bshr,rhv->bshv", o_lat, w_kvb[..., dn:])
    with jax.named_scope("attn.out"):
        out = dense_apply(p["wo"], o.reshape(b, s, h * dv))
    return out, pool


# ----------------------------------------------------------------- FFN


def ffn_init(key, cfg: ModelConfig, d_ff: int | None = None) -> Params:
    d_ff = d_ff or cfg.d_ff
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    if cfg.ffn == "swiglu":
        return {
            "wi": dense_init(ks[0], cfg.d_model, d_ff, dt, cfg.use_bias),
            "wg": dense_init(ks[1], cfg.d_model, d_ff, dt, cfg.use_bias),
            "wo": dense_init(ks[2], d_ff, cfg.d_model, dt, cfg.use_bias),
        }
    return {
        "wi": dense_init(ks[0], cfg.d_model, d_ff, dt, cfg.use_bias),
        "wo": dense_init(ks[2], d_ff, cfg.d_model, dt, cfg.use_bias),
    }


def ffn_apply(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if "wg" in p:  # swiglu
        h = jax.nn.silu(dense_apply(p["wi"], x)) * dense_apply(p["wg"], x)
    else:
        h = jax.nn.gelu(dense_apply(p["wi"], x))
    return dense_apply(p["wo"], h)


# ------------------------------------------------------------- embeddings


def embed_init(key, cfg: ModelConfig) -> Params:
    dt = jnp.dtype(cfg.param_dtype)
    return {"embedding": _normal(key, (cfg.vocab_size, cfg.d_model), dt, 1.0)}


def embed_apply(p: Params, tokens: jax.Array, dtype) -> jax.Array:
    return jnp.take(p["embedding"], tokens, axis=0).astype(dtype)


def lm_head_init(key, cfg: ModelConfig) -> Params:
    dt = jnp.dtype(cfg.param_dtype)
    return {"kernel": _normal(key, (cfg.d_model, cfg.vocab_size), dt, cfg.d_model**-0.5)}


def lm_head_apply(p: Params, x: jax.Array) -> jax.Array:
    if "qvalues" in p:  # int8 block-sparse serving weights (ISSUE 10)
        from repro.core.sonic_layers import serve_quant_apply

        return serve_quant_apply(p, x)
    return x @ p["kernel"].astype(x.dtype)
