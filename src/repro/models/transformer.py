"""Transformer LM assembly — dense / MoE / encoder / VLM families.

Structure: embed → lax.scan over stacked layer params (+ optional remat) →
final norm → LM head.  One code path serves train, prefill, and decode; the
mode is picked by (cache, cache_pos) exactly as in ``attention_apply``.

Layer params are stacked on a leading (n_layers,) axis so the whole trunk is
one scan — compact HLO, fast 512-device compiles, FSDP-friendly (per-layer
all-gathers happen inside the loop → XLA can prefetch layer i+1's params
during layer i's compute).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as M
from repro.sharding.mesh import MeshPlan

Params = dict[str, Any]

MLA_DENSE_LAYOUT = (
    "latent attention is served over the paged latent pool only; a dense "
    "per-slot latent cache is not wired")
MLA_INT8_KV = (
    "int8 KV over the latent pool is not wired: the latent has no "
    "per-head K/V to scale")


# ----------------------------------------------------------------- init


def _layer_init(key, cfg: ModelConfig, dense: bool = False) -> Params:
    """One layer; ``dense`` makes a leading dense layer of a MoE model
    (an FFN of ``dense_d_ff``)."""
    ks = jax.random.split(key, 4)
    attn = L.mla_init if cfg.attention == "mla" else L.attention_init
    p: Params = {
        "ln1": L.norm_init(cfg),
        "attn": attn(ks[0], cfg),
        "ln2": L.norm_init(cfg),
    }
    if dense:
        p["ffn"] = L.ffn_init(ks[2], cfg, cfg.dense_d_ff)
    elif cfg.n_experts and cfg.moe_router == "sigmoid_bias":
        p["moe"] = M.moe_held_init(ks[1], cfg)
    elif cfg.n_experts:
        p["moe"] = M.moe_init(ks[1], cfg)
    else:
        p["ffn"] = L.ffn_init(ks[2], cfg)
    return p


def init_params(cfg: ModelConfig, key) -> Params:
    """Layer params stacked on a leading axis: ``layers``, and before them
    ``dense_layers`` where the model leads with ``first_dense_layers``."""
    kemb, klyr, khead, kdense = jax.random.split(key, 4)
    n_dense = cfg.first_dense_layers
    layer_keys = jax.random.split(klyr, cfg.n_layers - n_dense)
    p: Params = {
        "embed": L.embed_init(kemb, cfg),
        "layers": jax.vmap(lambda k: _layer_init(k, cfg))(layer_keys),
        "final_norm": L.norm_init(cfg),
    }
    if n_dense:
        p["dense_layers"] = jax.vmap(lambda k: _layer_init(k, cfg, True))(
            jax.random.split(kdense, n_dense))
    if not cfg.tie_embeddings:
        p["lm_head"] = L.lm_head_init(khead, cfg)
    return p


def abstract_params(cfg: ModelConfig) -> Params:
    """ShapeDtypeStruct pytree — no allocation (dry-run path)."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


# ----------------------------------------------------------------- blocks


def layer_apply(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,  # (B, S, D)
    positions: jax.Array,
    plan: MeshPlan,
    cache: tuple[jax.Array, jax.Array] | None = None,
    cache_pos: jax.Array | None = None,
    block_table: jax.Array | None = None,
    decode_chunk: bool = False,
    count_mask: jax.Array | None = None,
    live: jax.Array | None = None,
) -> tuple[jax.Array, tuple | None, jax.Array | None]:
    """One layer → (x, new_cache, held rows).  ``held rows`` counts the
    (token, held expert) assignments of the tokens ``count_mask`` marks in a
    held-expert layer, and is None elsewhere or without a mask.  ``live``
    (B,) marks the rows whose decode output is used (``attention_apply``)."""
    b, s, _ = x.shape
    seq = plan.tp if s > 1 else None  # SP only when the seq dim exists

    if cfg.attention == "mla":
        h, pool = L.mla_apply(
            p["attn"], cfg, L.norm_apply(p["ln1"], x), positions,
            cache=cache[0] if cache is not None else None,
            cache_pos=cache_pos, block_table=block_table)
        new_cache = None if pool is None else (pool,)
    else:
        cache_kv = cache[:2] if cache is not None else None
        cache_scales = (cache[2:] if (cache is not None and len(cache) == 4)
                        else None)
        h, new_cache = L.attention_apply(
            p["attn"],
            cfg,
            L.norm_apply(p["ln1"], x),
            positions,
            plan=plan,
            cache=cache_kv,
            cache_scales=cache_scales,
            cache_pos=cache_pos,
            block_table=block_table,
            causal=not cfg.encoder_only,
            decode_chunk=decode_chunk,
            live=live,
        )
    # constrain the sublayer OUTPUT (a TP partial sum) before the residual
    # add: GSPMD then lowers psum+shard to reduce-scatter instead of
    # all-reducing the full (B,S,D) residual (§Perf iteration B: the AR was
    # 11 GB/step on qwen2-vl train — 2× the RS wire bytes)
    h = plan.constrain(h, plan.dp, seq, None)
    x = x + h

    hin = L.norm_apply(p["ln2"], x)
    n_held = None
    if "moe" in p and cfg.moe_router == "sigmoid_bias":
        h2, n_held = M.moe_held_apply(p["moe"], cfg, hin, count_mask)
    else:
        with jax.named_scope("ffn"):
            if "moe" in p:
                h2 = M.moe_apply(p["moe"], cfg, hin, plan)
            else:
                h2 = L.ffn_apply(p["ffn"], cfg, hin)
    h2 = plan.constrain(h2, plan.dp, seq, None)
    x = plan.constrain(x + h2, plan.dp, seq, None)
    return x, new_cache, n_held


def _stacks(params: Params, cfg: ModelConfig) -> list[tuple[Params, int]]:
    """(stacked layer params, index of its first layer) of each stack of
    alike layers, in order: the leading dense layers, then the rest."""
    out = [(params["layers"], cfg.first_dense_layers)]
    if cfg.first_dense_layers:
        out.insert(0, (params["dense_layers"], 0))
    return out


def _n_stacked(lp: Params) -> int:
    return jax.tree_util.tree_leaves(lp)[0].shape[0]


def _add(total: jax.Array | None, n: jax.Array | None) -> jax.Array | None:
    return total if n is None else total + n


def trunk_apply(
    params: Params,
    cfg: ModelConfig,
    x: jax.Array,  # (B, S, D) — post-embedding
    positions: jax.Array,
    plan: MeshPlan,
    cache: dict | None = None,  # {"k": (L,B,S_max,KH,Dh), "v": ...}
    cache_pos: jax.Array | None = None,
    remat: bool = False,
    block_table: jax.Array | None = None,  # paged: cache leaves are pools
    decode_chunk: bool = False,  # speculative-verify window (serving)
    count_mask: jax.Array | None = None,  # (B, S) real tokens to count
    live: jax.Array | None = None,  # (B,) rows whose decode output is used
) -> tuple[jax.Array, dict | None, jax.Array | None]:
    """Scan the stacked layers.  Returns (hidden, new_cache, held rows).

    With ``block_table`` the cache leaves are block pools
    (L, n_blocks, block_len, …), carried through the scan in place; the
    table is shared across layers (closed over by the scan body, not
    scanned).  A model with leading dense layers scans them first, then the
    rest, carrying the same pool through both.  ``held rows`` sums the
    held-expert assignments of the tokens ``count_mask`` marks over every
    layer (None without a mask)."""
    n_held0 = None if count_mask is None else jnp.int32(0)

    if cache is None:  # train / encoder forward

        def body(carry, lp):
            x, n_held = carry
            x, _, n = layer_apply(lp, cfg, x, positions, plan, None, None,
                                  count_mask=count_mask)
            return (x, _add(n_held, n)), None

        if remat:
            policy = (
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                if cfg.remat_policy == "dots"
                else jax.checkpoint_policies.nothing_saveable
            )
            body = jax.checkpoint(body, policy=policy)
        carry = (x, n_held0)
        for lp, _ in _stacks(params, cfg):
            if cfg.unroll_layers:
                for i in range(_n_stacked(lp)):
                    carry, _ = body(carry, jax.tree_util.tree_map(
                        lambda a: a[i], lp))
            else:
                carry, _ = jax.lax.scan(body, carry, lp)
        x, n_held = carry
        return x, None, n_held

    if block_table is not None:
        # paged: the pool leaves ride in the carry and each layer writes and
        # reads its own blocks in place by (layer, block) index
        # (``layers.LayerPool``).  As scanned input and output they would
        # cost a slice, an update and a copy of the whole pool every step.
        names = tuple(n for n in ("latent", "k", "v", "k_scale", "v_scale")
                      if n in cache)

        def body_paged(carry, inp):
            x, pools, n_held = carry
            lp, layer = inp
            views = tuple(L.LayerPool(p, layer) for p in pools)
            x, new_c, n = layer_apply(lp, cfg, x, positions, plan, views,
                                      cache_pos, block_table,
                                      decode_chunk=decode_chunk,
                                      count_mask=count_mask, live=live)
            return (x, tuple(c.pool for c in new_c), _add(n_held, n)), None

        carry = (x, tuple(cache[n] for n in names), n_held0)
        for lp, first in _stacks(params, cfg):
            ids = first + jnp.arange(_n_stacked(lp))
            carry, _ = jax.lax.scan(body_paged, carry, (lp, ids))
        x, pools, n_held = carry
        return x, dict(zip(names, pools)), n_held

    assert not cfg.first_dense_layers, "leading dense layers: paged cache only"
    quant = "k_scale" in cache

    def body_cached(x, inp):
        if quant:
            lp, kc, vc, ks, vs = inp
            x, new_c, _ = layer_apply(lp, cfg, x, positions, plan,
                                      (kc, vc, ks, vs), cache_pos, block_table,
                                      decode_chunk=decode_chunk, live=live)
        else:
            lp, kc, vc = inp
            x, new_c, _ = layer_apply(lp, cfg, x, positions, plan, (kc, vc),
                                      cache_pos, block_table,
                                      decode_chunk=decode_chunk, live=live)
        return x, new_c

    if quant:
        x, (nk, nv, nks, nvs) = jax.lax.scan(
            body_cached, x,
            (params["layers"], cache["k"], cache["v"],
             cache["k_scale"], cache["v_scale"]),
        )
        return x, {"k": nk, "v": nv, "k_scale": nks, "v_scale": nvs}, None
    x, (new_k, new_v) = jax.lax.scan(
        body_cached, x, (params["layers"], cache["k"], cache["v"])
    )
    return x, {"k": new_k, "v": new_v}, None


# ----------------------------------------------------------------- full model


def forward(
    params: Params,
    cfg: ModelConfig,
    plan: MeshPlan,
    *,
    tokens: jax.Array | None = None,  # (B, S) int32
    embeds: jax.Array | None = None,  # (B, S, D) — stubbed modality frontends
    positions: jax.Array | None = None,  # (B, S) / (B, 3, S); default arange
    cache: dict | None = None,
    cache_pos: jax.Array | None = None,  # decode step / chunk-resume start
    remat: bool = False,
    block_table: jax.Array | None = None,  # paged-KV decode/resume (serving)
    decode_chunk: bool = False,  # speculative-verify window (serving)
    count_mask: jax.Array | None = None,  # (B, S) bool: real tokens
    live: jax.Array | None = None,  # (B,) bool: rows whose output is used
) -> tuple[jax.Array, dict | None] | tuple[jax.Array, dict | None, jax.Array]:
    """→ (logits (B, S, V), new_cache), and with ``count_mask`` a third
    element: the (token, held expert) assignments of the tokens it marks,
    summed over the held-expert layers (an int32 scalar, 0 for a model
    without them).

    ``cache_pos`` with S > 1 resumes prefill mid-prompt: the S tokens are
    treated as the chunk at absolute positions ``cache_pos .. cache_pos+S-1``
    over an existing cache prefix (see ``layers.attention_apply`` modes and
    ``registry.check_slots_cache_contract``).  ``decode_chunk=True`` (with
    ``cache_pos``, S > 1) is the speculative-verify window: same cache
    writes, but attention runs decode-style so every window row is bitwise
    the computation sequential decode would do.  In a decode step or verify
    window a row that is not ``live`` reads one block of its cache: its
    output is dropped (masked and retired serving slots)."""
    dtype = jnp.dtype(cfg.compute_dtype)
    if embeds is None:
        assert tokens is not None
        x = L.embed_apply(params["embed"], tokens, dtype)
        b, s = tokens.shape
    else:
        x = embeds.astype(dtype)
        b, s, _ = embeds.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        if cache_pos is not None:
            # decode (S == 1) / chunk-resume prefill (S > 1): absolute
            # positions continue from each row's cache offset
            positions = cache_pos[:, None] + positions

    seq = plan.tp if s > 1 else None
    x = plan.constrain(x, plan.dp, seq, None)
    x, new_cache, n_held = trunk_apply(
        params, cfg, x, positions, plan, cache, cache_pos, remat, block_table,
        decode_chunk, count_mask, live,
    )
    x = L.norm_apply(params["final_norm"], x)
    with jax.named_scope("lm_head"):
        if cfg.tie_embeddings:
            logits = x @ params["embed"]["embedding"].astype(x.dtype).T
        else:
            logits = L.lm_head_apply(params["lm_head"], x)
    logits = plan.constrain(logits, plan.dp, None, plan.tp)
    if count_mask is not None:
        return logits, new_cache, n_held if n_held is not None else jnp.int32(0)
    return logits, new_cache


def init_cache(
    cfg: ModelConfig, batch: int, max_len: int, plan: MeshPlan, dtype=jnp.bfloat16
) -> dict:
    """Contract (all model families): the cache is a pytree of arrays with
    static shapes, and one decode step maps it to an identical pytree —
    it must be carry-able through ``lax.scan`` / donate-able into the
    compiled serving loop (checked by ``registry.check_decode_cache_carry``).
    """
    if cfg.attention == "mla":
        raise NotImplementedError(MLA_DENSE_LAYOUT)
    kh_eff = cfg.n_kv_heads * (plan.kv_repeat if plan else 1)
    shape = (cfg.n_layers, batch, max_len, kh_eff, cfg.head_dim)
    if plan is not None and plan.cache_quant_int8:
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(shape[:-1], jnp.float32),
            "v_scale": jnp.zeros(shape[:-1], jnp.float32),
        }
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_paged_cache(
    cfg: ModelConfig, n_blocks: int, block_len: int, plan: MeshPlan,
    dtype=jnp.bfloat16,
) -> dict:
    """Paged serving cache: a pool of KV blocks shared by every slot.

    Leaves are (n_layers, n_blocks, block_len, KH, Dh) — the block axis sits
    where the dense layout's slot axis does (``registry.CACHE_BLOCK_AXIS``),
    so the scan-carry and write contracts transfer.  The serving layer
    reserves the first ``n_slots`` physical blocks as per-slot scratch (see
    ``layers.paged_cache_write``) and allocates the rest.  Same carry
    contract as ``init_cache``: one paged decode step maps the pool pytree
    to an identical pytree (``registry.check_paged_cache_contract``).
    """
    assert n_blocks >= 2 and block_len >= 1, (n_blocks, block_len)
    if cfg.attention == "mla":
        # latent attention: one leaf of [c | k_pe] a token a layer
        if plan is not None and plan.cache_quant_int8:
            raise NotImplementedError(MLA_INT8_KV)
        return {"latent": jnp.zeros(
            (cfg.n_layers, n_blocks, block_len, cfg.kv_latent_dim), dtype)}
    kh_eff = cfg.n_kv_heads * (plan.kv_repeat if plan else 1)
    shape = (cfg.n_layers, n_blocks, block_len, kh_eff, cfg.head_dim)
    if plan is not None and plan.cache_quant_int8:
        # per-block KV scales ride the same block table as the values: the
        # scale pools drop the Dh axis (one fp32 per position per head) but
        # keep the (L, n_blocks, block_len, KH) leading layout, so every
        # write/gather/scatter helper indexes them identically
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(shape[:-1], jnp.float32),
            "v_scale": jnp.zeros(shape[:-1], jnp.float32),
        }
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def loss_fn(
    logits: jax.Array,  # (B, S, V)
    labels: jax.Array,  # (B, S) int32; -1 = ignore
) -> jax.Array:
    """Mean token cross-entropy, fp32, vocab-sharding-safe.

    The label logit is extracted with a compare-and-sum over the vocab axis
    (not take_along_axis): an elementwise (label == iota_V) mask reduces over
    the sharded axis with a plain psum, so GSPMD never all-gathers the
    (B, S, V) logits — the gather lowering would.
    """
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    v = logits.shape[-1]
    onehot = labels[..., None] == jax.lax.iota(jnp.int32, v)  # (B,S,V) fused
    ll = jnp.sum(jnp.where(onehot, lf, 0.0), axis=-1)
    valid = (labels >= 0).astype(jnp.float32)
    nll = (lse - ll) * valid
    return nll.sum() / jnp.maximum(valid.sum(), 1.0)
