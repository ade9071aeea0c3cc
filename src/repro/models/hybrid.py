"""zamba2-style hybrid: Mamba2 backbone + ONE shared attention block invoked
every ``cfg.shared_attention_every`` layers (weights reused; per-invocation KV
caches).  DESIGN.md notes the simplifications vs the released model (single
shared block, no LoRA adapters, no embedding concat).

Scan layout: mamba layer params stacked (L, …); the shared block's params are
closed over (not scanned).  The attention KV cache (n_inv, B, S, KH, Dh) rides
in the scan *carry* and is updated with dynamic slices at invocation index
idx // every.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.mamba2 import mamba2_apply, mamba2_init, mamba2_init_state
from repro.sharding.mesh import MeshPlan

Params = dict[str, Any]


def n_shared_invocations(cfg: ModelConfig) -> int:
    every = cfg.shared_attention_every
    return (cfg.n_layers + every - 1) // every if every else 0


def init_params(cfg: ModelConfig, key) -> Params:
    kemb, kmamba, kshared, khead = jax.random.split(key, 4)
    layer_keys = jax.random.split(kmamba, cfg.n_layers)
    ks = jax.random.split(kshared, 2)
    return {
        "embed": L.embed_init(kemb, cfg),
        "mamba_layers": jax.vmap(
            lambda k: {"ln": L.norm_init(cfg), "block": mamba2_init(k, cfg)}
        )(layer_keys),
        "shared": {
            "ln_a": L.norm_init(cfg),
            "attn": L.attention_init(ks[0], cfg),
            "ln_f": L.norm_init(cfg),
            "ffn": L.ffn_init(ks[1], cfg),
        },
        "final_norm": L.norm_init(cfg),
        "lm_head": L.lm_head_init(khead, cfg),
    }


def _shared_block(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    plan: MeshPlan,
    cache: tuple | None,
    cache_pos: jax.Array | None,
    live: jax.Array | None = None,
) -> tuple[jax.Array, tuple | None]:
    b, s, _ = x.shape
    seq = plan.tp if s > 1 else None
    h, new_cache = L.attention_apply(
        p["attn"], cfg, L.norm_apply(p["ln_a"], x), positions,
        plan=plan, cache=cache, cache_pos=cache_pos, causal=True, live=live,
    )
    x = plan.constrain(x + h, plan.dp, seq, None)
    h2 = L.ffn_apply(p["ffn"], cfg, L.norm_apply(p["ln_f"], x))
    x = plan.constrain(x + h2, plan.dp, seq, None)
    return x, new_cache


def forward(
    params: Params,
    cfg: ModelConfig,
    plan: MeshPlan,
    *,
    tokens: jax.Array | None = None,
    embeds: jax.Array | None = None,
    positions: jax.Array | None = None,
    cache: dict | None = None,  # see init_cache
    cache_pos: jax.Array | None = None,
    remat: bool = False,
    live: jax.Array | None = None,  # (B,) rows whose decode output is used
) -> tuple[jax.Array, dict | None]:
    dtype = jnp.dtype(cfg.compute_dtype)
    if embeds is None:
        x = L.embed_apply(params["embed"], tokens, dtype)
        b, s = tokens.shape
    else:
        x = embeds.astype(dtype)
        b, s, _ = embeds.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        if cache_pos is not None:
            positions = cache_pos[:, None]
    seq = plan.tp if s > 1 else None
    x = plan.constrain(x, plan.dp, seq, None)

    every = cfg.shared_attention_every
    shared_p = params["shared"]
    with_cache = cache is not None

    def body(carry, inp):
        if with_cache:
            x, kc, vc = carry
            lp, ssm_state, conv_state, idx = inp
            mstate = {"ssm": ssm_state, "conv": conv_state}
        else:
            x = carry
            lp, idx = inp
            mstate = None

        def run_shared(x, kc=None, vc=None):
            inv = idx // every
            if with_cache:
                kci = jax.lax.dynamic_index_in_dim(kc, inv, 0, keepdims=False)
                vci = jax.lax.dynamic_index_in_dim(vc, inv, 0, keepdims=False)
                xo, nc = _shared_block(
                    shared_p, cfg, x, positions, plan, (kci, vci), cache_pos,
                    live,
                )
                kc = jax.lax.dynamic_update_index_in_dim(kc, nc[0], inv, 0)
                vc = jax.lax.dynamic_update_index_in_dim(vc, nc[1], inv, 0)
                return xo, kc, vc
            xo, _ = _shared_block(shared_p, cfg, x, positions, plan, None, None)
            return xo

        if with_cache:
            x, kc, vc = jax.lax.cond(
                idx % every == 0,
                lambda a: run_shared(*a),
                lambda a: a,
                (x, kc, vc),
            )
        else:
            x = jax.lax.cond(idx % every == 0, run_shared, lambda x: x, x)

        # norm → mamba2 → residual
        h, new_mstate = mamba2_apply(lp["block"], cfg, L.norm_apply(lp["ln"], x), mstate)
        x = plan.constrain(x + h, plan.dp, plan.tp if x.shape[1] > 1 else None, None)

        if with_cache:
            return (x, kc, vc), (new_mstate["ssm"], new_mstate["conv"])
        return x, None

    idxs = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    lp_stacked = params["mamba_layers"]

    if with_cache:
        carry = (x, cache["attn_k"], cache["attn_v"])
        (x, nk, nv), (new_ssm, new_conv) = jax.lax.scan(
            body, carry, (lp_stacked, cache["ssm"], cache["conv"], idxs)
        )
        new_cache = {"attn_k": nk, "attn_v": nv, "ssm": new_ssm, "conv": new_conv}
    else:
        bodyfn = (
            jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
            if remat
            else body
        )
        x, _ = jax.lax.scan(bodyfn, x, (lp_stacked, idxs))
        new_cache = None

    x = L.norm_apply(params["final_norm"], x)
    logits = L.lm_head_apply(params["lm_head"], x)
    logits = plan.constrain(logits, plan.dp, None, plan.tp)
    return logits, new_cache


def init_cache(
    cfg: ModelConfig, batch: int, max_len: int, plan: MeshPlan, dtype=jnp.bfloat16
) -> dict:
    from repro.models.mamba2 import mamba2_dims

    dm = mamba2_dims(cfg)
    n_inv = n_shared_invocations(cfg)
    kh_eff = cfg.n_kv_heads * (plan.kv_repeat if plan else 1)
    return {
        "attn_k": jnp.zeros((n_inv, batch, max_len, kh_eff, cfg.head_dim), dtype),
        "attn_v": jnp.zeros((n_inv, batch, max_len, kh_eff, cfg.head_dim), dtype),
        "ssm": jnp.zeros((cfg.n_layers, batch, dm["h"], dm["n"], dm["p"]), jnp.float32),
        "conv": jnp.zeros(
            (cfg.n_layers, batch, cfg.ssm_conv_width - 1, dm["conv_dim"]), dtype
        ),
    }
