"""RWKV6 full-model assembly (rwkv6-3b): embed → scan over (time-mix +
channel-mix) layers → head.  Per-layer recurrent states replace the KV cache;
their size is O(1) in sequence length.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.rwkv6 import (
    rwkv6_channel_mix_apply,
    rwkv6_channel_mix_init,
    rwkv6_init_state,
    rwkv6_time_mix_apply,
    rwkv6_time_mix_init,
)
from repro.sharding.mesh import MeshPlan

Params = dict[str, Any]


def init_params(cfg: ModelConfig, key) -> Params:
    kemb, klyr, khead = jax.random.split(key, 3)
    layer_keys = jax.random.split(klyr, cfg.n_layers)

    def one(k):
        k1, k2 = jax.random.split(k)
        return {
            "ln1": L.norm_init(cfg),
            "time_mix": rwkv6_time_mix_init(k1, cfg),
            "ln2": L.norm_init(cfg),
            "channel_mix": rwkv6_channel_mix_init(k2, cfg),
        }

    return {
        "embed": L.embed_init(kemb, cfg),
        "embed_norm": L.norm_init(cfg),  # rwkv uses LN right after embedding
        "layers": jax.vmap(one)(layer_keys),
        "final_norm": L.norm_init(cfg),
        "lm_head": L.lm_head_init(khead, cfg),
    }


def forward(
    params: Params,
    cfg: ModelConfig,
    plan: MeshPlan,
    *,
    tokens: jax.Array | None = None,
    embeds: jax.Array | None = None,
    positions: jax.Array | None = None,  # unused (attention-free)
    cache: dict | None = None,  # stacked rwkv6_init_state over layers
    cache_pos: jax.Array | None = None,  # unused
    remat: bool = False,
    live: jax.Array | None = None,  # unused: no cache to read by length
) -> tuple[jax.Array, dict | None]:
    del positions, cache_pos, live
    dtype = jnp.dtype(cfg.compute_dtype)
    if embeds is None:
        x = L.embed_apply(params["embed"], tokens, dtype)
    else:
        x = embeds.astype(dtype)
    x = L.norm_apply(params["embed_norm"], x)
    s = x.shape[1]
    seq = plan.tp if s > 1 else None
    x = plan.constrain(x, plan.dp, seq, None)
    with_cache = cache is not None

    def body(x, inp):
        if with_cache:
            lp, st = inp
        else:
            lp, st = inp, None
        h, new_t = rwkv6_time_mix_apply(
            lp["time_mix"], cfg, L.norm_apply(lp["ln1"], x),
            {"shift_t": st["shift_t"], "wkv": st["wkv"]} if st else None,
        )
        x = plan.constrain(x + h, plan.dp, seq, None)
        h2, new_c = rwkv6_channel_mix_apply(
            lp["channel_mix"], cfg, L.norm_apply(lp["ln2"], x),
            {"shift_c": st["shift_c"]} if st else None,
        )
        x = plan.constrain(x + h2, plan.dp, seq, None)
        new_st = {**new_t, **new_c}
        return x, new_st if with_cache else None

    if with_cache:
        x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
    else:
        bodyfn = (
            jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
            if remat
            else body
        )
        x, _ = jax.lax.scan(bodyfn, x, params["layers"])
        new_cache = None

    x = L.norm_apply(params["final_norm"], x)
    logits = L.lm_head_apply(params["lm_head"], x)
    logits = plan.constrain(logits, plan.dp, None, plan.tp)
    return logits, new_cache


def init_cache(
    cfg: ModelConfig, batch: int, max_len: int, plan: MeshPlan, dtype=jnp.bfloat16
) -> dict:
    del max_len  # state is O(1) in sequence length
    one = rwkv6_init_state(cfg, batch, dtype)
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (cfg.n_layers, *a.shape)), one
    )
