"""Token-choice top-k MoE: softmax routing over capacity buffers with GSPMD
expert parallelism (``moe_apply``), and DeepSeek-V3's sigmoid-routed layer
computing one device's held experts drop-free (``moe_held_apply``, at the
end of this module).

Design (DESIGN.md §5):

* Experts are sharded over the ``model`` axis.  When n_experts < |model|, each
  expert is *split along d_ff* into ``split`` equal virtual experts — an exact
  decomposition for SwiGLU/MLP FFNs (elementwise in d_ff) — so the virtual
  expert count E_v = E·split always shards (grok-1: 8e × 2 = 16 ✓).  A token
  routed to real expert e is dispatched to all of e's virtual halves with the
  same gate weight.

* Dispatch is gather-based and grouped by batch row: per row, token→expert
  assignments are sorted (vmapped argsort — batch-sharded, no cross-device
  sort), producing an int32 index buffer (B, E_v, C) that gathers tokens into
  expert-major order.  Capacity C = ceil(S·k_v/E_v · capacity_factor);
  overflow tokens are dropped (standard Switch/GShard semantics), underflow
  slots are masked.

* The (B, E_v, C, d) → (E_v, B·C, d) transpose carries the sharding change
  dp-major → model-major: under GSPMD this lowers to exactly the expert
  all-to-all.

* ``moe_apply_dense`` is the oracle: computes every expert for every token and
  combines with the same gates (equals the sparse path when nothing drops).
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import Params, _normal, ffn_apply, ffn_init
from repro.sharding.mesh import MeshPlan


def expert_split_factor(cfg: ModelConfig, tp: int) -> int:
    e = cfg.n_experts
    if e % tp == 0:
        return 1
    # smallest split s.t. E·split % tp == 0 and d_ff % split == 0
    for s in range(2, tp + 1):
        if (e * s) % tp == 0 and cfg.d_ff % s == 0:
            return s
    return 1


def moe_init(key, cfg: ModelConfig) -> Params:
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    p = {
        "router": {"kernel": _normal(ks[0], (d, e), jnp.float32, d**-0.5)},
        "wi": _normal(ks[1], (e, d, f), dt, d**-0.5),
        "wo": _normal(ks[3], (e, f, d), dt, f**-0.5),
    }
    if cfg.ffn == "swiglu":
        p["wg"] = _normal(ks[2], (e, d, f), dt, d**-0.5)
    return p


# Deterministic routing (ROADMAP open item): under tp sharding, psum
# reordering — of the router contraction and of every layer upstream —
# perturbs the fp32 router logits by ~1e-6 rel between mesh layouts, flipping
# top-k choices on near-tied experts (~1% of tokens, 0.13 max rel output
# err).  The SELECTION copy of the logits is therefore snapped to a
# _ROUTER_QUANTUM grid (coarse enough to swallow layout noise, three orders
# below anything the softmax cares about), and exact grid ties are broken by
# a strictly-decreasing epsilon·expert_id bias (sub-quantum, so it never
# reorders distinct grid values) — the same decision on every layout, without
# relying on top_k's internal tie behaviour.  Gates stay differentiable: they
# are gathered from the softmax of the UNQUANTIZED logits.
#
# Residual risk (quantified): a logit sitting within the noise width of a
# half-quantum rounding boundary can still snap differently across layouts.
# With fp32 noise ~1e-6 and quantum 1e-3 that needs the logit within ~1e-6 of
# a boundary AND a competing expert within one quantum — ~1e-6 per logit
# pair, ~1e-3 per 512-logit test run — and is deterministic per (jax
# version, seed).  Under bf16 compute the upstream noise is ~1e-2, which no
# quantum can absorb without distorting routing; see ROADMAP open items.
_ROUTER_QUANTUM = 1e-3
_TIEBREAK_EPS = 1e-6


def _selection_logits(logits: jax.Array) -> jax.Array:
    """fp32 logits (…, E) → layout-deterministic selection copy (no grad)."""
    e = logits.shape[-1]
    snapped = jnp.round(logits / _ROUTER_QUANTUM) * _ROUTER_QUANTUM
    return jax.lax.stop_gradient(
        snapped - _TIEBREAK_EPS * jnp.arange(e, dtype=jnp.float32)
    )


def _router(p: Params, cfg: ModelConfig, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x (B, S, d) → (gates (B, S, k), experts (B, S, k) int32).

    Softmax-then-top-k with gate renormalization (Mixtral/DeepSeek style).
    Router math in fp32 for stability; expert choice is made on the
    deterministic selection logits, gate values on the smooth probs.
    """
    logits = x.astype(jnp.float32) @ p["router"]["kernel"]
    _, experts = jax.lax.top_k(_selection_logits(logits), cfg.experts_per_token)
    probs = jax.nn.softmax(logits, axis=-1)
    gates = jnp.take_along_axis(probs, experts, axis=-1)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, experts.astype(jnp.int32)


def _virtualize(
    gates: jax.Array, experts: jax.Array, split: int
) -> tuple[jax.Array, jax.Array]:
    """Expand (…, k) real routing to (…, k·split) virtual routing."""
    if split == 1:
        return gates, experts
    v_experts = experts[..., None] * split + jnp.arange(split)  # (…, k, split)
    v_gates = jnp.broadcast_to(gates[..., None], v_experts.shape)
    return (
        v_gates.reshape(*gates.shape[:-1], -1),
        v_experts.reshape(*experts.shape[:-1], -1).astype(jnp.int32),
    )


def _split_weights(p: Params, split: int) -> Params:
    """(E, d, f) → (E·split, d, f/split); exact SwiGLU/MLP decomposition."""
    if split == 1:
        return p
    out = {"router": p["router"]}
    for name in ("wi", "wg"):
        if name in p:
            e, d, f = p[name].shape
            out[name] = (
                p[name].reshape(e, d, split, f // split)
                .transpose(0, 2, 1, 3)
                .reshape(e * split, d, f // split)
            )
    e, f, d = p["wo"].shape
    out["wo"] = (
        p["wo"].reshape(e, split, f // split, d).reshape(e * split, f // split, d)
    )
    return out


def _expert_ffn(p: Params, cfg: ModelConfig, h: jax.Array) -> jax.Array:
    """h (E_v, T, d) → (E_v, T, d), batched per-expert FFN."""
    dt = h.dtype
    hi = jnp.einsum("etd,edf->etf", h, p["wi"].astype(dt))
    if "wg" in p:
        hi = jax.nn.silu(hi) * jnp.einsum("etd,edf->etf", h, p["wg"].astype(dt))
    else:
        hi = jax.nn.gelu(hi)
    return jnp.einsum("etf,efd->etd", hi, p["wo"].astype(dt))


def _dispatch_indices(
    experts: jax.Array, gates: jax.Array, e_v: int, capacity: int
) -> tuple[jax.Array, jax.Array]:
    """Per batch row: token→expert assignments → expert-major buffers.

    experts/gates: (T, k_v) for ONE group.  Returns:
      idx_buf  (E_v, C) int32   — token id filling each expert slot, -1 empty
      gate_buf (E_v, C) float32 — combine weight of that slot (0 if empty)
    Slots are unique per (expert, pos-in-expert): writes never collide;
    tokens past capacity are dropped (Switch/GShard semantics).
    """
    t, k_v = experts.shape
    flat = experts.reshape(-1)  # (T·k_v,)
    order = jnp.argsort(flat, stable=True)  # expert-major, token-minor
    sorted_e = flat[order]
    counts = jnp.bincount(sorted_e, length=e_v)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(t * k_v, dtype=jnp.int32) - starts[sorted_e].astype(jnp.int32)
    keep = pos_in_e < capacity
    slot = jnp.where(keep, pos_in_e, capacity)  # dropped → overflow col C
    token_of = (order // k_v).astype(jnp.int32)
    gate_of = gates.reshape(-1)[order].astype(jnp.float32)
    idx_buf = jnp.full((e_v, capacity + 1), -1, jnp.int32)
    idx_buf = idx_buf.at[sorted_e, slot].set(token_of, mode="drop")
    gate_buf = jnp.zeros((e_v, capacity + 1), jnp.float32)
    gate_buf = gate_buf.at[sorted_e, slot].set(gate_of, mode="drop")
    return idx_buf[:, :capacity], gate_buf[:, :capacity]


def moe_apply(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,  # (B, S, d)
    plan: MeshPlan,
    capacity_factor: float | None = None,
) -> jax.Array:
    """Sparse MoE forward.

    Two sharding regimes (DESIGN.md §5):
      * EP (n_experts % tp == 0, e.g. moonshot 64e/16): experts sharded over
        the model axis; the dp-major → model-major buffer transpose is the
        expert all-to-all.
      * TP-experts (otherwise, e.g. grok-1 8e/16): expert weights stay in
        their natural (E, d, f) layout with d_ff tp-sharded — no in-graph
        weight reshapes (transposing 600 GB of grok experts in-graph forces
        SPMD rematerialization; measured +22 GB/dev temp) — tokens replicate
        over model, partial outputs psum.
    """
    b, s, d = x.shape
    e = cfg.n_experts
    k = cfg.experts_per_token
    ep = plan.mesh is None or (e % plan.tp_size == 0)
    cf = capacity_factor or cfg.moe_capacity_factor
    capacity = max(int(math.ceil(s * k / e * cf)), 1)

    # tokens replicated over model axis inside the MoE block (AG from SP)
    # BEFORE the router contraction: the router then reduces over the full,
    # identically-laid-out d axis on every shard, minimizing the layout-
    # dependent reduction noise the tie-break has to absorb
    x = plan.constrain(x, plan.dp, None, None)

    gates, experts = _router(p, cfg, x)  # (B,S,k)

    idx_buf, gate_buf = jax.vmap(
        lambda ee, g: _dispatch_indices(ee, g, e, capacity)
    )(experts, gates)
    # idx_buf (B, E, C); gather tokens → expert-major buffer.  x is
    # model-replicated; with EP the output expert dim is tp-sharded ⇒ each
    # model shard gathers only its experts' tokens (no extra comm).
    idx_safe = jnp.maximum(idx_buf, 0).reshape(b, e * capacity)
    buf = jnp.take_along_axis(x, idx_safe[..., None], axis=1)
    buf = buf.reshape(b, e, capacity, d)
    buf = jnp.where((idx_buf >= 0)[..., None], buf, 0)
    e_spec = plan.tp if (ep and plan.mesh is not None) else None
    buf = plan.constrain(buf, plan.dp, e_spec, None, None)

    # dp-major → model-major on experts: the expert all-to-all (EP only)
    buf = buf.transpose(1, 0, 2, 3).reshape(e, b * capacity, d)
    buf = plan.constrain(buf, e_spec, plan.dp, None)

    out_buf = _expert_ffn(p, cfg, buf)  # (E, B·C, d); TP: psum'd over model
    out_buf = plan.constrain(out_buf, e_spec, plan.dp, None)

    # back to dp-major token dim, experts KEPT tp-sharded under EP
    out_buf = out_buf.reshape(e, b, capacity, d).transpose(1, 0, 2, 3)
    out_buf = plan.constrain(out_buf, plan.dp, e_spec, None, None)

    # combine: scatter-add each slot's weighted output back to its token.
    # Under EP segment_sum contracts the tp-sharded (E·C) dim ⇒ GSPMD emits
    # per-shard partial sums + one all-reduce of the (B, S, d) result.
    weighted = out_buf * gate_buf[..., None].astype(out_buf.dtype)
    seg_ids = jnp.where(idx_buf >= 0, idx_buf, s)  # dropped → segment S

    def combine_one(w, sid):
        return jax.ops.segment_sum(
            w.reshape(e * capacity, d), sid.reshape(-1), num_segments=s + 1
        )[:s]

    out = jax.vmap(combine_one)(weighted, seg_ids)
    return plan.constrain(out, plan.dp, plan.tp if s > 1 else None, None)


def moe_apply_dense(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Oracle: every expert on every token, gate-combined.  O(E/k) overhead —
    smoke tests and decode-shape fallback only."""
    b, s, d = x.shape
    gates, experts = _router(p, cfg, x)
    xt = x.reshape(1, b * s, d)
    outs = _expert_ffn(p, cfg, jnp.broadcast_to(xt, (cfg.n_experts, b * s, d)))
    outs = outs.reshape(cfg.n_experts, b, s, d)
    onehot = jax.nn.one_hot(experts, cfg.n_experts, dtype=x.dtype)  # (B,S,k,E)
    w = (onehot * gates[..., None].astype(x.dtype)).sum(2)  # (B,S,E)
    return jnp.einsum("ebsd,bse->bsd", outs, w)


def moe_load_balance_loss(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Switch-style auxiliary load-balancing loss (mean fraction · mean prob).
    Expert counts use the same deterministic selection as ``_router``."""
    logits = x.astype(jnp.float32) @ p["router"]["kernel"]
    probs = jax.nn.softmax(logits, -1)
    _, experts = jax.lax.top_k(_selection_logits(logits), cfg.experts_per_token)
    frac = jax.nn.one_hot(experts, cfg.n_experts).mean((0, 1, 2))
    return cfg.n_experts * jnp.sum(frac * probs.mean((0, 1)))


# ------------------------------------------------ held experts, drop-free
#
# DeepSeek-V3's expert layer as one device of an expert-parallel group runs
# it: the router spans all ``n_experts`` (sigmoid scores in fp32; the
# experts chosen are the top-k of scores + a per-expert correction bias; a
# chosen expert's weight is its score over the chosen scores' sum, times
# ``moe_routed_scaling``), and this device computes only the part of the
# result that its ``experts_held`` experts (from ``expert_offset``) give,
# plus the shared experts.  What the absent experts add is left to the
# devices that hold them; on one device there is no exchange at all.
#
# Nothing drops: every (token, held expert) assignment is computed, in one
# of two forms chosen from the launch's token count T = B·S, which is
# static.  At or below ``ALL_HELD_MAX_TOKENS`` every token goes through
# every held expert in one batched contraction (``_held_rows_all``): each
# layer's stacked ``wi``/``wg``/``wo`` slice is read once, where it lies,
# and the choices are picked out of the result.  Above it the T·k
# assignments are sorted by held expert (the others last) into one buffer
# that grouped matmuls (``jax.lax.ragged_dot``) run over
# (``_held_rows_grouped``); those take their weights whole, so a layer scan
# first copies each slice out of the stack.  Either way each token's rows
# combine in a fixed choice order, so within one form a token's output
# does not depend on what else is in the launch; across the bound the two
# forms may differ by rounding.

# The all-held form does 2·T FLOPs per bf16 weight (2 bytes): T FLOPs per
# weight byte.  Up to the chip's ridge (v5e: 197 TFLOP/s / 819 GB/s, about
# 240) it is bound by the weight read, which the grouped form pays too;
# 128 leaves a margin.
ALL_HELD_MAX_TOKENS = 128


def takes_all_held(n_tokens: int) -> bool:
    """Whether a launch of ``n_tokens`` tokens (B·S) computes its held
    experts in the all-held form."""
    return n_tokens <= ALL_HELD_MAX_TOKENS


def moe_held_init(key, cfg: ModelConfig) -> Params:
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    e, d, f = cfg.n_experts_held, cfg.d_model, cfg.d_ff
    return {
        "router": {"kernel": _normal(ks[0], (d, cfg.n_experts), jnp.float32,
                                     d**-0.5),
                   "score_bias": jnp.zeros((cfg.n_experts,), jnp.float32)},
        "wi": _normal(ks[1], (e, d, f), dt, d**-0.5),
        "wg": _normal(ks[2], (e, d, f), dt, d**-0.5),
        "wo": _normal(ks[3], (e, f, d), dt, f**-0.5),
        "shared": ffn_init(ks[4], cfg, cfg.moe_shared_experts * f),
    }


def route_sigmoid_bias(p: Params, cfg: ModelConfig,
                       x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x (T, d) → (weights (T, k) fp32, experts (T, k) int32) over all
    ``n_experts``, on unsnapped fp32 scores."""
    logits = jnp.dot(x.astype(jnp.float32),
                     p["router"]["kernel"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, experts = jax.lax.top_k(scores + p["router"]["score_bias"],
                               cfg.experts_per_token)
    w = jnp.take_along_axis(scores, experts, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * cfg.moe_routed_scaling, experts.astype(jnp.int32)


def held_rows_launched(cfg: ModelConfig, n_tokens: int) -> int:
    """Assignment rows of one forward over ``n_tokens`` tokens, over every
    MoE layer: each token's k assignments, padding and experts held
    elsewhere included (the grouped form's buffer; the all-held form
    computes every held expert's row of each token instead)."""
    return (cfg.n_layers - cfg.first_dense_layers) * n_tokens * cfg.experts_per_token


def moe_held_apply(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,  # (B, S, d)
    count_mask: jax.Array | None = None,  # (B, S) bool: the real tokens
) -> tuple[jax.Array, jax.Array | None]:
    """Held experts' part + shared experts → ((B, S, d), held rows).

    ``held rows`` counts the (token, held expert) assignments of the tokens
    ``count_mask`` marks (None without a mask)."""
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    with jax.named_scope("moe.route"):
        w, experts = route_sigmoid_bias(p, cfg, xt)
        local = experts - cfg.expert_offset
        held = (local >= 0) & (local < cfg.n_experts_held)
    form = _held_rows_all if takes_all_held(t) else _held_rows_grouped
    y = form(p, xt, local, held)
    with jax.named_scope("moe.experts"):
        # each token's k rows summed in a fixed order, weighted zero where
        # the expert is held elsewhere
        y = y.astype(jnp.float32)
        w_held = jnp.where(held, w, 0.0)
        routed = sum(w_held[:, j, None] * y[:, j]
                     for j in range(cfg.experts_per_token))
    with jax.named_scope("moe.shared"):
        shared = ffn_apply(p["shared"], cfg, xt)
    out = (routed + shared.astype(jnp.float32)).astype(x.dtype).reshape(b, s, d)
    n_held = None
    if count_mask is not None:
        n_held = jnp.sum(held & count_mask.reshape(t, 1), dtype=jnp.int32)
    return out, n_held


def _held_rows_all(p: Params, xt: jax.Array, local: jax.Array,
                   held: jax.Array) -> jax.Array:
    """(T, k, d) rows of each token's choices: every token through every
    held expert, then each token's k picked out (zero where held
    elsewhere).  No sort and no copy of the weights."""
    dt = xt.dtype
    with jax.named_scope("moe.experts"):
        hi = jnp.einsum("td,edf->etf", xt, p["wi"].astype(dt))
        hg = jnp.einsum("td,edf->etf", xt, p["wg"].astype(dt))
        y = jnp.einsum("etf,efd->etd", jax.nn.silu(hi) * hg,
                       p["wo"].astype(dt))  # (E_held, T, d)
        pick = jnp.clip(local, 0, y.shape[0] - 1).T[..., None]  # (k, T, 1)
        y = jnp.take_along_axis(y, pick, axis=0)
        return jnp.where(held.T[..., None], y, 0).swapaxes(0, 1)


def _held_rows_grouped(p: Params, xt: jax.Array, local: jax.Array,
                       held: jax.Array) -> jax.Array:
    """(T, k, d) rows of each token's choices by grouped matmuls over the
    assignments sorted by held expert (zero where held elsewhere)."""
    (t, k), e = local.shape, p["wi"].shape[0]
    with jax.named_scope("moe.route"):
        group = jnp.where(held, local, e).reshape(-1)  # elsewhere → last
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=e + 1)[:e].astype(jnp.int32)
        rows = xt[order // k]  # (T·k, d), grouped by held expert
    with jax.named_scope("moe.experts"):
        dt = xt.dtype
        hi = jax.lax.ragged_dot(rows, p["wi"].astype(dt), sizes)
        hg = jax.lax.ragged_dot(rows, p["wg"].astype(dt), sizes)
        y = jax.lax.ragged_dot(jax.nn.silu(hi) * hg, p["wo"].astype(dt), sizes)
        # rows past the held groups are not computed: zero them and put
        # the rows back in (token, choice) order
        y = jnp.where((jnp.arange(t * k) < sizes.sum())[:, None], y, 0)
        y = jnp.zeros_like(y).at[order].set(y, unique_indices=True)
        return y.reshape(t, k, -1)
