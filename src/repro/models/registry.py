"""Arch registry: maps every assigned ``--arch`` id to its config, model
module, abstract input specs, and shape-support rules (DESIGN.md §4).

``input_specs(arch, shape, plan)`` returns ShapeDtypeStructs (with
NamedShardings when the plan has a mesh) for every model input of that
(arch × shape) cell — the dry-run lowers against these, allocating nothing.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeSpec, get_config, reduced_config
from repro.sharding.mesh import MeshPlan

SDS = jax.ShapeDtypeStruct


@dataclasses.dataclass(frozen=True)
class Arch:
    arch_id: str
    cfg: ModelConfig
    module: Any  # repro.models.{transformer|hybrid|rwkv_model}
    period: int  # layers per homogeneous period (cost-probe granularity)
    input_kind: str  # "tokens" | "embeds" | "embeds+mrope"

    # -- delegation ---------------------------------------------------------
    def init_params(self, key):
        return self.module.init_params(self.cfg, key)

    def abstract_params(self, cfg: ModelConfig | None = None):
        cfg = cfg or self.cfg
        return jax.eval_shape(lambda: self.module.init_params(cfg, jax.random.PRNGKey(0)))

    def forward(self, params, plan: MeshPlan, cfg: ModelConfig | None = None, **kw):
        return self.module.forward(params, cfg or self.cfg, plan, **kw)

    def init_cache(self, batch: int, max_len: int, plan: MeshPlan,
                   cfg: ModelConfig | None = None):
        return self.module.init_cache(cfg or self.cfg, batch, max_len, plan)

    def abstract_cache(self, batch: int, max_len: int, plan: MeshPlan,
                       cfg: ModelConfig | None = None):
        return jax.eval_shape(
            lambda: self.module.init_cache(cfg or self.cfg, batch, max_len, plan)
        )

    # -- chunked prefill (serving; see check_slots_cache_contract) ----------
    @property
    def supports_chunked_prefill(self) -> bool:
        return self.chunked_prefill_skip_reason() == ""

    def chunked_prefill_skip_reason(self) -> str:
        """'' when the family can resume prefill at a nonzero start position
        over an existing cache prefix (the batched/chunked admission path),
        else why not (mirrors ``paged_skip_reason``'s skip-matrix style)."""
        if self.cfg.encoder_only:
            return "encoder-only arch has no decode step"
        if self.cfg.rwkv_head_size:
            return ("rwkv carries O(1) recurrent state, not a growing KV "
                    "cache; resuming prefill mid-prompt needs a state-"
                    "snapshot contract that is not wired yet")
        if self.cfg.family == "hybrid":
            return ("hybrid cache mixes attention KV with O(1) ssm/conv "
                    "state; chunk-resume over the recurrent leaves is not "
                    "wired yet")
        return ""

    # -- speculative decoding (serving; see serve.engine.SpecConfig) --------
    @property
    def supports_spec_decode(self) -> bool:
        return self.spec_decode_skip_reason() == ""

    def spec_decode_skip_reason(self) -> str:
        """'' when the family can run speculative draft-and-verify decoding,
        else why not.  The verify pass is a chunk-resume forward (K+1 tokens
        at a nonzero per-row cache offset, ``decode_chunk`` attention) plus
        cursor rollback over a growing KV cache, so the support matrix is
        exactly the chunked-prefill one: rwkv's O(1) recurrent state cannot
        be rolled back by truncating a cursor, hybrid mixes KV with
        recurrent leaves, encoder-only never decodes.  (The int8-quantized
        KV cache is NOT excluded: verify rows attend the same dequantized
        values sequential decode attends.)  Latent attention
        is refused: verify windows over the latent pool are not wired."""
        if self.cfg.attention == "mla":
            return ("latent attention: speculative verify over the latent "
                    "pool is not wired")
        return self.chunked_prefill_skip_reason()

    # -- cache layouts a family refuses ------------------------------------
    def dense_layout_skip_reason(self) -> str:
        """'' when the family serves from dense per-slot cache rows
        (``kv_layout="dense"``), else why not."""
        if self.cfg.attention == "mla":
            from repro.models.transformer import MLA_DENSE_LAYOUT

            return MLA_DENSE_LAYOUT
        return ""

    def kv_int8_skip_reason(self) -> str:
        """'' when the family's cache can hold int8 KV with per-position
        scales (``MeshPlan.cache_quant_int8``), else why not."""
        if self.cfg.attention == "mla":
            from repro.models.transformer import MLA_INT8_KV

            return MLA_INT8_KV
        return ""

    # -- paged KV (serving; see check_paged_cache_contract) -----------------
    @property
    def supports_paged_kv(self) -> bool:
        return self.paged_skip_reason() == ""

    def paged_skip_reason(self) -> str:
        """'' when the family supports the paged-KV serving layout, else why
        not (mirrors ``supports``'s skip-matrix style)."""
        if self.cfg.encoder_only:
            return "encoder-only arch has no decode step"
        if not hasattr(self.module, "init_paged_cache"):
            if self.cfg.rwkv_head_size:
                return ("rwkv state is O(1) in sequence length — there is no "
                        "growing KV cache to page")
            if self.cfg.family == "hybrid":
                return ("hybrid cache mixes attention KV with O(1) ssm/conv "
                        "state; per-leaf paging not wired yet")
            return f"{self.arch_id}: model family has no init_paged_cache"
        return ""

    def init_paged_cache(self, n_blocks: int, block_len: int, plan: MeshPlan,
                         cfg: ModelConfig | None = None):
        reason = self.paged_skip_reason()
        if reason:
            raise NotImplementedError(f"{self.arch_id}: {reason}")
        return self.module.init_paged_cache(
            cfg or self.cfg, n_blocks, block_len, plan
        )

    def abstract_paged_cache(self, n_blocks: int, block_len: int,
                             plan: MeshPlan, cfg: ModelConfig | None = None):
        return jax.eval_shape(
            lambda: self.init_paged_cache(n_blocks, block_len, plan, cfg)
        )

    # -- shape support (DESIGN.md §4 skip matrix) ---------------------------
    def supports(self, shape: ShapeSpec) -> tuple[bool, str]:
        if shape.kind == "decode" and self.cfg.encoder_only:
            return False, "encoder-only arch has no decode step"
        if shape.name == "long_500k" and not self.cfg.is_subquadratic:
            return False, (
                "pure full-attention arch: 500k-token decode requires "
                "sub-quadratic attention (skip noted in DESIGN.md §4)"
            )
        return True, ""


def _module_for(cfg: ModelConfig):
    if cfg.family == "hybrid":
        from repro.models import hybrid

        return hybrid
    if cfg.rwkv_head_size:
        from repro.models import rwkv_model

        return rwkv_model
    from repro.models import transformer

    return transformer


_INPUT_KIND = {
    "hubert-xlarge": "embeds",
    "qwen2-vl-2b": "embeds+mrope",
}


def get_arch(arch_id: str, reduced: bool = False) -> Arch:
    cfg = reduced_config(arch_id) if reduced else get_config(arch_id)
    period = cfg.shared_attention_every or 1
    return Arch(
        arch_id=arch_id,
        cfg=cfg,
        module=_module_for(cfg),
        period=period,
        input_kind=_INPUT_KIND.get(arch_id, "tokens"),
    )


def input_specs(
    arch: Arch,
    shape: ShapeSpec,
    plan: MeshPlan,
    cfg: ModelConfig | None = None,
) -> dict[str, Any]:
    """Abstract (ShapeDtypeStruct) model inputs for one (arch × shape) cell.

    train   → tokens/embeds (+positions) + labels
    prefill → tokens/embeds (+positions)
    decode  → token (B,1) + cache (length = shape.seq_len) + pos (B,)
    """
    cfg = cfg or arch.cfg
    b, s = shape.global_batch, shape.seq_len
    bf16 = jnp.bfloat16

    def sds(shp, dtype, *spec):
        sh = plan.ns(*spec) if plan.mesh is not None else None
        return SDS(shp, dtype, sharding=sh)

    def token_inputs(seq: int) -> dict[str, Any]:
        if arch.input_kind == "tokens":
            return {"tokens": sds((b, seq), jnp.int32, plan.dp, None)}
        out = {"embeds": sds((b, seq, cfg.d_model), bf16, plan.dp, None, None)}
        if arch.input_kind == "embeds+mrope":
            out["positions"] = sds((b, 3, seq), jnp.int32, plan.dp, None, None)
        return out

    if shape.kind == "train":
        specs = token_inputs(s)
        specs["labels"] = sds((b, s), jnp.int32, plan.dp, None)
        return specs

    if shape.kind == "prefill":
        return token_inputs(s)

    # decode: one new token, cache of length s
    specs: dict[str, Any] = {}
    if arch.input_kind == "tokens":
        specs["token"] = sds((b, 1), jnp.int32, plan.dp, None)
    else:
        specs["token"] = sds((b, 1, cfg.d_model), bf16, plan.dp, None, None)
        if arch.input_kind == "embeds+mrope":
            specs["positions"] = sds((b, 3, 1), jnp.int32, plan.dp, None, None)
    specs["pos"] = sds((b,), jnp.int32, plan.dp)
    cache_abs = arch.abstract_cache(b, s, plan, cfg)
    specs["cache"] = cache_shardings(arch, cache_abs, plan, cfg)
    return specs


def check_decode_cache_carry(
    arch: Arch,
    batch: int = 2,
    max_len: int = 8,
    plan: MeshPlan | None = None,
    cfg: ModelConfig | None = None,
) -> None:
    """Assert the scan-carry contract the compiled serving loop relies on:
    one decode step must map the cache pytree to an *identical* pytree
    (same treedef, shapes, dtypes).  Pure ``eval_shape`` — allocates nothing.

    Raises AssertionError with the offending leaf paths on violation.
    """
    plan = plan or MeshPlan()
    cfg = cfg or arch.cfg
    params = arch.abstract_params(cfg)
    cache = arch.abstract_cache(batch, max_len, plan, cfg)
    if arch.input_kind == "tokens":
        tok = SDS((batch, 1), jnp.int32)
        kw = {"tokens": tok}
    else:
        kw = {"embeds": SDS((batch, 1, cfg.d_model), jnp.bfloat16)}
        if arch.input_kind == "embeds+mrope":
            kw["positions"] = SDS((batch, 3, 1), jnp.int32)
    pos = SDS((batch,), jnp.int32)

    def step(params, cache, pos, kw):
        _, new_cache = arch.forward(
            params, plan, cfg=cfg, cache=cache, cache_pos=pos, **kw
        )
        return new_cache

    out = jax.eval_shape(step, params, cache, pos, kw)
    in_leaves, in_tree = jax.tree_util.tree_flatten(cache)
    out_leaves, out_tree = jax.tree_util.tree_flatten(out)
    assert in_tree == out_tree, (
        f"{arch.arch_id}: decode changed the cache treedef\n{in_tree}\n{out_tree}"
    )
    bad = [
        (i, a.shape, a.dtype, b.shape, b.dtype)
        for i, (a, b) in enumerate(zip(in_leaves, out_leaves))
        if a.shape != b.shape or a.dtype != b.dtype
    ]
    assert not bad, f"{arch.arch_id}: decode changed cache leaf specs: {bad}"


CACHE_SLOT_AXIS = 1  # every model family stacks cache leaves (n_layers, B, …)


def write_cache_slot(cache, sub_cache, slot):
    """Write a batch-1 sub-cache into row ``slot`` of a slot cache.

    Contract (``check_slot_cache_contract``): every cache leaf carries the
    batch/slot dimension on axis ``CACHE_SLOT_AXIS``, so a whole request's
    state is one axis-1 row per leaf and admission/retirement is a single
    ``dynamic_update_slice_in_dim`` — no other slot's rows are touched.
    ``slot`` may be a traced scalar (the serving slot-programs jit over it).
    """
    return jax.tree_util.tree_map(
        lambda full, one: jax.lax.dynamic_update_slice_in_dim(
            full, one.astype(full.dtype), slot, axis=CACHE_SLOT_AXIS
        ),
        cache,
        sub_cache,
    )


def check_slot_cache_contract(
    arch: Arch,
    max_len: int = 8,
    plan: MeshPlan | None = None,
    cfg: ModelConfig | None = None,
) -> None:
    """Assert the per-slot cache write/reset contract the continuous-batching
    scheduler relies on: the batch dim of every cache leaf — and ONLY it —
    lives on axis ``CACHE_SLOT_AXIS``.  Verified structurally by diffing
    abstract caches at two batch sizes; pure ``eval_shape``, allocates nothing.
    """
    plan = plan or MeshPlan()
    a, b = 3, 5
    ca = arch.abstract_cache(a, max_len, plan, cfg)
    cb = arch.abstract_cache(b, max_len, plan, cfg)
    la, ta = jax.tree_util.tree_flatten(ca)
    lb, tb = jax.tree_util.tree_flatten(cb)
    assert ta == tb, f"{arch.arch_id}: cache treedef depends on batch size"
    bad = []
    for i, (x, y) in enumerate(zip(la, lb)):
        want = tuple(
            b if d == CACHE_SLOT_AXIS else s for d, s in enumerate(x.shape)
        )
        if x.dtype != y.dtype or y.shape != want or x.shape[CACHE_SLOT_AXIS] != a:
            bad.append((i, x.shape, y.shape))
    assert not bad, (
        f"{arch.arch_id}: cache leaves whose batch dim is not axis "
        f"{CACHE_SLOT_AXIS}: {bad}"
    )


def gather_cache_slots(cache, slots):
    """Gather rows ``slots`` (B,) of a slot cache into a batch-B sub-cache.

    The batched-prefill twin of reading one slot row: the engine's
    ``prefill_slots`` program gathers the B rows it is about to resume,
    runs one chunk forward over them, and scatters the result back with
    ``write_cache_slots``.  ``slots`` may be traced and may contain
    out-of-range ids (the masked dummy rows of a fixed-width launch) —
    those clip to the last slot here and their results are dropped on the
    write side, so the fixed launch shape never retraces."""
    return jax.tree_util.tree_map(
        lambda full: jnp.take(full, slots, axis=CACHE_SLOT_AXIS, mode="clip"),
        cache,
    )


def write_cache_slots(cache, sub_cache, slots):
    """Scatter B updated sub-cache rows back into slots ``slots`` (B,).

    Multi-slot twin of ``write_cache_slot`` (same ``CACHE_SLOT_AXIS``
    contract, checked by ``check_slots_cache_contract``): one scatter per
    leaf installs all B rows in one launch.  Real slot ids are distinct by
    the scheduler contract (one request per slot), hence
    ``unique_indices``; out-of-range ids — the dummy rows that pad a
    bucketed prefill batch up to its fixed width — DROP (``mode="drop"``),
    which is how masked rows write nothing at all."""
    assert CACHE_SLOT_AXIS == 1  # the at[:, slots] indexing below

    def wr(full, rows):
        return full.at[:, slots].set(
            rows.astype(full.dtype), mode="drop", unique_indices=True
        )

    return jax.tree_util.tree_map(wr, cache, sub_cache)


def check_slots_cache_contract(
    arch: Arch,
    n_slots: int = 4,
    chunk: int = 2,
    max_len: int = 8,
    plan: MeshPlan | None = None,
    cfg: ModelConfig | None = None,
) -> None:
    """Assert the multi-slot scatter + chunk-resume contract the batched
    prefill programs rely on.  Pure ``eval_shape`` — allocates nothing.
    Raises NotImplementedError (with ``chunked_prefill_skip_reason``) for
    unsupported families, AssertionError with leaf details otherwise.

    Checked:
      * ``gather_cache_slots`` → ``write_cache_slots`` round-trips the slot
        cache to an *identical* pytree (the donation/in-place contract);
      * a chunk-resume forward — tokens (B, C) with per-row ``cache_pos``
        over the gathered sub-cache — maps the sub-cache to an identical
        pytree and yields (B, C, V) logits;
      * when the family also supports paged KV, the paged twin (same
        forward with a block table over a pool) maps the pool pytree to an
        identical pytree.
    A family that refuses dense slot rows (``dense_layout_skip_reason``)
    is checked on the paged twin alone.
    """
    plan = plan or MeshPlan()
    cfg = cfg or arch.cfg
    reason = arch.chunked_prefill_skip_reason()
    if reason:
        raise NotImplementedError(f"{arch.arch_id}: {reason}")
    b = n_slots - 1  # a partial group, like a real admit round
    params = arch.abstract_params(cfg)
    starts = SDS((b,), jnp.int32)
    if arch.input_kind == "tokens":
        kw: dict[str, Any] = {"tokens": SDS((b, chunk), jnp.int32)}
    else:
        kw = {"embeds": SDS((b, chunk, cfg.d_model), jnp.bfloat16)}
        if arch.input_kind == "embeds+mrope":
            kw["positions"] = SDS((b, 3, chunk), jnp.int32)

    if not arch.dense_layout_skip_reason():
        _check_dense_slots(arch, params, kw, n_slots, b, max_len, plan, cfg)

    if arch.supports_paged_kv:
        block_len = max(max_len // 4, 1)
        mb = max_len // block_len
        pool = arch.abstract_paged_cache(n_slots + 2, block_len, plan, cfg)
        table = SDS((b, mb), jnp.int32)

        def resume_paged(params, pool, starts, table, kw):
            return arch.forward(
                params, plan, cfg=cfg, cache=pool, cache_pos=starts,
                block_table=table, **kw,
            )

        _, new_pool = jax.eval_shape(
            resume_paged, params, pool, starts, table, kw
        )
        _assert_same_pytree(arch, pool, new_pool, "paged chunk-resume forward")


def _assert_same_pytree(arch: Arch, a, c, what: str) -> None:
    la, ta = jax.tree_util.tree_flatten(a)
    lc, tc = jax.tree_util.tree_flatten(c)
    assert ta == tc, f"{arch.arch_id}: {what} changed the cache treedef"
    bad = [
        (i, x.shape, x.dtype, y.shape, y.dtype)
        for i, (x, y) in enumerate(zip(la, lc))
        if x.shape != y.shape or x.dtype != y.dtype
    ]
    assert not bad, f"{arch.arch_id}: {what} changed leaf specs: {bad}"


def _check_dense_slots(arch, params, kw, n_slots, b, max_len, plan,
                       cfg) -> None:
    """The dense slot-row half of ``check_slots_cache_contract``."""
    cache = arch.abstract_cache(n_slots, max_len, plan, cfg)
    slots = SDS((b,), jnp.int32)

    def roundtrip(cache, slots):
        small = gather_cache_slots(cache, slots)
        return write_cache_slots(cache, small, slots), small

    out, small = jax.eval_shape(roundtrip, cache, slots)
    _assert_same_pytree(arch, cache, out, "slot gather/scatter round-trip")
    for i, leaf in enumerate(jax.tree_util.tree_leaves(small)):
        assert leaf.shape[CACHE_SLOT_AXIS] == b, (
            f"{arch.arch_id}: gathered sub-cache leaf {i} batch dim is "
            f"{leaf.shape} (want {b} on axis {CACHE_SLOT_AXIS})"
        )

    starts = SDS((b,), jnp.int32)
    chunk = next(iter(kw.values())).shape[1]

    def resume(params, small, starts, kw):
        return arch.forward(
            params, plan, cfg=cfg, cache=small, cache_pos=starts, **kw
        )

    logits, new_small = jax.eval_shape(resume, params, small, starts, kw)
    _assert_same_pytree(arch, small, new_small, "chunk-resume forward")
    assert logits.shape == (b, chunk, cfg.vocab_size), (
        f"{arch.arch_id}: chunk-resume logits shape {logits.shape}"
    )


CACHE_BLOCK_AXIS = 1  # paged pools put the physical-block axis where the
#                       dense slot layout puts the slot axis


def check_paged_cache_contract(
    arch: Arch,
    n_slots: int = 2,
    block_len: int = 4,
    max_blocks: int = 3,
    plan: MeshPlan | None = None,
    cfg: ModelConfig | None = None,
) -> None:
    """Assert the paged-KV contract the serving stack relies on.  Pure
    ``eval_shape`` — allocates nothing.  Raises NotImplementedError (with the
    family's ``paged_skip_reason``) for unsupported cells, AssertionError
    with leaf details on a structural violation.

    Checked:
      * pool leaves carry the block axis on ``CACHE_BLOCK_AXIS`` and the
        in-block position axis right after it (diffed at two pool sizes);
      * one paged decode step (forward with a block table) maps the pool
        pytree to an *identical* pytree — the scan/donation carry contract.
    """
    plan = plan or MeshPlan()
    cfg = cfg or arch.cfg
    reason = arch.paged_skip_reason()
    if reason:
        raise NotImplementedError(f"{arch.arch_id}: {reason}")
    a, b = 5, 7
    la, ta = jax.tree_util.tree_flatten(
        arch.abstract_paged_cache(a, block_len, plan, cfg))
    lb, tb = jax.tree_util.tree_flatten(
        arch.abstract_paged_cache(b, block_len, plan, cfg))
    assert ta == tb, f"{arch.arch_id}: pool treedef depends on n_blocks"
    bad = []
    for i, (x, y) in enumerate(zip(la, lb)):
        want = tuple(
            b if d == CACHE_BLOCK_AXIS else s for d, s in enumerate(x.shape)
        )
        if (x.dtype != y.dtype or y.shape != want
                or x.shape[CACHE_BLOCK_AXIS] != a
                or x.shape[CACHE_BLOCK_AXIS + 1] != block_len):
            bad.append((i, x.shape, y.shape))
    assert not bad, (
        f"{arch.arch_id}: pool leaves whose block axis is not axis "
        f"{CACHE_BLOCK_AXIS} (or block_len not on axis "
        f"{CACHE_BLOCK_AXIS + 1}): {bad}"
    )

    params = arch.abstract_params(cfg)
    pool = arch.abstract_paged_cache(a, block_len, plan, cfg)
    table = SDS((n_slots, max_blocks), jnp.int32)
    pos = SDS((n_slots,), jnp.int32)
    if arch.input_kind == "tokens":
        kw = {"tokens": SDS((n_slots, 1), jnp.int32)}
    else:
        kw = {"embeds": SDS((n_slots, 1, cfg.d_model), jnp.bfloat16)}
        if arch.input_kind == "embeds+mrope":
            kw["positions"] = SDS((n_slots, 3, 1), jnp.int32)

    def step(params, pool, pos, table, kw):
        _, new_pool = arch.forward(
            params, plan, cfg=cfg, cache=pool, cache_pos=pos,
            block_table=table, **kw,
        )
        return new_pool

    out = jax.eval_shape(step, params, pool, pos, table, kw)
    in_leaves, in_tree = jax.tree_util.tree_flatten(pool)
    out_leaves, out_tree = jax.tree_util.tree_flatten(out)
    assert in_tree == out_tree, (
        f"{arch.arch_id}: paged decode changed the pool treedef"
    )
    bad = [
        (i, x.shape, x.dtype, y.shape, y.dtype)
        for i, (x, y) in enumerate(zip(in_leaves, out_leaves))
        if x.shape != y.shape or x.dtype != y.dtype
    ]
    assert not bad, f"{arch.arch_id}: paged decode changed pool leaf specs: {bad}"


def cache_shardings(arch: Arch, cache_abs, plan: MeshPlan, cfg: ModelConfig):
    """Attach NamedShardings to an abstract cache pytree."""
    if plan.mesh is None:
        return cache_abs
    cspec = plan.cache_spec()

    def shard_leaf(path: str, leaf: SDS) -> SDS:
        nd = len(leaf.shape)
        if "scale" in path:  # int8-cache scales (L, B, S, KH)
            spec = (None, *cspec[:3])
        elif "attn" in path or path in ("k", "v"):
            spec = (None, *cspec)  # (L/n_inv, B, S, KH, Dh)
        elif "ssm" in path:  # (L, B, H, N, P): heads over tp when divisible
            h = leaf.shape[2]
            tp_ok = h % plan.tp_size == 0
            spec = (None, plan.dp, plan.tp if tp_ok else None, None, None)
        elif "conv" in path:  # (L, B, W-1, conv_dim)
            spec = (None, plan.dp, None, plan.tp)
        elif "wkv" in path:  # (L, B, H, n, n): shard key-dim (n % tp varies)
            spec = (None, plan.dp, None, None, None)
        elif "shift" in path:  # (L, B, d)
            spec = (None, plan.dp, None)
        else:
            spec = tuple([None] * nd)
        spec = tuple(spec[:nd]) + (None,) * (nd - len(spec))
        # divisibility guard: drop axis entries that don't divide
        fixed = []
        for dim, entry in zip(leaf.shape, spec):
            if entry is None:
                fixed.append(None)
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            size = 1
            for a in axes:
                size *= plan.mesh.shape[a]
            fixed.append(entry if dim % size == 0 else None)
        return SDS(leaf.shape, leaf.dtype, sharding=plan.ns(*fixed))

    from repro.utils.tree import tree_map_with_path_names

    return tree_map_with_path_names(shard_leaf, cache_abs)


def live_cells(arch_ids=None, shapes=None) -> list[tuple[str, str]]:
    """All (arch_id, shape_name) pairs that are not skipped."""
    from repro.configs.base import ALL_ARCH_IDS, SHAPES

    out = []
    for aid in arch_ids or ALL_ARCH_IDS:
        arch = get_arch(aid)
        for sname in shapes or SHAPES:
            ok, _ = arch.supports(SHAPES[sname])
            if ok:
                out.append((aid, sname))
    return out


def skip_reason(arch_id: str, shape_name: str) -> str:
    from repro.configs.base import SHAPES

    ok, reason = get_arch(arch_id).supports(SHAPES[shape_name])
    return "" if ok else reason
