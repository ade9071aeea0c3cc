"""Latent attention (MLA) over the latent paged pool and the drop-free
held-expert layer, at tiny widths on the CPU: absorbed attention equals the
expanded form, decode over the pool equals the full forward, the expert
shares add up to the uncut layer, a token's output does not depend on its
launch, the all-held and grouped forms agree and the launch's size picks
between them, pool writes touch only their own positions, the held-row
counters, and the layouts and paths the family refuses."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L
from repro.models import moe as M
from repro.models.registry import get_arch
from repro.serve import ContinuousScheduler, ServeConfig, ServeEngine, SpecConfig
from repro.sharding.mesh import MeshPlan

PLAN = MeshPlan()
MAX_LEN, BLOCK_LEN = 32, 4
N_SLOTS, N_ALLOC = 3, 10


def _arch(**kw):
    arch = get_arch("moonlight-16b-a3b", reduced=True)
    return dataclasses.replace(arch, cfg=arch.cfg.replace(**kw))


@pytest.fixture(scope="module")
def f32_model():
    """Tiny Moonlight computing in fp32, every expert held, nonzero
    correction biases."""
    arch = _arch(compute_dtype="float32", experts_held=0)
    params = arch.init_params(jax.random.PRNGKey(0))
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(9),
                                    params["layers"]["moe"]["router"]["score_bias"].shape)
    params["layers"]["moe"]["router"]["score_bias"] = bias
    return arch, params


def _expanded_mla(p, cfg, x):
    """Latent attention with W_kvb expanded into per-head keys and values
    (the published form), fp32, one sequence x (S, D)."""
    s = x.shape[0]
    h, r = cfg.n_heads, cfg.mla_kv_rank
    dn, dr, dv = cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim
    pos = jnp.arange(s)[None]
    q = (x @ p["wq"]["kernel"]).reshape(1, s, h, dn + dr)
    kv = x @ p["wkv_a"]["kernel"]
    c = L.norm_apply(p["kv_norm"], kv[:, :r], L.MLA_KV_NORM_EPS)
    ang = L.rope_angles(cfg, pos, dim=dr)
    q_pe = L.apply_rope(q[..., dn:], ang)[0]
    k_pe = L.apply_rope(kv[None, :, None, r:], ang)[0, :, 0]
    kvb = (c @ p["wkv_b"]["kernel"]).reshape(s, h, dn + dv)
    sc = (jnp.einsum("qhn,shn->hqs", q[0, ..., :dn], kvb[..., :dn])
          + jnp.einsum("qhr,sr->hqs", q_pe, k_pe)) / np.sqrt(dn + dr)
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    o = jnp.einsum("hqs,shv->qhv", jax.nn.softmax(sc, -1), kvb[..., dn:])
    return o.reshape(s, h * dv) @ p["wo"]["kernel"]


def _layer(params, i=0):
    return jax.tree_util.tree_map(lambda a: a[i], params["layers"])


def test_absorbed_attention_equals_expanded(f32_model):
    arch, params = f32_model
    cfg = arch.cfg
    p = _layer(params)["attn"]
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 12, cfg.d_model))
    pos = jnp.arange(12)[None]
    with jax.default_matmul_precision("highest"):
        got, _ = L.mla_apply(p, cfg, x, pos)
        want = _expanded_mla(p, cfg, x[0])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def _pool_forward(arch, params, toks, chunk):
    """Chunked prefill of ``toks[:, :P]`` then one-token decodes over a
    latent pool; returns the logits of every position."""
    b, total = toks.shape
    mb = MAX_LEN // BLOCK_LEN
    # row i maps blocks b + i·mb … (ids 0..b−1 are the scratch blocks)
    table = jnp.asarray(b + np.arange(b * mb).reshape(b, mb), jnp.int32)
    # an fp32 pool for the fp32 model (serving pools are bf16)
    pool = arch.module.init_paged_cache(arch.cfg, b + b * mb, BLOCK_LEN, PLAN,
                                        dtype=jnp.float32)
    out = []
    start = 0
    for n in chunk:
        lg, pool = arch.forward(params, PLAN, tokens=toks[:, start:start + n],
                                cache=pool, block_table=table,
                                cache_pos=jnp.full((b,), start, jnp.int32))
        out.append(lg)
        start += n
    for t in range(start, total):
        lg, pool = arch.forward(params, PLAN, tokens=toks[:, t:t + 1],
                                cache=pool, block_table=table,
                                cache_pos=jnp.full((b,), t, jnp.int32))
        out.append(lg)
    return jnp.concatenate(out, axis=1)


def test_paged_prefill_and_absorbed_decode_equal_the_full_forward(f32_model):
    """Chunk-resume prefill over the latent pool, then decode steps that
    read only the pool, give the full forward's logits at every position."""
    arch, params = f32_model
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 14), 0,
                              arch.cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        want, _ = arch.forward(params, PLAN, tokens=toks)
        got = _pool_forward(arch, params, toks, chunk=(4, 4))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def _moe_cfg(**kw):
    return _arch(compute_dtype="float32", **kw).cfg


@pytest.fixture(params=["all_held", "grouped"])
def form(request, monkeypatch):
    """Each form of the held experts, forced through the bound on the
    launch's token count (the tests' launches all lie below it)."""
    if request.param == "grouped":
        monkeypatch.setattr(M, "ALL_HELD_MAX_TOKENS", 0)
    return request.param


def test_expert_shares_add_up_to_the_uncut_layer(f32_model, form):
    """Four devices' shares of 2 experts each: their outputs, with the
    shared expert (which every device computes) counted once, add up to the
    layer that holds all 8."""
    assert M.takes_all_held(14) == (form == "all_held")
    _, params = f32_model
    p = _layer(params)["moe"]
    full = _moe_cfg(experts_held=0)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 7, full.d_model))
    with jax.default_matmul_precision("highest"):
        whole, _ = M.moe_held_apply(p, full, x)
        shared = L.ffn_apply(p["shared"], full, x)
        parts = []
        for i in range(4):
            cfg = _moe_cfg(experts_held=2, expert_offset=2 * i)
            share = {**p, **{k: p[k][2 * i:2 * i + 2] for k in ("wi", "wg", "wo")}}
            parts.append(M.moe_held_apply(share, cfg, x)[0])
    np.testing.assert_allclose(np.asarray(sum(parts) - 3 * shared),
                               np.asarray(whole), rtol=1e-4, atol=1e-5)


def test_a_tokens_output_does_not_depend_on_its_launch(form):
    """Drop-free: within one form, each token's output is bitwise the same
    launched alone as launched with 63 others (which crowd its experts)."""
    assert M.takes_all_held(64) == (form == "all_held")
    arch = _arch()
    p = _layer(arch.init_params(jax.random.PRNGKey(4)))["moe"]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 64, arch.cfg.d_model),
                          jnp.bfloat16)
    together, _ = M.moe_held_apply(p, arch.cfg, x)
    for t in (0, 17, 63):
        alone, _ = M.moe_held_apply(p, arch.cfg, x[:, t:t + 1])
        np.testing.assert_array_equal(np.asarray(alone[0, 0], np.float32),
                                      np.asarray(together[0, t], np.float32))


@pytest.mark.parametrize("t", [1, 5, 33])
def test_the_all_held_form_equals_the_grouped_form(f32_model, t):
    """At fp32 and ``highest`` the two forms give the same rows: tokens
    whose choices are all held elsewhere get zeros, and experts that no
    token chose change nothing."""
    _, params = f32_model
    p = _layer(params)["moe"]
    e, k = p["wi"].shape[0], 3
    x = jax.random.normal(jax.random.PRNGKey(10), (t, p["wi"].shape[1]))
    # experts 2 and 5 get no rows; -2, -1, e and e + 1 are held elsewhere
    pool = np.array([0, 1, 3, 4, 6, 7, -2, -1, e, e + 1])
    rng = np.random.default_rng(t)
    local = np.stack([rng.permutation(pool)[:k] for _ in range(t)])
    local[0] = (-1, e, e + 1)
    local = jnp.asarray(local, jnp.int32)
    held = (local >= 0) & (local < e)
    with jax.default_matmul_precision("highest"):
        every = M._held_rows_all(p, x, local, held)
        grouped = M._held_rows_grouped(p, x, local, held)
    assert every.shape == grouped.shape == (t, k, x.shape[1])
    np.testing.assert_array_equal(np.asarray(every[0]), 0)
    # rows near zero are held to 1e-5 of the rows' scale
    grouped = np.asarray(grouped)
    np.testing.assert_allclose(np.asarray(every), grouped, rtol=1e-5,
                               atol=1e-5 * np.abs(grouped).max())


def test_the_form_is_chosen_by_the_launchs_token_count():
    bound = M.ALL_HELD_MAX_TOKENS
    assert bound == 128
    assert M.takes_all_held(1) and M.takes_all_held(bound)
    assert not M.takes_all_held(bound + 1)
    arch = _arch()
    eng = _engine(arch, arch.init_params(jax.random.PRNGKey(11)))
    assert eng.moe_tokens_all_held(bound) == bound
    assert eng.moe_tokens_all_held(bound + 1) == 0


def test_held_count_is_the_real_tokens_assignments_here():
    arch = _arch()
    cfg = arch.cfg
    p = _layer(arch.init_params(jax.random.PRNGKey(6)))["moe"]
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 5, cfg.d_model))
    mask = jnp.asarray([[1, 1, 1, 0, 0], [1, 0, 0, 0, 0]], bool)
    _, n = M.moe_held_apply(p, cfg, x, mask)
    _, experts = M.route_sigmoid_bias(p, cfg, x.reshape(10, -1))
    held = np.asarray((experts >= cfg.expert_offset)
                      & (experts < cfg.expert_offset + cfg.experts_held))
    assert int(n) == held[np.asarray(mask).reshape(-1)].sum()
    assert M.held_rows_launched(cfg, 10) == 2 * 10 * 3  # 2 MoE layers, top-3


# ------------------------------------------------------- serving path


def _engine(arch, params, **kw):
    sc = ServeConfig(max_len=MAX_LEN, kv_layout="paged", block_len=BLOCK_LEN,
                     **kw)
    return ServeEngine(arch, params, PLAN, sc)


@pytest.mark.parametrize("arch_id", ["moonlight-16b-a3b", "tinyllama-1.1b"])
def test_scheduler_counts_held_and_launched_rows(arch_id):
    """With every expert held here, each real token's k assignments are
    held: the on-device count equals the host's count of real tokens, and
    the launched rows count every launched token.  A dense FFN counts as
    an expert layer holding its one expert (a row per token and layer)."""
    arch = get_arch(arch_id, reduced=True)
    if arch.cfg.n_experts:
        arch = _arch(experts_held=0)
        per_token = (arch.cfg.n_layers - 1) * arch.cfg.experts_per_token
    else:
        per_token = arch.cfg.n_layers
    eng = _engine(arch, arch.init_params(jax.random.PRNGKey(8)))
    sched = ContinuousScheduler(eng, n_slots=N_SLOTS, segment_len=3,
                                segment_mode="while", n_blocks=N_ALLOC * 2,
                                prefill_chunk=8, prefill_buckets=2)
    for i, n in enumerate((5, 11, 3, 9)):
        sched.submit(np.arange(n, dtype=np.int32) + i, 6)
    sched.run()
    st = sched.stats
    assert st["moe_rows_held"] == per_token * (
        st["prefill_tokens_real"] + st["slot_steps_live"])
    assert st["moe_rows_computed"] == per_token * (
        st["prefill_tokens_launched"] + N_SLOTS * st["steps_total"])
    # 8-token chunks (at most 4 wide) and 3-slot segments: every launch
    # lies below the bound, so a held-expert model counts every token
    assert st["moe_tokens_all_held"] == (
        st["prefill_tokens_launched"] + N_SLOTS * st["steps_total"]
        if arch.cfg.n_experts else 0)


def test_per_request_admission_prefills_into_the_pool(f32_model):
    """Without chunked prefill a request prefills straight into its mapped
    blocks, as every paged family does; its tokens equal the chunked
    path's."""
    arch, params = f32_model
    prompt = np.arange(7, dtype=np.int32) * 3
    out = []
    for chunk in (0, 4):
        sched = ContinuousScheduler(_engine(arch, params), n_slots=2,
                                    segment_len=4, n_blocks=N_ALLOC,
                                    prefill_chunk=chunk, prefill_buckets=2)
        h = sched.submit(prompt, 6)
        sched.run()
        out.append(h.tokens)
        assert sched.stats["moe_rows_held"] > 0
    assert out[0] == out[1]


def test_refused_layouts_and_paths_say_why():
    arch = _arch()
    params = arch.init_params(jax.random.PRNGKey(0))
    assert "latent" in arch.spec_decode_skip_reason()
    eng = _engine(arch, params, spec=SpecConfig(k=2))
    assert eng.spec is None and "latent" in eng.spec_skip_reason
    with pytest.raises(NotImplementedError, match="paged latent pool only"):
        ServeEngine(arch, params, PLAN, ServeConfig(max_len=MAX_LEN))
    with pytest.raises(NotImplementedError, match="int8 KV"):
        ServeEngine(arch, params, MeshPlan(cache_quant_int8=True),
                    ServeConfig(max_len=MAX_LEN, kv_layout="paged",
                                block_len=BLOCK_LEN))
    with pytest.raises(NotImplementedError, match="int8 weights"):
        _engine(arch, params, weight_quant="int8")
    with pytest.raises(NotImplementedError, match="paged latent pool only"):
        arch.init_cache(2, MAX_LEN, PLAN)


def _random_pool(pool, seed):
    return {k: jax.random.normal(jax.random.PRNGKey(seed), a.shape, a.dtype)
            for k, a in pool.items()}


def _bits(a):
    a = np.asarray(a)
    return a.view(f"u{a.dtype.itemsize}")


@pytest.mark.parametrize("program", ["prefill_slots_paged",
                                     "slot_segment_while_paged"])
def test_latent_pool_writes_touch_only_their_own_positions(program):
    """One chunk-prefill launch (with bucket-padding spill ids and a dummy
    row) or one decode segment: every position of the latent pool that the
    launch does not own is bitwise unchanged in every layer, and every one
    it owns was written."""
    arch = _arch()
    eng = _engine(arch, arch.init_params(jax.random.PRNGKey(10)))
    mb, n_blocks = eng.max_blocks_per_slot, N_SLOTS + N_ALLOC
    pool = _random_pool(eng.init_paged_cache(N_ALLOC, N_SLOTS), 11)
    old = _bits(pool["latent"])
    state = (jnp.zeros((N_SLOTS,), jnp.int32), jnp.zeros((N_SLOTS,), jnp.int32),
             jnp.zeros((N_SLOTS,), bool))
    key = jax.random.PRNGKey(12)
    written = np.zeros((n_blocks, BLOCK_LEN), bool)
    if program == "prefill_slots_paged":
        width, chunk = 4, 8
        bt = n_blocks + np.arange(width * mb, dtype=np.int32).reshape(width, mb)
        bt[0, :2] = [3, 4]  # slot 0 resumes at 4 with 6 real tokens: 4..9
        bt[1, :1] = [7]  # slot 2: 0..3, its padding spills out of range
        slots = np.array([0, 2, N_SLOTS, N_SLOTS + 1], np.int32)
        starts = np.array([4, 0, 0, 0], np.int32)
        last = np.array([5, 3, 0, 0], np.int32)
        prompts = jnp.ones((width, chunk), jnp.int32)
        new, *_ = eng._prefill_slots_paged(
            eng.params, pool, *state, prompts, jnp.asarray(slots),
            jnp.asarray(starts), jnp.asarray(last), jnp.asarray(bt), key)
        # slot 0 writes 4..11 (8..11 beyond its 2 blocks drop), slot 2 0..7
        for row, first, end in ((0, 4, 12), (1, 0, 8)):
            for q in range(first, end):
                phys = bt[row, q // BLOCK_LEN]
                if phys < n_blocks:
                    written[phys, q % BLOCK_LEN] = True
        assert written.sum() == 4 + 4
    else:
        table = np.repeat(np.arange(N_SLOTS, dtype=np.int32)[:, None], mb, 1)
        table[0, :2], table[1, :1] = [3, 4], [5]
        pos = np.array([5, 1, 0], np.int32)
        active = jnp.asarray([True, True, False])
        new, *_ = eng._slot_segment_while_paged(
            2, eng.params, pool, state[0], jnp.asarray(pos), state[2], key,
            active, jnp.full((N_SLOTS,), 20, jnp.int32), jnp.bool_(False),
            jnp.asarray(table))[1:]
        # two steps: slot 0 at 5, 6 (block 4), slot 1 at 1, 2 (block 5);
        # idle slot 2 writes its frozen position 0 into its scratch block
        for blk, offs in ((4, (1, 2)), (5, (1, 2)), (2, (0,))):
            written[blk, list(offs)] = True
    got = _bits(new["latent"])
    np.testing.assert_array_equal(got[:, ~written], old[:, ~written])
    assert (got[:, written] != old[:, written]).any(axis=-1).all()
