"""Moonlight-16B-A3B as one chip's EP4 share (``moonlight.decode``): its
reference against the program on seeded weights at tiny widths, the work
counts pinned at the published widths, the correction bias's effect, and
the file checks, all on the CPU."""
import dataclasses
import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run, spec, tiny_cell, work

CELL = "moonlight.decode"


@pytest.fixture(scope="module")
def config():
    return spec.load_cell(CELL).config


@pytest.fixture(scope="module")
def ref(config):
    return spec.reference_module(config)


def tiny_config(config: dict, offset: int = 0) -> dict:
    """The cell's file at tiny widths: 1 dense and 2 MoE layers, 2 of 8
    experts held from ``offset``, top-3, float32 weights drawn at 64^-1/2."""
    return {**config, "num_hidden_layers": 3, "hidden_size": 64,
            "num_attention_heads": 4, "num_key_value_heads": 4,
            "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
            "v_head_dim": 16, "intermediate_size": 128,
            "moe_intermediate_size": 32, "n_routed_experts": 2,
            "published": {"n_routed_experts": 8}, "num_experts_per_tok": 3,
            "vocab_size": 256, "deployment": {"expert_offset": offset},
            "weights": {**config["weights"], "dtype": "float32",
                        "init_std": 0.125}}


def test_committed_file_is_the_preset(config, ref):
    """The program's config built from the file is the arch's own preset:
    every width as published, 16 of 64 experts held from 0."""
    from repro.configs.base import get_config

    cfg = run.program_config(config, ref)
    assert cfg == get_config("moonlight-16b-a3b")
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_offset,
            cfg.experts_per_token) == (64, 16, 0, 6)
    assert config["reduced"] == ["n_routed_experts"]
    assert config["published"] == {"n_routed_experts": 64}


def test_work_counts_are_pinned(config, ref):
    s = ref.work_shapes(config)
    mla = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    dense = mla + 3 * 2048 * 11264
    shared, expert, router = 3 * 2048 * 2816, 3 * 2048 * 1408, 2048 * 64
    assert mla == 13_762_560 and dense == 82_968_576
    assert s.layer_weights == dense + 26 * (mla + router + shared + 16 * expert)
    assert s.layer_weights == 4_492_754_944
    assert s.token_weights == dense + 26 * (mla + router + shared
                                            + 16 * expert * 6 / 64)
    assert s.token_weights == 1_231_421_440
    assert s.head_weights == 335_544_320
    assert s.kv_bytes_per_token == 27 * 576 * 2 == 31_104
    assert s.attn_flops_per_pair == 27 * (4 * 16 * 512 + 2 * 16 * 64)
    assert s.attn_flops_per_pair == 27 * 34_816
    # one token reads each held expert with probability 6/64; 32 read 96 %
    for t in (1, 32):
        p = 1 - (1 - 6 / 64) ** t
        want = 2 * (dense + 26 * (mla + router + shared + 16 * expert * p)
                    + 335_544_320)
        assert s.step_weight_bytes(t) == pytest.approx(want, rel=1e-12)
    assert 2 * 26 * 16 * expert * (1 - (1 - 6 / 64) ** 32) == pytest.approx(
        6.889e9, rel=1e-3)
    flops, _ = work.decode(s, 1, [100])
    assert flops == 2 * (1_231_421_440 + 335_544_320) + 27 * 34_816 * 100


def test_memory_the_file_states_is_the_shapes(config, ref):
    s = ref.work_shapes(config)
    mem = config["memory"]
    embed_head = 2 * 2 * 2048 * 163840
    assert mem["embedding_and_head_bytes"] == embed_head
    assert mem["weights_bytes"] == 2 * s.layer_weights + embed_head
    serving = spec.load_cell(CELL).traffic["serving"]
    blocks = serving["pool_blocks"] + serving["slots"]
    assert mem["latent_pool_bytes"] == (s.kv_bytes_per_token * blocks
                                        * serving["block_len"])
    assert mem["resident_bytes"] == mem["weights_bytes"] + mem["latent_pool_bytes"]


def test_a_file_the_reference_does_not_compute_is_refused(config, ref):
    for key, value in (("scoring_func", "softmax"), ("first_k_dense_replace", 3),
                       ("n_shared_experts", 1), ("q_lora_rank", 1536),
                       ("hidden_act", "gelu")):
        with pytest.raises(ValueError, match="not the model this reference"):
            run.program_config({**config, key: value}, ref)
    # the GQA softmax-routed preset is not the model either, before weights
    with pytest.raises(ValueError, match="is not the model mla_moe_sigmoid"):
        run.program_config({**config, "arch_id": "moonshot-v1-16b-a3b"}, ref)


def test_correction_bias_changes_some_choices(config, ref):
    """At the published router width the assumed bias scale moves some
    tokens' experts, and leaves most of them."""
    m = ref.model_from_config(config)
    kw, kb, kh = jax.random.split(jax.random.PRNGKey(0), 3)
    router = {"kernel": (jax.random.normal(kw, (m.d, m.experts)) * m.init_std
                         ).astype(jnp.bfloat16),
              "score_bias": m.score_bias_std * jax.random.normal(kb, (m.experts,))}
    h = jax.random.normal(kh, (512, m.d))
    _, with_bias = ref.route(router, h, m)
    _, without = ref.route({**router, "score_bias": jnp.zeros(m.experts)}, h, m)
    changed = (jnp.sort(with_bias, -1) != jnp.sort(without, -1)).any(-1).mean()
    assert 0.05 < float(changed) < 0.95, float(changed)


def test_served_tokens_match_the_reference(config, ref):
    """Chunked prefill and decode segments through ``ContinuousScheduler``
    over the latent pool, the program in fp32 on the reference's seeded
    weights: every served token is the reference's best at its position,
    but for a tie within 1e-4."""
    from repro.models.registry import get_arch
    from repro.serve import ContinuousScheduler, ServeConfig, ServeEngine
    from repro.sharding.mesh import MeshPlan

    tiny = tiny_config(config, offset=2)
    m = ref.model_from_config(tiny)
    params = ref.make_params(m, jax.random.PRNGKey(3))
    cfg = run.program_config(tiny, ref).replace(compute_dtype="float32")
    arch = dataclasses.replace(get_arch(tiny["arch_id"]), cfg=cfg)
    eng = ServeEngine(arch, params, MeshPlan(), ServeConfig(
        max_len=64, kv_layout="paged", block_len=8, eos_token=-1))
    sched = ContinuousScheduler(eng, n_slots=3, segment_len=4,
                                segment_mode="while", n_blocks=24,
                                prefill_chunk=8, prefill_buckets=2)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (5, 19, 12, 3)]
    handles = [sched.submit(p, 10) for p in prompts]
    sched.run()
    assert sched.stats["moe_rows_held"] > 0
    logits = jax.jit(lambda p, t: ref.logits(p, t, m))
    for p, h in zip(prompts, handles):
        full = np.concatenate([p, np.asarray(h.tokens, np.int32)])
        lg = np.asarray(logits(params, jnp.asarray(full[:-1])))
        served = lg[np.arange(len(p) - 1, len(full) - 1), full[len(p):]]
        gap = lg[len(p) - 1:].max(-1) - served
        assert gap.max() <= 1e-4, gap


def test_gaps_score_the_head_in_blocks(config, ref):
    """``gaps`` over vocabulary blocks equals the gaps of the whole logits,
    and the int8 control reads a positive gap."""
    tiny = {**tiny_config(config), "vocab_size": 4 * ref.VOCAB_BLOCK}
    m = ref.model_from_config(tiny)
    params = ref.make_params(m, jax.random.PRNGKey(5))
    toks = jax.random.randint(jax.random.PRNGKey(6), (16,), 0, m.vocab)
    tgts = jnp.roll(toks, -1)
    gap, ctrl = jax.jit(lambda p, a, b: ref.gaps(p, a, b, m, 8))(params, toks, tgts)
    lg = ref.logits(params, toks, m)
    want = lg.max(-1) - jnp.take_along_axis(lg, tgts[:, None], -1)[:, 0]
    np.testing.assert_allclose(np.asarray(gap), np.asarray(want), atol=1e-5)
    assert float(ctrl.max()) >= 0 and float(ctrl.sum()) > 0


def test_tiny_cell_runs_correct_and_reads_its_expert_rows(tmp_path, config):
    """A traced run of the cell at tiny widths on the CPU: correct, and the
    expert layer's useful-row share is read from the program's counters."""
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "checks", "references"):
        (bench / sub).mkdir(parents=True)
    shutil.copy(spec.BENCH_DIR / "references" / "mla_moe_sigmoid.py",
                bench / "references")
    (bench / "configs" / "tiny.json").write_text(json.dumps(tiny_config(config)))
    (bench / "traffic" / "tiny_mix.json").write_text(
        json.dumps(tiny_cell.traffic("closed")))
    (bench / "checks" / "tiny.cell.json").write_text(
        json.dumps({"number": "mean_gap", "limit": 0.01}))
    per_layer = json.loads((spec.ROOT / "BENCHMARK.json").read_text())["per_layer"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": "tiny.cell", "config": "tiny",
                       "traffic": "tiny_mix", "chips": 1}],
        "end_to_end": [{"name": "output_tok_s", "unit": "tokens/s"}],
        "per_layer": [{k: v for k, v in m.items() if k != "workloads"}
                      for m in per_layer if m["name"] == "moe.useful_rows_pct"],
    }))
    cell = spec.load_cell("tiny.cell", root=tmp_path, bench_dir=bench)
    out = run.run_cell(cell, 2**31 + 9, 2.0, True, trace_dir=tmp_path / "trace",
                       trace_span=(0.5, 1.0), peaks=tiny_cell.PEAKS)
    assert out["correct"], out["checks"]
    rows = out["metrics"]["moe.useful_rows_pct"]["value"]
    assert 0 < rows <= 100, rows
