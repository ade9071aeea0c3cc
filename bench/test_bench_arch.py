"""A configuration of another architecture joins the benchmark as files
only: its reference module states the program's config and the work (each
layer group's matrices, KV, attention FLOPs a pair and, where its layers
attend a sliding window, that window), and the harness reads both without
an edit to any file of ``bench/``."""
import json
import textwrap
from types import SimpleNamespace

import pytest

from bench import run, spec, tiny_cell, work

REQUIRED = ("model_from_config", "make_params", "gaps", "program_fields",
            "PROGRAM_REQUIRES", "work_shapes")

# one dense layer, then two layers whose FFN is a router and 4 experts, one
# of which each token multiplies; attention is q and o only, and a token
# stores 6 latent values a layer
TOY_REFERENCE = '''
"""Toy reference: a dense layer, then layers of routed experts."""
from bench import work

PROGRAM_REQUIRES = {"family": "moe"}


def model_from_config(config):
    return (config["num_hidden_layers"], config["hidden_size"])


def make_params(model, key):
    raise AssertionError("weights made")


def gaps(params, tokens, targets, model, control_bits=0):
    raise AssertionError("not checked here")


def program_fields(config):
    return dict(n_layers=config["num_hidden_layers"],
                d_model=config["hidden_size"],
                n_heads=config["num_attention_heads"],
                n_kv_heads=config["num_attention_heads"],
                head_dim=config["head_dim"],
                d_ff=config["moe_intermediate_size"],
                vocab_size=config["vocab_size"],
                n_experts=config["n_routed_experts"],
                experts_per_token=config["num_experts_per_tok"])


def work_shapes(config):
    d, fd = config["hidden_size"], config["intermediate_size"]
    e, f = config["n_routed_experts"], config["moe_intermediate_size"]
    share = config["num_experts_per_tok"] / e
    attn = ((d, d, 1.0), (d, d, 1.0))
    dense = work.LayerGroup(
        layers=config["first_k_dense_replace"],
        mats=attn + ((d, fd, 1.0), (fd, d, 1.0)),
        kv_per_token=config["kv_lora_rank"], attn_flops_per_pair=20)
    moe = work.LayerGroup(
        layers=config["num_hidden_layers"] - config["first_k_dense_replace"],
        mats=attn + ((d, e, 1.0), (d, e * f, share), (e * f, d, share)),
        kv_per_token=config["kv_lora_rank"], attn_flops_per_pair=20)
    return work.Shapes(groups=(dense, moe), d=d, vocab=config["vocab_size"])
'''

TOY_CONFIG = {
    "source": "test", "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "hidden_size": 8, "num_attention_heads": 2, "head_dim": 4,
    "intermediate_size": 16, "moe_intermediate_size": 4,
    "n_routed_experts": 4, "num_experts_per_tok": 1, "kv_lora_rank": 6,
    "vocab_size": 32, "reduced": [], "arch_id": "moonshot-v1-16b-a3b",
    "reference": "toy_moe", "weights": {"format": "dense", "dtype": "float32"},
    "control": {"kind": "reference", "bits": 8},
}


# four GQA layers at DENSE's widths in bench/test_bench_work.py (576
# weights, 8 KV elements a token, 32 FLOPs a pair), three attending a
# window of 4 keys to each one that attends every earlier key
TOY_WINDOW_REFERENCE = '''
"""Toy reference: grouped-query attention, sliding-window layers and
full-attention layers as ``layer_types`` lists them."""
from bench import work

PROGRAM_REQUIRES = {"family": "dense"}


def model_from_config(config):
    return config


def make_params(model, key):
    raise AssertionError("weights made")


def gaps(params, tokens, targets, model, control_bits=0):
    raise AssertionError("not checked here")


def program_fields(config):
    raise AssertionError("no program computes a window")


def work_shapes(config):
    d, f = config["hidden_size"], config["intermediate_size"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["head_dim"]
    mats = ((d, h * hd, 1.0), (d, kv * hd, 1.0), (d, kv * hd, 1.0),
            (h * hd, d, 1.0), (d, f, 1.0), (d, f, 1.0), (f, d, 1.0))

    def group(kind, window):
        return work.LayerGroup(
            layers=config["layer_types"].count(kind), mats=mats,
            kv_per_token=2 * kv * hd, attn_flops_per_pair=4 * h * hd,
            window=window)

    return work.Shapes(groups=(group("full_attention", 0),
                               group("sliding_attention",
                                     config["sliding_window"])),
                       d=d, vocab=config["vocab_size"])
'''

TOY_WINDOW_CONFIG = {
    "source": "test", "num_hidden_layers": 4, "hidden_size": 8,
    "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
    "intermediate_size": 16, "vocab_size": 32, "sliding_window": 4,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "reduced": [], "arch_id": "internlm2-1.8b", "reference": "toy_window",
    "weights": {"format": "dense", "dtype": "float32"},
    "control": {"kind": "reference", "bits": 8},
}


def write_toy(root, arch_id="moonshot-v1-16b-a3b", source=TOY_REFERENCE,
              config=TOY_CONFIG):
    """Write the toy cell's files under ``root`` (the reference module
    ``source`` under the name ``config["reference"]``) and load the cell."""
    bench = root / "bench"
    for sub in ("configs", "traffic", "checks", "references"):
        (bench / sub).mkdir(parents=True, exist_ok=True)
    (bench / "references" / f"{config['reference']}.py").write_text(
        textwrap.dedent(source))
    (bench / "configs" / "toy.json").write_text(
        json.dumps({**config, "arch_id": arch_id}))
    (bench / "traffic" / "toy_mix.json").write_text(json.dumps(tiny_cell.traffic()))
    (bench / "checks" / "toy.cell.json").write_text(
        json.dumps({"number": "max_gap", "limit": 0.05}))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": "toy.cell", "config": "toy",
                       "traffic": "toy_mix", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "step_mfu", "unit": "%"}],
    }))
    return spec.load_cell("toy.cell", root=root, bench_dir=bench)


def test_another_architecture_joins_as_files(tmp_path):
    cell = write_toy(tmp_path)
    assert cell.bench_dir == tmp_path / "bench"
    assert not (spec.BENCH_DIR / "references" / "toy_moe.py").exists()
    ref = spec.reference_module(cell.config, cell.bench_dir)
    cfg = run.program_config(cell.config, ref)
    assert (cfg.family, cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.n_experts,
            cfg.experts_per_token) == ("moe", 3, 8, 4, 4, 1)

    s = ref.work_shapes(cell.config)
    # dense layer: q, o 8x8, FFN 8x16 and 16x8; each MoE layer: q, o, the
    # 8x4 router, and 4 experts of 8x4 and 4x8, a quarter of tokens each
    assert s.layer_weights == 384 + 2 * (128 + 32 + 256) == 1216
    assert s.token_weights == 384 + 2 * (128 + 32 + 256 / 4) == 832
    assert s.head_weights == 256
    assert s.kv_bytes_per_token == 3 * 6 * 2
    assert s.attn_flops_per_pair == 3 * 20
    # bf16: 768 B dense layer, 640 B of share-1 MoE matrices, 512 B head,
    # and the experts' 1,024 B read with probability 1 - (3/4)^t
    assert s.step_weight_bytes(1) == 768 + 640 + 1024 * 0.25 + 512 == 2176
    assert s.step_weight_bytes(2) == 768 + 640 + 1024 * (1 - 0.75 ** 2) + 512 == 2368
    flops, nbytes = work.decode(s, 1, [10, 20])
    assert flops == 2 * (2 * (832 + 256)) + 60 * 30
    assert nbytes == 2368 + 2 * 8 * 2 + 36 * (30 + 2)


def test_a_windowed_architecture_joins_as_files(tmp_path):
    cell = write_toy(tmp_path, "internlm2-1.8b", TOY_WINDOW_REFERENCE,
                     TOY_WINDOW_CONFIG)
    assert not (spec.BENCH_DIR / "references" / "toy_window.py").exists()
    s = spec.reference_module(cell.config, cell.bench_dir).work_shapes(cell.config)
    assert [(g.layers, g.window) for g in s.groups] == [(1, 0), (3, 4)]
    assert s.layer_weights == 4 * 576 and s.head_weights == 256
    assert s.kv_bytes_per_token == 4 * 16 and s.attn_flops_per_pair == 4 * 32
    # one step, contexts 3 and 10: the full layer attends 3 + 10 keys, each
    # window layer 3 + 4; KV 16 B a token a layer, read and one written
    flops, nbytes = work.decode(s, 1, [3, 10])
    assert flops == 2 * 2 * (2304 + 256) + 32 * 13 + 3 * 32 * 7 == 11328
    assert nbytes == 2 * 2560 + 2 * 16 + 16 * (13 + 2) + 3 * 16 * (7 + 2) == 5824
    # a chunk of 4 after 6 cached: queries 6 ... 9 attend 7 ... 10 keys in
    # the full layer and 4 each in a window layer, which reads 3 of the
    # prefix's 6 positions
    flops, nbytes = work.prefill(s, [(6, 4)])
    assert flops == 2 * 2304 * 4 + 2 * 256 + 32 * 34 + 3 * 32 * 16 == 21568
    assert nbytes == 2 * 2560 + 4 * 16 + 16 * (6 + 4) + 3 * 16 * (3 + 4) == 5680
    # the committed decode reader counts the window: each bound 1 s at
    # these peaks, over 2 s of device time
    ctx = spec.MetricContext(
        reduced=SimpleNamespace(module_s={"jit_segment": 2.0}), stats0={},
        stats1={}, launches=SimpleNamespace(segments=[(1, [3, 10])]),
        records=[], t0=0.0, t1=1.0, shapes=s,
        peaks={"bf16_flop_s": 11328.0, "hbm_byte_s": 5824.0})
    assert spec.metric_reader("decode_roofline")(ctx) == 50.0


def test_a_reference_the_arch_does_not_match_is_refused_before_weights(tmp_path):
    cell = write_toy(tmp_path, arch_id="internlm2-1.8b")
    ref = spec.reference_module(cell.config, cell.bench_dir)
    with pytest.raises(ValueError, match="is not the model toy_moe computes"):
        run.program_config(cell.config, ref)
    # the whole run stops there too: the toy's make_params would raise
    # AssertionError had a weight been made
    with pytest.raises(ValueError, match="family"):
        run.run_cell(cell, 3, 1.0, False, peaks=tiny_cell.PEAKS)


def test_reference_refuses_a_file_it_does_not_compute():
    config = json.loads((spec.BENCH_DIR / "configs" / "internlm2-1.8b.json").read_text())
    ref = spec.reference_module(config)
    for key, value in (("hidden_act", "gelu"), ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match="not the decoder"):
            run.program_config({**config, key: value}, ref)


def test_every_reference_provides_what_the_harness_calls():
    for path in sorted((spec.BENCH_DIR / "references").glob("*.py")):
        mod = spec.load_module(path)
        missing = [n for n in REQUIRED if not hasattr(mod, n)]
        assert not missing, (path.name, missing)


# the program's config of each committed configuration, as the harness
# built it when it mapped the file's keys itself
PROGRAM_FIELDS = {
    "internlm2-1.8b": dict(n_layers=24, d_model=2048, n_heads=16, n_kv_heads=8,
                           head_dim=128, d_ff=8192, vocab_size=92544,
                           rope_theta=1000000.0),
    "mistral-nemo-12b-sonic": dict(n_layers=4, d_model=5120, n_heads=32,
                                   n_kv_heads=8, head_dim=128, d_ff=14336,
                                   vocab_size=131072, rope_theta=1000000.0),
}


@pytest.mark.parametrize("name", sorted(PROGRAM_FIELDS))
def test_committed_program_config_is_unchanged(name):
    from repro.configs.base import get_config

    config = json.loads((spec.BENCH_DIR / "configs" / f"{name}.json").read_text())
    cfg = run.program_config(config, spec.reference_module(config))
    assert cfg == get_config(config["arch_id"]).replace(**PROGRAM_FIELDS[name])
    assert (cfg.family, cfg.pos_enc, cfg.norm, cfg.ffn, cfg.use_bias,
            cfg.tie_embeddings) == ("dense", "rope", "rmsnorm", "swiglu",
                                    False, False)
