"""A cell small enough for the CPU, written as files the harness finds by
name, for the tests of ``bench/``: internlm2's arch at tiny widths, or a
block-sparse int8 variant, under a short closed or open loop."""
from __future__ import annotations

import json
import pathlib
import shutil

from bench import spec


def config(fmt: str = "dense") -> dict:
    cfg = {
        "source": "test", "num_hidden_layers": 2, "hidden_size": 64,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "intermediate_size": 128, "vocab_size": 256, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-05, "hidden_act": "silu",
        "tie_word_embeddings": False, "reduced": [], "arch_id": "internlm2-1.8b",
        "reference": "gqa_rope_swiglu",
        "weights": {"format": "dense", "dtype": "float32", "block": [16, 16]},
        "control": {"kind": "reference", "bits": 8},
    }
    if fmt == "int8_block_sparse":
        cfg["weights"] = {"format": fmt, "dtype": "bfloat16", "sparsity": 0.5,
                          "block": [16, 16]}
        cfg["control"] = {"kind": "reference", "bits": 4}
    return cfg


def traffic(kind: str = "closed") -> dict:
    serving = {"slots": 4, "block_len": 8, "max_len": 64, "prefill_chunk": 16,
               "prefill_buckets": 2, "segment_len": 4, "segment_mode": "while"}
    t = {"kind": kind, "prompt_len": {"dist": "uniform", "min": 4, "max": 24},
         "output_len": {"dist": "uniform", "min": 4, "max": 24},
         "pool_size": 8192, "block": 8, "serving": serving}
    if kind == "closed":
        t.update(clients=4, warmup_retired=2)
    else:
        t.update(arrivals="poisson", rate_per_s=20.0, warmup_s=0.5)
    return t


def write(root: pathlib.Path, fmt: str = "dense", kind: str = "closed") -> spec.Cell:
    """Write a one-cell benchmark under ``root``, with a copy of the
    reference module, and load its cell."""
    bench = root / "bench"
    for sub in ("configs", "traffic", "checks", "references"):
        (bench / sub).mkdir(parents=True, exist_ok=True)
    ref = f"{config(fmt)['reference']}.py"
    shutil.copy(spec.BENCH_DIR / "references" / ref, bench / "references" / ref)
    (bench / "checks" / "tiny.cell.json").write_text(
        json.dumps({"number": "max_gap", "limit": 0.05}))
    (bench / "configs" / "tiny.json").write_text(json.dumps(config(fmt)))
    (bench / "traffic" / "tiny_mix.json").write_text(json.dumps(traffic(kind)))
    e2e = [{"name": n, "unit": u} for n, u in (
        ("output_tok_s", "tokens/s"), ("ttft_p95_ms", "ms"),
        ("tpot_p95_ms", "ms"), ("setup_s", "s"))]
    per_layer = json.loads((spec.ROOT / "BENCHMARK.json").read_text())["per_layer"]
    (root / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": "tiny.cell", "config": "tiny",
                       "traffic": "tiny_mix", "chips": 1}],
        "end_to_end": e2e,
        "per_layer": [{k: v for k, v in m.items() if k != "workloads"}
                      for m in per_layer],
    }))
    return spec.load_cell("tiny.cell", root=root, bench_dir=bench)


PEAKS = {"bf16_flop_s": 197e12, "hbm_byte_s": 819e9}
