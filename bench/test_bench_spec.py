"""Everything a cell needs is found by the names in BENCHMARK.json, and a
file added beside the others is picked up without an edit to any file."""
import json

import pytest

from bench import spec, work

BENCH = json.loads((spec.ROOT / "BENCHMARK.json").read_text())


def test_every_named_file_exists_and_loads():
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.chips == w["chips"] == 1
        assert spec.reference_module(cell.config).model_from_config(cell.config)
        assert cell.traffic["serving"]["max_len"] % cell.traffic["serving"]["prefill_chunk"] == 0
        names = {m["name"] for m in cell.end_to_end}
        assert {"setup_s", "output_tok_s"} <= names
        assert cell.per_layer
    for m in BENCH["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
    for c in BENCH["configs"]:
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]


def test_new_files_are_found_by_name(tmp_path):
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics", "checks"):
        (bench / sub).mkdir(parents=True)
    (bench / "checks" / "new.cell.json").write_text(json.dumps({"number": "max_gap"}))
    (bench / "configs" / "new-model.json").write_text(json.dumps({"weights": {}}))
    (bench / "traffic" / "new_mix.json").write_text(json.dumps({"kind": "open"}))
    (bench / "metrics" / "new.metric_pct.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": "new.cell", "config": "new-model",
                       "traffic": "new_mix", "chips": 1}],
        "end_to_end": [{"name": "setup_s"}, {"name": "only_elsewhere",
                                             "workloads": ["other.cell"]}],
        "per_layer": [{"name": "new.metric_pct"}],
    }))
    cell = spec.load_cell("new.cell", root=tmp_path, bench_dir=bench)
    assert cell.config["name"] == "new-model" and cell.traffic["kind"] == "open"
    assert cell.check["number"] == "max_gap"
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    assert spec.metric_reader("new.metric_pct", bench_dir=bench)(None) == 42.0
    with pytest.raises(KeyError):
        spec.load_cell("missing.cell", root=tmp_path, bench_dir=bench)


def test_unknown_device_kind_raises():
    assert work.peaks("TPU v5 lite")["hbm_byte_s"] == 819e9
    with pytest.raises(ValueError, match="no peak rates"):
        work.peaks("cpu")
