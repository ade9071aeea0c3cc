"""Whole runs of a tiny cell on the CPU, the chip check skipped: correct on
the sound program, with every field of the result line, and the traced run's
per-layer metrics."""
import pytest

from bench import run, tiny_cell

END_TO_END = {"output_tok_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}


@pytest.mark.parametrize("fmt,kind", [("dense", "closed"),
                                      ("int8_block_sparse", "open")])
def test_sound_run_is_correct(tmp_path, fmt, kind):
    cell = tiny_cell.write(tmp_path, fmt, kind)
    out = run.run_cell(cell, 2**31 + 5, 2.0, False, peaks=tiny_cell.PEAKS)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == END_TO_END
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["count"] == 1
    assert list(out)[-1] == "checks"
    assert {"max_gap", "compiles_in_window", "stalled_requests"} <= set(out["checks"])


def test_tiny_cell_reports_the_metrics_of_its_own_architecture(tmp_path):
    """The tiny GQA cell reports every per-layer metric of the committed
    GQA cells, and none that only a cell of another architecture reads."""
    import json

    from bench import spec

    cell = tiny_cell.write(tmp_path)
    names = {m["name"] for m in cell.per_layer}
    for m in json.loads((spec.ROOT / "BENCHMARK.json").read_text())["per_layer"]:
        if "internlm2.decode" in m.get("workloads", ["internlm2.decode"]):
            assert m["name"] in names
    assert "moe.useful_rows_pct" not in names and "decode_roofline" in names
    assert all(m.get("workloads", ["tiny.cell"]) == ["tiny.cell"]
               for m in cell.per_layer)


def test_traced_run_reads_every_per_layer_metric(tmp_path):
    cell = tiny_cell.write(tmp_path, "dense", "closed")
    out = run.run_cell(cell, 7, 2.5, True, trace_dir=tmp_path / "trace",
                       trace_span=(0.5, 1.5), peaks=tiny_cell.PEAKS)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {m["name"] for m in cell.per_layer}
    for name, m in out["metrics"].items():
        assert 0 < m["value"] <= 100, (name, m)
    dev = out["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert 1.0 <= dev["window_s"] <= 2.5
    assert 0 < len(out["breakdown"]["device_ops"]) <= 10
    assert any(n.startswith("bench.") for n, _ in out["breakdown"]["idle_gaps"])


def test_instrument_refuses_a_program_it_cannot_read():
    from types import SimpleNamespace

    from bench import serve_loop as sl

    def prefill(params, cache, tok, pos, done, prompts, slots, starts,
                last_local, *rest):
        return None

    segment = lambda *a: None  # noqa: E731
    whole = dict(_prefill_slots_paged=prefill, _slot_segment_paged=segment,
                 _slot_segment_while_paged=segment)
    sl.instrument(SimpleNamespace(**whole), 4, sl.LaunchLog())
    with pytest.raises(AttributeError, match="_slot_segment_paged"):
        sl.instrument(SimpleNamespace(**{**whole, "_slot_segment_paged": None}),
                      4, sl.LaunchLog())
    renamed = lambda params, cache, tok, pos, done, prompts, rows, starts, last: None  # noqa: E731
    with pytest.raises(TypeError, match="slots"):
        sl.instrument(SimpleNamespace(**{**whole, "_prefill_slots_paged": renamed}),
                      4, sl.LaunchLog())


def test_decode_work_is_logged_from_the_records():
    from bench import serve_loop as sl
    from bench.window import Record

    log = sl.LaunchLog()
    recs = [Record(0.0, prompt_len=10, max_new=9, emit_t=[1.0, 2.0, 3.0, 4.0]),
            Record(0.0, prompt_len=5, max_new=9, emit_t=[3.0, 3.5]),
            Record(0.0, prompt_len=7, max_new=9, emit_t=[1.0])]
    # before the call: 2, 0 and 1 tokens; the first came from prefill
    log.decoded(recs, [2, 0, 1])
    assert log.segments == [(2, [12, 13, 6])]
    log.decoded(recs, [4, 2, 1])  # nothing decoded: nothing logged
    assert len(log.segments) == 1


def test_main_refuses_a_machine_without_a_tpu(capsys):
    assert run.main(["--workload", "internlm2.decode", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) != 0
    assert capsys.readouterr().out == ""
