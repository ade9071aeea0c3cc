"""The window's arithmetic on synthetic request records."""
import pytest

from bench.window import Record, output_tok_s, p95, summarize, tpot_ms, ttft_ms


def _recs():
    # window [10, 20] on the host clock
    return [
        # sent before the window, tokens at 9, 11, 13: two inside
        Record(due=8.0, prompt_len=4, max_new=3, emit_t=[9.0, 11.0, 13.0]),
        # sent at 12, first token at 12.5, then 14, 16.5, and 21 (after close)
        Record(due=12.0, prompt_len=4, max_new=4, emit_t=[12.5, 14.0, 16.5, 21.0]),
        # due at 15, never served by the close: waits 5 s so far
        Record(due=15.0, prompt_len=4, max_new=2),
        # due after the close: not counted
        Record(due=20.5, prompt_len=4, max_new=2, emit_t=[20.7]),
    ]


def test_tokens_over_the_window():
    assert output_tok_s(_recs(), 10.0, 20.0) == pytest.approx(5 / 10.0)


def test_ttft_counts_requests_not_yet_served():
    assert ttft_ms(_recs(), 10.0, 20.0) == pytest.approx([500.0, 5000.0])


def test_tpot_is_a_per_request_mean_up_to_the_close():
    # request 0: tokens 1..2 inside: (13 - 9) / 2; request 1: (16.5 - 12.5) / 2
    assert tpot_ms(_recs(), 10.0, 20.0) == pytest.approx([2000.0, 2000.0])


class _Handle:
    def __init__(self, state):
        self.state = state

    terminal = property(lambda self: self.state in ("finished", "cancelled", "expired"))
    done = property(lambda self: self.state == "finished")


def test_failed_counts_requests_ended_unfinished():
    recs = _recs()
    for r, state in zip(recs, ("expired", "finished", "cancelled", "expired")):
        r.handle = _Handle(state)
    # sent inside the window: the finished one, and the cancelled one
    assert summarize(recs, 10.0, 20.0)["failed"] == 1


def test_summary_and_percentile():
    s = summarize(_recs(), 10.0, 20.0)
    assert s["attempted"] == 2 and s["failed"] == 0
    assert s["n_ttft"] == 2 and s["n_tpot"] == 2
    assert s["ttft_p95_ms"] == pytest.approx(500 + 0.95 * 4500)
    assert p95([]) is None
    assert p95(list(range(101))) == pytest.approx(95.0)
