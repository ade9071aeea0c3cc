"""The traffic generator: deterministic per seed, within its file's ranges
and rate, and the same sizes for every seed in every block."""
import json

import numpy as np
import pytest

from bench import spec
from bench import traffic as tr

MIXES = sorted(p.stem for p in (spec.BENCH_DIR / "traffic").glob("*.json"))


def _mix(name):
    return json.loads((spec.BENCH_DIR / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    t = _mix(name)
    a, b = tr.generate(t, 1000, 2**31 + 7), tr.generate(t, 1000, 2**31 + 7)
    assert len(a) == t["pool_size"]
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.due) == (y.max_new, y.due)
    c = tr.generate(t, 1000, 3)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("name", MIXES)
def test_sizes_in_range_and_fit_the_cache(name):
    t = _mix(name)
    reqs = tr.generate(t, 1000, 11)
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.max_new for r in reqs])
    assert p.min() >= t["prompt_len"]["min"] and p.max() <= t["prompt_len"]["max"]
    assert o.max() <= t["output_len"]["max"] and o.min() >= 1
    first = t.get("clients", 0)
    assert o[first:].min() >= t["output_len"]["min"]
    assert (p + o).max() <= t["serving"]["max_len"]
    assert all(r.prompt.min() >= 0 and r.prompt.max() < 1000 for r in reqs)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_sends_the_same_sizes_per_block(name):
    t = _mix(name)
    k = t["block"]
    a, b = tr.generate(t, 1000, 1), tr.generate(t, 1000, 2)
    first = t.get("clients", 0)  # the closed loop's residual first requests
    for i in range(0, len(a), k):
        pa = sorted(len(r.prompt) for r in a[i:i + k])
        pb = sorted(len(r.prompt) for r in b[i:i + k])
        assert pa == pb
        if i >= first:
            assert sorted(r.max_new for r in a[i:i + k]) == sorted(
                r.max_new for r in b[i:i + k])
    if t["kind"] == "open":
        # whole blocks of arrivals span the same time for every seed
        assert a[k - 1].due == pytest.approx(b[k - 1].due)


def test_open_loop_rate():
    t = {"kind": "open", "arrivals": "poisson", "rate_per_s": 5.0,
         "prompt_len": {"dist": "uniform", "min": 1, "max": 4},
         "output_len": {"dist": "uniform", "min": 1, "max": 4},
         "pool_size": 4096, "block": 512}
    reqs = tr.generate(t, 10, 5)
    due = np.array([r.due for r in reqs])
    assert np.all(np.diff(due) > 0)
    assert len(reqs) / due[-1] == pytest.approx(5.0, rel=0.02)


def test_quantiles_cover_both_distributions():
    u = tr.quantiles({"dist": "uniform", "min": 256, "max": 1024}, 769)
    assert u.min() == 256 and u.max() == 1024 and len(set(u)) == 769
    ln = tr.quantiles({"dist": "log_normal", "mean": 338, "sigma": 1.0,
                       "min": 1, "max": 10**6}, 20000)
    # median e^mu = mean / e^(sigma^2 / 2); the mean as stated, less the
    # tail beyond the grid's last quantile
    assert np.median(ln) == pytest.approx(338 / np.exp(0.5), rel=0.01)
    assert ln.mean() == pytest.approx(338, rel=0.02)
    clipped = tr.quantiles({"dist": "log_normal", "mean": 338, "sigma": 1.0,
                            "min": 4, "max": 1024}, 64)
    assert clipped.min() >= 4 and clipped.max() == 1024
    with pytest.raises(ValueError):
        tr.quantiles({"dist": "zipf", "min": 1, "max": 2}, 4)


@pytest.mark.parametrize("name", MIXES)
def test_mix_lengths_have_a_heavy_tail(name):
    """A block's lengths as the mix states them: a log-normal's long tail
    (the longest several times the median), the mean near the source's."""
    t = _mix(name)
    for key in ("prompt_len", "output_len"):
        q = tr.quantiles(t[key], t["block"])
        assert q.max() >= 4 * np.median(q)
        assert q.mean() == pytest.approx(t[key]["mean"], rel=0.15)
