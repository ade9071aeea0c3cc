"""The one traffic generator: a mix file's parameters and a seed in, the
requests out.

Every seed gets the same sizes (and, in an open loop, the same gaps between
arrivals) in every run of ``block`` requests: each block holds the same grid
of quantiles of the mix's distributions, and the seed only shuffles them
within the block and draws the token ids.  So any run that sends whole
blocks does the same work whatever the seed, in another order.

Kinds:
  closed  ``clients`` callers, each sending its next request as soon as its
          last one finished.  The first request of each client gets an
          output spread evenly up to the mix's longest (the residual life
          of a loop already running, the same for every seed), so the
          loop starts near its steady state.
  open    requests due at a Poisson process of ``rate_per_s``, sent when
          due whatever the system is doing.
"""
from __future__ import annotations

import dataclasses
import statistics

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    prompt: np.ndarray  # (P,) int32 token ids
    max_new: int
    due: float = 0.0  # seconds after the traffic starts (open loop)


def seed_rng(seed: int) -> np.random.Generator:
    """A generator for any whole seed, negative or past 64 bits included."""
    return np.random.default_rng(abs(int(seed)) % 2**64)


def quantiles(spec: dict, n: int) -> np.ndarray:
    """n integer sizes at the midpoint quantiles of ``spec``'s distribution,
    clipped to [min, max]: ``uniform`` over [min, max], both ends included,
    or ``log_normal`` of the given ``mean`` and ``sigma`` (of the log) before
    clipping."""
    lo, hi = int(spec["min"]), int(spec["max"])
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "uniform":
        x = lo + u * (hi - lo + 1)
    elif spec["dist"] == "log_normal":
        sigma = float(spec["sigma"])
        mu = np.log(float(spec["mean"])) - sigma ** 2 / 2
        z = np.array([statistics.NormalDist().inv_cdf(v) for v in u])
        x = np.exp(mu + sigma * z)
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def _blocks(grid: np.ndarray, n_blocks: int, rng) -> np.ndarray:
    """``n_blocks`` shuffled copies of ``grid``, one after the other."""
    return np.concatenate([rng.permutation(grid) for _ in range(n_blocks)])


def generate(traffic: dict, vocab: int, seed: int) -> list[Request]:
    """The mix's ``pool_size`` requests for ``seed``, in sending order."""
    n, block = int(traffic["pool_size"]), int(traffic["block"])
    if n % block:
        raise ValueError(f"pool_size {n} is not a whole number of blocks of {block}")
    rng = seed_rng(seed)
    nb = n // block
    p_lens = _blocks(quantiles(traffic["prompt_len"], block), nb, rng)
    o_lens = _blocks(quantiles(traffic["output_len"], block), nb, rng)
    kind = traffic["kind"]
    due = np.zeros(n)
    if kind == "closed":
        c = int(traffic["clients"])
        share = rng.permutation((np.arange(c) + 0.5) / c)
        o_lens[:c] = np.maximum(np.ceil(share * int(traffic["output_len"]["max"])), 1)
    elif kind == "open":
        if traffic["arrivals"] != "poisson":
            raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
        u = (np.arange(block) + 0.5) / block
        gaps = -np.log1p(-u) / float(traffic["rate_per_s"])
        due = np.cumsum(_blocks(gaps, nb, rng))
    else:
        raise ValueError(f"unknown traffic kind {kind!r}")
    return [Request(rng.integers(0, vocab, int(p), dtype=np.int32), int(o),
                    float(t)) for p, o, t in zip(p_lens, o_lens, due)]
