"""What decides ``correct``: the served tokens against the plain reference.

Once the window has closed, a sample drawn from the seed of the requests
that finished inside it (the one with the most served tokens always among
them) is run through the reference once: each prompt followed by the
tokens it was served.  At every served position the reference scores the
served token; the number compared is the widest gap by which a served
token's logit lies below the reference's best there.  The first token comes
from the chunked prefill program, the rest from the decode segments through
the paged cache, so both are covered.

The reference is the configuration's plain module (``bench/references``),
fed the weights the benchmark made from the seed, made again here once the
program's state is freed; it imports nothing of the program.

A control of the ``reference`` kind scores, at the same positions, the
token that the reference with its weights rounded to ``bits`` puts first.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic as traffic_mod

SAMPLE_TOKENS = 512  # served tokens to reach with the sample, at least
SAMPLE_MIN_REQUESTS = 4
SAMPLE_MAX_REQUESTS = 16


@dataclasses.dataclass
class Served:
    prompt: np.ndarray
    tokens: list[int]


def sample(done: list[Served], seed: int) -> list[Served]:
    """The finished request with the most served tokens, then others drawn
    from the seed until ``SAMPLE_TOKENS`` served tokens and
    ``SAMPLE_MIN_REQUESTS`` requests, or ``SAMPLE_MAX_REQUESTS``."""
    if not done:
        return []
    order = sorted(range(len(done)), key=lambda i: -len(done[i].tokens))
    rest = traffic_mod.seed_rng(seed + 1).permutation(order[1:]).tolist()
    picked = [order[0]]
    n = len(done[order[0]].tokens)
    for i in rest:
        if (n >= SAMPLE_TOKENS and len(picked) >= SAMPLE_MIN_REQUESTS
                or len(picked) >= SAMPLE_MAX_REQUESTS):
            break
        picked.append(i)
        n += len(done[i].tokens)
    return [done[i] for i in picked]


def served_gaps(ref, model, params, reqs: list[Served], max_len: int,
                control_bits: int = 0) -> dict:
    """Widest gap of a served token below the reference's best over
    ``reqs`` (and of the control's pick, with ``control_bits``), each
    sequence padded to ``max_len`` so one program serves every request."""
    fn = jax.jit(lambda p, t, g: ref.gaps(p, t, g, model, control_bits))
    widest = ctrl_widest = total = ctrl_total = 0.0
    n_tok = agree = 0
    for r in reqs:
        full = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        toks = np.zeros(max_len, np.int32)
        tgts = np.zeros(max_len, np.int32)
        toks[:len(full) - 1] = full[:-1]
        tgts[:len(full) - 1] = full[1:]
        gap, ctrl = jax.device_get(fn(params, jnp.asarray(toks), jnp.asarray(tgts)))
        at = slice(len(r.prompt) - 1, len(full) - 1)
        widest = max(widest, float(gap[at].max()))
        ctrl_widest = max(ctrl_widest, float(ctrl[at].max()))
        total += float(gap[at].sum())
        ctrl_total += float(ctrl[at].sum())
        n_tok += at.stop - at.start
        agree += int((gap[at] == 0).sum())
    out = {"max_gap": widest, "mean_gap": total / max(n_tok, 1),
           "tokens": n_tok, "requests": len(reqs),
           "argmax_agreement": agree / max(n_tok, 1)}
    if control_bits:
        out["control_max_gap"] = ctrl_widest
        out["control_mean_gap"] = ctrl_total / max(n_tok, 1)
    return out
