"""Work a step requires, counted from the configuration's shapes alone.

The configuration's reference module states the shapes
(``work_shapes(config) -> Shapes``): one or more groups of alike layers,
each with its matrices, the KV it stores a token, its attention FLOPs a
(query, key) pair and, where its layers attend a sliding window, the
window (``LayerGroup.window``), and the LM head.  Nothing here reads what
the program executes, so the counts are the same whatever implements the
work, and a roofline share built on them cannot pass 100% by a change of
implementation:

- Weights are counted in the format the configuration states: bf16 (2 B)
  for dense weights, and for int8 block-sparse weights only the kept int8
  blocks with one fp32 scale each, matrix by matrix; FLOPs only over kept
  blocks.
- A matrix carries the share of a step's tokens that multiply it: 1 for a
  dense one, experts per token / experts for a routed expert.  A token's
  FLOPs count each matrix times its share.  A step of ``t`` tokens reads a
  matrix of share ``s`` with probability ``1 - (1 - s)^t``: the expected
  bytes if every token picks its experts uniformly and independently of the
  others, the one assumption of these counts.  At share 1 a step reads
  every weight once, whatever ``t``.
- KV reads are counted at each row's actual context, not at ``max_len``.
- A group's ``window`` W, where it is not 0, bounds the keys one query of
  its layers attends, itself included, as transformers' sliding-window
  causal mask does: a query at position p attends keys p - W + 1 ... p.
  Such a layer reads and multiplies only those keys: at most W a decoded
  token, Σ min(p + 1, W) over a prefill chunk's queries, and only the last
  W - 1 positions of a chunk's prefix.  Window 0 (every earlier key) is
  the default.  Each group's keys, pairs and KV bytes are whole numbers,
  summed exactly, so where no group has a window they equal the
  whole-context products to the last bit.
- Prefill counts the real tokens of each chunk, not the bucket padding, and
  LM-head logits only at each row's last real token.
- Every product is counted at 2 FLOPs per multiply-add; attention at the
  FLOPs per (query, key) pair the reference states for each layer (scores
  and values: 4 x heads x the head width for grouped-query attention).

A step's least time is the larger of its FLOPs over the bf16 peak and its
bytes over HBM bandwidth (``roofline_s``).
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"
KV_BYTES = 2  # bf16 cache
ACT_BYTES = 2  # bf16 embedding rows


def peaks(device_kind: str) -> dict:
    """The peak rates of ``device_kind``; one that is not in the table is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise ValueError(f"no peak rates for device kind {device_kind!r} in "
                         f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


@dataclasses.dataclass(frozen=True)
class LayerGroup:
    """``layers`` alike layers: each layer's matrices as (k, n, share), the
    KV elements a token stores in one layer, one layer's attention FLOPs
    per (query, key) pair, and the most keys one query of these layers
    attends, itself included (``window``; 0 for every earlier key)."""

    layers: int
    mats: tuple[tuple[int, int, float], ...]
    kv_per_token: int
    attn_flops_per_pair: int
    window: int = 0

    def keys(self, context: int) -> int:
        """Keys a query attends in these layers where ``context`` keys (its
        position + 1) precede it, itself included."""
        return min(context, self.window) if self.window else context

    def pairs(self, start: int, real: int) -> int:
        """(query, key) pairs of ``real`` queries at positions ``start`` ...
        ``start + real - 1``: Σ keys(p + 1), in closed form."""
        a, b = start + 1, start + real  # contexts of the first and last
        w = self.window or b
        m = min(b, w)  # contexts a ... m attend all of theirs, the rest w
        below = (a + m) * (m - a + 1) // 2 if m >= a else 0
        return below + w * max(0, b - max(a - 1, w))

    def prefix_read(self, start: int) -> int:
        """Positions of a chunk's prefix (the ``start`` before it) that its
        first query attends."""
        return min(start, self.window - 1) if self.window else start

    @property
    def kv_bytes(self) -> int:
        """KV bytes a token stores in all of these layers."""
        return self.layers * self.kv_per_token * KV_BYTES


@dataclasses.dataclass(frozen=True)
class Shapes:
    groups: tuple[LayerGroup, ...]
    d: int  # embedding row, and the LM head's input
    vocab: int
    fmt: str = "dense"  # "dense" | "int8_block_sparse"
    bytes_per_weight: int = 2  # dense format
    sparsity: float = 0.0
    block: tuple[int, int] = (128, 128)

    def _kept(self, k: int, n: int) -> tuple[int, int]:
        """(weights, blocks) kept of a (k, n) matrix."""
        if self.fmt == "dense":
            return k * n, 0
        bk, bn = self.block
        kb, nb = k // bk, n // bn
        r = max(int(round(kb * (1.0 - self.sparsity))), 1)
        return r * nb * bk * bn, r * nb

    def _mats(self):
        """(layers, k, n, share) of every layer matrix."""
        return [(g.layers, k, n, share) for g in self.groups
                for k, n, share in g.mats]

    @property
    def layer_weights(self) -> int:
        """Kept weights of every layer matrix (every expert's)."""
        return sum(ls * self._kept(k, n)[0] for ls, k, n, _ in self._mats())

    @property
    def token_weights(self) -> float:
        """Kept layer weights one token multiplies: each matrix's times its
        share."""
        return sum(ls * self._kept(k, n)[0] * share
                   for ls, k, n, share in self._mats())

    @property
    def head_weights(self) -> int:
        return self._kept(self.d, self.vocab)[0]

    def _bytes(self, k: int, n: int) -> int:
        w, blocks = self._kept(k, n)
        if self.fmt == "dense":
            return w * self.bytes_per_weight
        return w + 4 * blocks  # int8 values + one fp32 scale per kept block

    def step_weight_bytes(self, t: float) -> float:
        """Expected bytes of the layer matrices and the LM head that a step
        of ``t`` tokens reads, each matrix at most once (module docstring)."""
        layers = sum(ls * self._bytes(k, n) * (1.0 - (1.0 - share) ** t)
                     for ls, k, n, share in self._mats())
        return layers + self._bytes(self.d, self.vocab)

    @property
    def kv_bytes_per_token(self) -> int:
        """KV bytes a token stores over every layer."""
        return sum(g.layers * g.kv_per_token for g in self.groups) * KV_BYTES

    @property
    def attn_flops_per_pair(self) -> int:
        """Attention FLOPs of a (query, key) pair over every layer."""
        return sum(g.layers * g.attn_flops_per_pair for g in self.groups)


def decode(s: Shapes, steps: int, contexts: list[int]) -> tuple[float, float]:
    """(FLOPs, bytes) of ``steps`` decode steps that emitted one token per
    entry of ``contexts``, each the number of keys up to that token (its
    position + 1), of which a windowed group attends at most its window."""
    n = len(contexts)
    flops = sum(token_flops(s, c) for c in contexts)
    # weights once per step, each step taken at the segment's mean tokens;
    # per token its embedding row, the KV it reads (its whole context, or
    # its window) and the one position it writes
    nbytes = (steps * s.step_weight_bytes(n / steps) + n * s.d * ACT_BYTES
              + sum(g.kv_bytes * (sum(g.keys(c) for c in contexts) + n)
                    for g in s.groups))
    return flops, nbytes


def prefill(s: Shapes, rows: list[tuple[int, int]]) -> tuple[float, float]:
    """(FLOPs, bytes) of one prefill launch over ``rows`` of (start, real
    tokens): real tokens only, logits only at each row's last token."""
    flops = 0.0
    nbytes = s.step_weight_bytes(sum(real for _, real in rows))
    for start, real in rows:
        flops += (2.0 * s.token_weights * real + 2.0 * s.head_weights
                  + sum(g.layers * g.attn_flops_per_pair * g.pairs(start, real)
                        for g in s.groups))  # causal (query, key) pairs
        # embedding rows, the prefix KV read, the chunk's KV written
        nbytes += (real * s.d * ACT_BYTES
                   + sum(g.kv_bytes * (g.prefix_read(start) + real)
                         for g in s.groups))
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, pk: dict) -> float:
    return max(flops / pk["bf16_flop_s"], nbytes / pk["hbm_byte_s"])


def token_flops(s: Shapes, context: int) -> float:
    """FLOPs of decoding one token that ``context`` keys precede, itself
    included (each windowed group attends at most its window of them)."""
    return (2.0 * (s.token_weights + s.head_weights)
            + sum(g.layers * g.attn_flops_per_pair * g.keys(context)
                  for g in s.groups))


def window_flops(s: Shapes, records, t0: float, t1: float) -> tuple[float, float]:
    """(prefill, decode) FLOPs required by the requests' work that finished
    inside [t0, t1] on the host clock: every prompt whose first token came
    then, and every later token emitted then (token j of a request with a
    P-token prompt attends P + j keys, min(P + j, W) in a group of window
    W)."""
    pre = dec = 0.0
    for r in records:
        for j, t in enumerate(r.emit_t):
            if not t0 <= t <= t1:
                continue
            if j == 0:
                pre += prefill(s, [(0, r.prompt_len)])[0]
            else:
                dec += token_flops(s, r.prompt_len + j)
    return pre, dec
