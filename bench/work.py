"""Work a step requires, counted from the configuration's shapes alone.

Nothing here reads what the program executes, so the counts are the same
whatever implements the work, and a roofline share built on them cannot
pass 100% by a change of implementation:

- Weights are counted in the format the configuration states: bf16 (2 B)
  for dense weights, and for int8 block-sparse weights only the kept int8
  blocks with one fp32 scale each; FLOPs only over kept blocks.
- KV reads are counted at each row's actual context, not at ``max_len``.
- Prefill counts the real tokens of each chunk, not the bucket padding, and
  LM-head logits only at each row's last real token.
- Every product is counted at 2 FLOPs per multiply-add; attention at
  4 x heads x head_dim FLOPs per (query, key) pair (scores and values).

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
class Shapes:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    fmt: str = "dense"  # "dense" | "int8_block_sparse"
    bytes_per_weight: int = 2  # dense format
    sparsity: float = 0.0
    block: tuple[int, int] = (128, 128)

    @classmethod
    def from_config(cls, config: dict) -> "Shapes":
        w = config["weights"]
        d, h = config["hidden_size"], config["num_attention_heads"]
        return cls(
            layers=config["num_hidden_layers"], d=d, heads=h,
            kv_heads=config["num_key_value_heads"],
            head_dim=config.get("head_dim", d // h),
            ffn=config["intermediate_size"], vocab=config["vocab_size"],
            fmt=w["format"], bytes_per_weight=w.get("stated_bytes_per_weight", 2),
            sparsity=w.get("sparsity", 0.0), block=tuple(w.get("block", (128, 128))),
        )

    def _kept(self, k: int, n: int) -> tuple[int, int]:
        """(weights, blocks) kept of a (k, n) matrix."""
        if self.fmt == "dense":
            return k * n, 0
        bk, bn = self.block
        kb, nb = k // bk, n // bn
        r = max(int(round(kb * (1.0 - self.sparsity))), 1)
        return r * nb * bk * bn, r * nb

    def _layer_mats(self) -> list[tuple[int, int]]:
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return [(self.d, q), (self.d, kv), (self.d, kv), (q, self.d),
                (self.d, self.ffn), (self.d, self.ffn), (self.ffn, self.d)]

    @property
    def layer_weights(self) -> int:
        """Kept projection weights of all layers."""
        return self.layers * sum(self._kept(k, n)[0] for k, n in self._layer_mats())

    @property
    def head_weights(self) -> int:
        return self._kept(self.d, self.vocab)[0]

    def _bytes(self, k: int, n: int) -> int:
        w, blocks = self._kept(k, n)
        if self.fmt == "dense":
            return w * self.bytes_per_weight
        return w + 4 * blocks  # int8 values + one fp32 scale per kept block

    @property
    def weight_bytes(self) -> int:
        """Bytes of every projection and the LM head, read once a step."""
        layer = sum(self._bytes(k, n) for k, n in self._layer_mats())
        return self.layers * layer + self._bytes(self.d, self.vocab)

    @property
    def kv_bytes_per_token(self) -> int:
        return self.layers * 2 * self.kv_heads * self.head_dim * KV_BYTES

    @property
    def attn_flops_per_pair(self) -> int:
        return 4 * self.layers * self.heads * self.head_dim


def decode(s: Shapes, steps: int, contexts: list[int]) -> tuple[float, float]:
    """(FLOPs, bytes) of ``steps`` decode steps that emitted one token per
    entry of ``contexts``, each the number of keys that token's step
    attended (its position + 1)."""
    n = len(contexts)
    flops = sum(token_flops(s, c) for c in contexts)
    # weights once per step; per token its embedding row, the KV it reads
    # (its whole context) and the one position it writes
    nbytes = (steps * float(s.weight_bytes) + n * s.d * ACT_BYTES
              + s.kv_bytes_per_token * (sum(contexts) + n))
    return flops, nbytes


def prefill(s: Shapes, rows: list[tuple[int, int]]) -> tuple[float, float]:
    """(FLOPs, bytes) of one prefill launch over ``rows`` of (start, real
    tokens): real tokens only, logits only at each row's last token."""
    flops, nbytes = 0.0, float(s.weight_bytes)
    for start, real in rows:
        pairs = real * start + real * (real + 1) / 2  # causal (query, key)
        flops += (2.0 * s.layer_weights * real + 2.0 * s.head_weights
                  + s.attn_flops_per_pair * pairs)
        # embedding rows, the prefix KV read, the chunk's KV written
        nbytes += real * s.d * ACT_BYTES + s.kv_bytes_per_token * (start + real)
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, pk: dict) -> float:
    return max(flops / pk["bf16_flop_s"], nbytes / pk["hbm_byte_s"])


def token_flops(s: Shapes, context: int) -> float:
    """FLOPs of decoding one token that attends ``context`` keys."""
    return 2.0 * (s.layer_weights + s.head_weights) + s.attn_flops_per_pair * context


def window_flops(s: Shapes, records, t0: float, t1: float) -> tuple[float, float]:
    """(prefill, decode) FLOPs required by the requests' work that finished
    inside [t0, t1] on the host clock: every prompt whose first token came
    then, and every later token emitted then (token j of a request with a
    P-token prompt attends P + j keys)."""
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
