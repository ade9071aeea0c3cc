"""Plain reference of a decoder of the Llama kind: RMSNorm, rotary positions
(rotate-half), grouped-query attention, SwiGLU feed-forward, untied LM head.

Written from the published description with nothing of the program
imported.  It also makes the weights a cell serves, from the seed, in the
layout of the program's parameter tree (nested dicts, layer weights
stacked on a leading axis), so the program receives them as input and the
reference reads the same values back.

What every reference module provides (``bench/run.py`` and ``bench/check.py``
call nothing else, so a configuration of another architecture joins with a
module of its own and no edit to the harness):

  model_from_config(config)  the model the configuration file states
  make_params(model, key)    the cell's weights, in the program's tree
                             layout (run under jit, ``model`` static)
  gaps(params, tokens, targets, model, control_bits)
                             per position, the served token's and the
                             control's gap below the reference's best
  program_fields(config)     the program's ``ModelConfig`` fields, by name,
                             with the values the file states; raises where
                             the file states a model this module does not
                             compute
  PROGRAM_REQUIRES           ``ModelConfig`` fields and the values that say
                             the program computes what this module does;
                             the harness refuses any other before a weight
                             is made
  work_shapes(config)        the ``bench.work.Shapes`` that the roofline
                             and MFU metrics count the work from

Weight formats (``config["weights"]["format"]``):

  dense              every weight normal(0, fan_in^-1/2), in ``dtype``.
  int8_block_sparse  every projection and the LM head hold, in each
                     128x128 block that is kept, int8 values times a
                     power-of-two scale of the block's own (drawn per layer
                     and block, over a range of 2^6), and zeros in the
                     blocks that are not; ``sparsity`` of the blocks of each
                     output column of blocks are zero.  A block's largest
                     magnitude is 127 x its scale, so symmetric per-block
                     int8 quantization (scale = max|block| / 127) gives back
                     the same int8 values and the same scales: the
                     program's quantization of these weights is exact, and
                     a scale read from another block, layer or matrix
                     changes what it computes.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from bench import work

RMS_EPS = 1e-5
Q_MAX = 127  # symmetric int8
SCALE_SHIFTS = range(-3, 4)  # a block's scale: the matrix's times 2^shift


@dataclasses.dataclass(frozen=True)
class Model:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    rope_theta: float
    fmt: str = "dense"
    dtype: str = "float32"
    sparsity: float = 0.0
    block: tuple[int, int] = (128, 128)


def model_from_config(config: dict) -> Model:
    """The model a configuration file states (Hugging Face key names)."""
    w = config["weights"]
    d, h = config["hidden_size"], config["num_attention_heads"]
    return Model(
        layers=config["num_hidden_layers"], d=d, heads=h,
        kv_heads=config["num_key_value_heads"],
        head_dim=config.get("head_dim", d // h),
        ffn=config["intermediate_size"], vocab=config["vocab_size"],
        rope_theta=float(config["rope_theta"]),
        fmt=w["format"], dtype=w["dtype"], sparsity=w.get("sparsity", 0.0),
        block=tuple(w.get("block", (128, 128))),
    )


PROGRAM_REQUIRES = {"family": "dense", "pos_enc": "rope", "norm": "rmsnorm",
                    "ffn": "swiglu", "use_bias": False, "tie_embeddings": False}


def program_fields(config: dict) -> dict:
    """The program's ``ModelConfig`` fields for a configuration file: every
    shape it states."""
    if (config["hidden_act"], config["tie_word_embeddings"]) != ("silu", False):
        raise ValueError(
            f"{config['arch_id']} is not the decoder this reference computes: "
            f"hidden_act {config['hidden_act']!r}, tie_word_embeddings "
            f"{config['tie_word_embeddings']!r}; it computes 'silu', False")
    m = model_from_config(config)
    return dict(n_layers=m.layers, d_model=m.d, n_heads=m.heads,
                n_kv_heads=m.kv_heads, head_dim=m.head_dim, d_ff=m.ffn,
                vocab_size=m.vocab, rope_theta=m.rope_theta)


def work_shapes(config: dict) -> work.Shapes:
    """One group of alike layers, every projection multiplied by every
    token; K and V of every KV head stored a token; scores and values at
    4 x heads x head_dim FLOPs a (query, key) pair."""
    m = model_from_config(config)
    layer = work.LayerGroup(
        layers=m.layers,
        mats=tuple((k, n, 1.0) for _, k, n in projection_shapes(m).values()),
        kv_per_token=2 * m.kv_heads * m.head_dim,
        attn_flops_per_pair=4 * m.heads * m.head_dim)
    return work.Shapes(
        groups=(layer,), d=m.d, vocab=m.vocab, fmt=m.fmt,
        bytes_per_weight=config["weights"].get("stated_bytes_per_weight", 2),
        sparsity=m.sparsity, block=m.block)


def projection_shapes(m: Model) -> dict[str, tuple[str, int, int]]:
    """name -> (parent key, K, N) of every stacked layer projection."""
    qd, kvd = m.heads * m.head_dim, m.kv_heads * m.head_dim
    return {"wq": ("attn", m.d, qd), "wk": ("attn", m.d, kvd),
            "wv": ("attn", m.d, kvd), "wo": ("attn", qd, m.d),
            "wi": ("ffn", m.d, m.ffn), "wg": ("ffn", m.d, m.ffn),
            "wo_ffn": ("ffn", m.ffn, m.d)}


# ------------------------------------------------------------------ weights


def _sparse_int8(key, lead: tuple[int, ...], k: int, n: int, m: Model):
    """(*lead, k, n) weight: kept blocks hold int8 x 2^-(e + shift), one
    shift per block, the rest zero."""
    bk, bn = m.block
    kb, nb = k // bk, n // bn
    keep = max(int(round(kb * (1.0 - m.sparsity))), 1)
    kq, ks, kk, ke = jax.random.split(key, 4)
    shape = (*lead, kb, bk, nb, bn)
    q = jax.random.randint(kq, shape, -Q_MAX, Q_MAX + 1)
    # one element of every block at full scale, so max|block| = 127 x scale
    sign = jnp.where(jax.random.bernoulli(ks, 0.5, (*lead, kb, 1, nb, 1)),
                     Q_MAX, -Q_MAX)
    corner = ((jax.lax.broadcasted_iota(jnp.int32, shape, len(lead) + 1) == 0)
              & (jax.lax.broadcasted_iota(jnp.int32, shape, len(lead) + 3) == 0))
    q = jnp.where(corner, sign, q)
    # balanced pruning: `keep` of the kb blocks of each output block column
    rank = jnp.argsort(jnp.argsort(
        jax.random.uniform(kk, (*lead, kb, nb)), axis=-2), axis=-2)
    kept = (rank < keep)[..., :, None, :, None]
    shift = jax.random.randint(ke, (*lead, kb, 1, nb, 1), SCALE_SHIFTS.start,
                               SCALE_SHIFTS.stop)
    # std(q) = 73.3; e keeps each output's variance near 1 / fan-in
    spread = sum(4.0 ** s for s in SCALE_SHIFTS) / len(SCALE_SHIFTS)
    e = round(math.log2(73.3 * math.sqrt(k * keep / kb * spread)))
    # 2^-(e + shift) built from its float32 bits, so exact on every backend
    scale = jax.lax.bitcast_convert_type(
        ((127 - e - shift) << 23).astype(jnp.int32), jnp.float32)
    w = jnp.where(kept, q, 0).astype(jnp.float32) * scale
    return w.reshape(*lead, k, n).astype(m.dtype)


def _dense(key, lead: tuple[int, ...], k: int, n: int, m: Model):
    return (jax.random.normal(key, (*lead, k, n), jnp.float32)
            * k ** -0.5).astype(m.dtype)


def make_params(m: Model, key) -> dict:
    """The cell's weights in the program's tree layout (run under jit)."""
    mat = _sparse_int8 if m.fmt == "int8_block_sparse" else _dense
    keys = iter(jax.random.split(key, 16))
    lead = (m.layers,)
    layers = {"attn": {}, "ffn": {}}
    for name, (parent, k, n) in projection_shapes(m).items():
        layers[parent][name.removesuffix("_ffn")] = {
            "kernel": mat(next(keys), lead, k, n, m)}
    for ln in ("ln1", "ln2"):
        layers[ln] = {"scale": 1.0 + 0.1 * jax.random.normal(
            next(keys), (m.layers, m.d), jnp.float32)}
    return {
        "embed": {"embedding": jax.random.normal(
            next(keys), (m.vocab, m.d), jnp.float32).astype(m.dtype)},
        "layers": layers,
        "final_norm": {"scale": 1.0 + 0.1 * jax.random.normal(
            next(keys), (m.d,), jnp.float32)},
        "lm_head": {"kernel": mat(next(keys), (), m.d, m.vocab, m)},
    }


# ------------------------------------------------------------------ forward


def requantize_blocks(w: jax.Array, block: tuple[int, int], bits: int) -> jax.Array:
    """Round each (bk, bn) block of a (K, N) weight to ``bits``-bit symmetric
    integers with one scale per block (a control's lower precision)."""
    k, n = w.shape
    bk, bn = block
    q_max = 2 ** (bits - 1) - 1
    b = w.reshape(k // bk, bk, n // bn, bn)
    amax = jnp.abs(b).max(axis=(1, 3), keepdims=True)
    s = jnp.where(amax > 0, amax / q_max, 1.0)
    return (jnp.round(b / s) * s).reshape(k, n)


def _rms(x, scale):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + RMS_EPS) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv  # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(params: dict, tokens: jax.Array, m: Model,
            weight_fn=None) -> jax.Array:
    """(S,) tokens -> (S, V) float32 logits; float32 throughout, matrix
    products at ``highest`` precision.  ``weight_fn`` (if given) maps each
    projection and the LM head before use (a control's lower precision)."""
    f32 = lambda a: a.astype(jnp.float32)
    wf = (lambda w: f32(w)) if weight_fn is None else (lambda w: weight_fn(f32(w)))
    s = tokens.shape[0]
    pos = jnp.arange(s)
    g = m.heads // m.kv_heads
    causal = pos[:, None] >= pos[None, :]
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"]["embedding"])[tokens]

        def layer(x, p):
            a = p["attn"]
            h = _rms(x, f32(p["ln1"]["scale"]))
            q = (h @ wf(a["wq"]["kernel"])).reshape(s, m.kv_heads, g, m.head_dim)
            k = (h @ wf(a["wk"]["kernel"])).reshape(s, m.kv_heads, m.head_dim)
            v = (h @ wf(a["wv"]["kernel"])).reshape(s, m.kv_heads, m.head_dim)
            q = _rope(q.reshape(s, m.heads, m.head_dim), pos, m.rope_theta
                      ).reshape(s, m.kv_heads, g, m.head_dim)
            k = _rope(k, pos, m.rope_theta)
            sc = jnp.einsum("qkgd,skd->kgqs", q, k) * m.head_dim ** -0.5
            sc = jnp.where(causal, sc, -jnp.inf)
            o = jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(sc, axis=-1), v)
            x = x + o.reshape(s, m.heads * m.head_dim) @ wf(a["wo"]["kernel"])
            f = p["ffn"]
            h = _rms(x, f32(p["ln2"]["scale"]))
            u = jax.nn.silu(h @ wf(f["wi"]["kernel"])) * (h @ wf(f["wg"]["kernel"]))
            return x + u @ wf(f["wo"]["kernel"]), None

        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = _rms(x, f32(params["final_norm"]["scale"]))
        return x @ wf(params["lm_head"]["kernel"])


def gaps(params: dict, tokens: jax.Array, targets: jax.Array, m: Model,
         control_bits: int = 0) -> tuple[jax.Array, jax.Array]:
    """Per position of ``tokens`` (S,): how far the logit of ``targets``
    (the token served there) lies below the reference's best, and, with
    ``control_bits``, how far the token that the reference with its weights
    rounded to that many bits puts first lies below the reference's best
    (zeros without).  Run under jit; padding after a sequence's end is
    causally invisible to its real positions."""
    logits = forward(params, tokens, m)
    best = logits.max(-1)
    served = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    ctrl = jnp.zeros_like(best)
    if control_bits:
        low = forward(params, tokens, m, weight_fn=lambda w: requantize_blocks(
            w, m.block, control_bits))
        pick = low.argmax(-1)
        ctrl = best - jnp.take_along_axis(logits, pick[:, None], axis=-1)[:, 0]
    return best - served, ctrl
