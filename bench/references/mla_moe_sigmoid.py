"""Plain reference of DeepSeek-V3's block as Moonlight-16B-A3B uses it:
latent attention (MLA) with a decoupled rotary key, leading dense SwiGLU
layers, then layers of sigmoid-routed experts with shared experts; RMSNorm,
untied LM head.  Held to one device's share of the experts.

Written from the published configuration and DeepSeek-V3's description
(arXiv:2412.19437, sec. 2.1) with nothing of the program imported.  It
provides what ``bench/references/gqa_rope_swiglu.py``'s header lists
(``model_from_config``, ``make_params``, ``gaps``, ``program_fields``,
``PROGRAM_REQUIRES``, ``work_shapes``) with the same signatures.

The layer, h = RMSNorm(x):

  attention  q = h·W_q → (heads, nope + rope), split into q_nope, q_pe;
             [c | k_pe] = h·W_kva, c = RMSNorm_kv(c) (the kv_rank latent,
             eps 1e-6 as DeepSeek-V3's kv_a_layernorm);
             per head [k_nope | v] = c·W_kvb (columns head-major, each head
             nope then v);
             q_pe and k_pe (one vector shared by every head) rotate by RoPE,
             pairing dimension i with i + rope/2 (rotate-half; the published
             code pairs interleaved dimensions, which for weights drawn
             from a seed is a permutation of W_q's and W_kva's columns);
             score = (q_nope·k_nope + q_pe·k_pe) / sqrt(nope + rope), causal
             softmax, out = softmax·v → W_o.
             Computed expanded: k_nope and v are formed for every position.
  experts    s = sigmoid(h·W_r) in fp32 over all published experts; the
             chosen are the top-k of s + b (b the per-expert correction
             bias); a chosen expert's weight is s_e / Σ s_chosen ×
             routed_scaling; y = Σ over chosen experts held here of
             w_e·E_e(h) + Shared(h), where E_e and Shared are SwiGLU FFNs
             (Shared of n_shared × the expert width).  The held experts are
             computed by a plain loop over them, each on every token,
             weighted by its routing weight (zero where not chosen).  What
             the experts held on other devices add is left out, as the
             device serving this share leaves it out.

Weights (``config["weights"]``, format dense): every projection, the
router and the embedding normal(0, ``init_std``) in ``dtype``, as
DeepSeek-V3 initializes every learnable parameter (0.006; arXiv:2412.19437
sec. 4.2).  At that scale a routed expert's output (three matrices, two of
them multiplied) is small beside attention's, so an expert choice that
rounding flips moves a token's residual a little, as in a trained model
served in bf16; at fan_in^-1/2 every layer's experts move it by as much
as attention, and one flip early in the stack changes most later choices.
The correction bias normal(0, ``score_bias_std``) in fp32; norm scales
1 + normal(0, 0.1).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from bench import work

RMS_EPS = 1e-5
KV_NORM_EPS = 1e-6
VOCAB_BLOCK = 10240  # LM-head columns scored at a time in ``gaps``


@dataclasses.dataclass(frozen=True)
class Model:
    layers: int
    dense_layers: int
    d: int
    heads: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    dense_ff: int
    expert_ff: int
    experts: int  # the router's width: the published routed experts
    held: int  # experts this device holds, from ``offset``
    offset: int
    top_k: int
    shared: int
    scaling: float
    vocab: int
    rope_theta: float
    dtype: str = "bfloat16"
    init_std: float = 0.006
    score_bias_std: float = 0.005


def model_from_config(config: dict) -> Model:
    """The model a configuration file states (Hugging Face key names); the
    file's ``n_routed_experts`` is the share held here, ``published`` the
    router's width and ``deployment.expert_offset`` the first held."""
    w = config["weights"]
    return Model(
        layers=config["num_hidden_layers"],
        dense_layers=config["first_k_dense_replace"],
        d=config["hidden_size"], heads=config["num_attention_heads"],
        kv_rank=config["kv_lora_rank"], nope=config["qk_nope_head_dim"],
        rope=config["qk_rope_head_dim"], v=config["v_head_dim"],
        dense_ff=config["intermediate_size"],
        expert_ff=config["moe_intermediate_size"],
        experts=config["published"]["n_routed_experts"],
        held=config["n_routed_experts"],
        offset=config["deployment"]["expert_offset"],
        top_k=config["num_experts_per_tok"],
        shared=config["n_shared_experts"],
        scaling=float(config["routed_scaling_factor"]),
        vocab=config["vocab_size"], rope_theta=float(config["rope_theta"]),
        dtype=w["dtype"], init_std=w["init_std"],
        score_bias_std=w["score_bias_std"],
    )


PROGRAM_REQUIRES = {"family": "moe", "attention": "mla",
                    "moe_router": "sigmoid_bias", "moe_shared_experts": 2,
                    "first_dense_layers": 1, "pos_enc": "rope",
                    "norm": "rmsnorm", "ffn": "swiglu", "use_bias": False,
                    "tie_embeddings": False}

# what the file must state for this module and the program to compute it
_FILE_REQUIRES = {
    "hidden_act": "silu", "tie_word_embeddings": False,
    "attention_bias": False, "q_lora_rank": None, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "moe_layer_freq": 1,
    "first_k_dense_replace": PROGRAM_REQUIRES["first_dense_layers"],
    "n_shared_experts": PROGRAM_REQUIRES["moe_shared_experts"],
}


def program_fields(config: dict) -> dict:
    """The program's ``ModelConfig`` fields for a configuration file: every
    shape it states, and the share of experts held here."""
    wrong = {k: config.get(k) for k, v in _FILE_REQUIRES.items()
             if config.get(k) != v}
    if wrong:
        raise ValueError(
            f"{config['arch_id']} is not the model this reference computes: "
            f"the file states {wrong}; it computes {_FILE_REQUIRES}")
    m = model_from_config(config)
    return dict(
        n_layers=m.layers, d_model=m.d, n_heads=m.heads, n_kv_heads=m.heads,
        head_dim=m.nope + m.rope, d_ff=m.expert_ff, vocab_size=m.vocab,
        rope_theta=m.rope_theta, mla_kv_rank=m.kv_rank, mla_nope_dim=m.nope,
        mla_rope_dim=m.rope, mla_v_dim=m.v, n_experts=m.experts,
        experts_per_token=m.top_k, moe_routed_scaling=m.scaling,
        experts_held=m.held, expert_offset=m.offset, dense_d_ff=m.dense_ff)


def _attention_mats(m: Model) -> dict[str, tuple[int, int]]:
    return {"wq": (m.d, m.heads * (m.nope + m.rope)),
            "wkv_a": (m.d, m.kv_rank + m.rope),
            "wkv_b": (m.kv_rank, m.heads * (m.nope + m.v)),
            "wo": (m.heads * m.v, m.d)}


def _ffn_mats(d: int, f: int) -> dict[str, tuple[int, int]]:
    return {"wi": (d, f), "wg": (d, f), "wo": (f, d)}


def work_shapes(config: dict) -> work.Shapes:
    """Two layer groups.  The dense layers: the MLA matrices and the dense
    FFN.  The MoE layers: the MLA matrices, the router and the shared
    experts at share 1, and each held expert's three matrices at share
    top_k / published experts.  A token stores kv_rank + rope values a
    layer.  Attention FLOPs a (query, key) pair are those of the absorbed
    form the program serves, over the latent: scores 2 × heads ×
    (kv_rank + rope), values 2 × heads × kv_rank, i.e. 4 × heads × kv_rank
    + 2 × heads × rope (W_kvb's two halves, folded into the query and the
    output, are counted once a token as the (kv_rank, heads × (nope + v))
    matrix)."""
    m = model_from_config(config)
    attn = tuple((k, n, 1.0) for k, n in _attention_mats(m).values())
    kv, pair = m.kv_rank + m.rope, 4 * m.heads * m.kv_rank + 2 * m.heads * m.rope
    dense = work.LayerGroup(
        layers=m.dense_layers,
        mats=attn + tuple((k, n, 1.0) for k, n in
                          _ffn_mats(m.d, m.dense_ff).values()),
        kv_per_token=kv, attn_flops_per_pair=pair)
    share = m.top_k / m.experts
    expert = tuple((k, n, share) for k, n in _ffn_mats(m.d, m.expert_ff).values())
    moe = work.LayerGroup(
        layers=m.layers - m.dense_layers,
        mats=(attn + ((m.d, m.experts, 1.0),)
              + tuple((k, n, 1.0) for k, n in
                      _ffn_mats(m.d, m.shared * m.expert_ff).values())
              + expert * m.held),
        kv_per_token=kv, attn_flops_per_pair=pair)
    return work.Shapes(
        groups=(dense, moe), d=m.d, vocab=m.vocab,
        bytes_per_weight=config["weights"].get("stated_bytes_per_weight", 2))


# ------------------------------------------------------------------ weights


def _dense(key, lead: tuple[int, ...], k: int, n: int, m: Model):
    # drawn in fp32 and rounded in one elementwise fusion: no fp32 copy of
    # a whole leaf is ever held
    return (jax.random.normal(key, (*lead, k, n), jnp.float32)
            * m.init_std).astype(m.dtype)


def _norm(key, shape):
    return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)


def _layers(key, n: int, m: Model, moe: bool) -> dict:
    """``n`` stacked layers in the program's tree layout."""
    keys = iter(jax.random.split(key, 16))
    lead = (n,)
    attn = {name: {"kernel": _dense(next(keys), lead, k, nn, m)}
            for name, (k, nn) in _attention_mats(m).items()}
    attn["kv_norm"] = {"scale": _norm(next(keys), (n, m.kv_rank))}
    out = {"ln1": {"scale": _norm(next(keys), (n, m.d))}, "attn": attn,
           "ln2": {"scale": _norm(next(keys), (n, m.d))}}
    if not moe:
        out["ffn"] = {name: {"kernel": _dense(next(keys), lead, k, nn, m)}
                      for name, (k, nn) in _ffn_mats(m.d, m.dense_ff).items()}
        return out
    held = (n, m.held)
    out["moe"] = {
        "router": {"kernel": _dense(next(keys), lead, m.d, m.experts, m),
                   "score_bias": m.score_bias_std * jax.random.normal(
                       next(keys), (n, m.experts), jnp.float32)},
        "wi": _dense(next(keys), held, m.d, m.expert_ff, m),
        "wg": _dense(next(keys), held, m.d, m.expert_ff, m),
        "wo": _dense(next(keys), held, m.expert_ff, m.d, m),
        "shared": {name: {"kernel": _dense(next(keys), lead, k, nn, m)}
                   for name, (k, nn) in
                   _ffn_mats(m.d, m.shared * m.expert_ff).items()},
    }
    return out


def make_params(m: Model, key) -> dict:
    """The cell's weights in the program's tree layout (run under jit)."""
    kd, kl, ke, kn, kh = jax.random.split(key, 5)
    return {
        "embed": {"embedding": (jax.random.normal(
            ke, (m.vocab, m.d), jnp.float32) * m.init_std).astype(m.dtype)},
        "dense_layers": _layers(kd, m.dense_layers, m, moe=False),
        "layers": _layers(kl, m.layers - m.dense_layers, m, moe=True),
        "final_norm": {"scale": _norm(kn, (m.d,))},
        "lm_head": {"kernel": _dense(kh, (), m.d, m.vocab, m)},
    }


# ------------------------------------------------------------------ forward


def requantize_blocks(w: jax.Array, bits: int) -> jax.Array:
    """Round each block of a (K, N) weight to ``bits``-bit symmetric
    integers with one scale per block (a control's lower precision).  A
    block is 128 x 128, or as wide as 128 and the dimension's greatest
    common divisor allow (2048 x 576 takes 128 x 64)."""
    k, n = w.shape
    bk, bn = math.gcd(k, 128), math.gcd(n, 128)
    q_max = 2 ** (bits - 1) - 1
    b = w.reshape(k // bk, bk, n // bn, bn)
    amax = jnp.abs(b).max(axis=(1, 3), keepdims=True)
    s = jnp.where(amax > 0, amax / q_max, 1.0)
    return (jnp.round(b / s) * s).reshape(k, n)


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, scale, eps=RMS_EPS):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv  # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(f: dict, h, wf):
    return ((jax.nn.silu(h @ wf(f["wi"])) * (h @ wf(f["wg"]))) @ wf(f["wo"]))


def _attention(a: dict, h, m: Model, wf):
    s = h.shape[0]
    pos = jnp.arange(s)
    q = (h @ wf(a["wq"]["kernel"])).reshape(s, m.heads, m.nope + m.rope)
    kv = h @ wf(a["wkv_a"]["kernel"])
    c = _rms(kv[:, :m.kv_rank], _f32(a["kv_norm"]["scale"]), KV_NORM_EPS)
    k_pe = _rope(kv[:, None, m.kv_rank:], pos, m.rope_theta)[:, 0]
    q_pe = _rope(q[..., m.nope:], pos, m.rope_theta)
    kvb = (c @ wf(a["wkv_b"]["kernel"])).reshape(s, m.heads, m.nope + m.v)
    sc = (jnp.einsum("qhn,shn->hqs", q[..., :m.nope], kvb[..., :m.nope])
          + jnp.einsum("qhr,sr->hqs", q_pe, k_pe)) / math.sqrt(m.nope + m.rope)
    sc = jnp.where(pos[:, None] >= pos[None, :], sc, -jnp.inf)
    o = jnp.einsum("hqs,shv->qhv", jax.nn.softmax(sc, axis=-1), kvb[..., m.nope:])
    return o.reshape(s, m.heads * m.v) @ wf(a["wo"]["kernel"])


def route(router: dict, h, m: Model, wf=_f32):
    """(weights (S, top_k), experts (S, top_k)) over all published
    experts."""
    scores = jax.nn.sigmoid(h @ wf(router["kernel"]))
    _, top = jax.lax.top_k(scores + router["score_bias"], m.top_k)
    w = jnp.take_along_axis(scores, top, axis=-1)
    return w / (w.sum(-1, keepdims=True) + 1e-20) * m.scaling, top


def _experts(e: dict, h, m: Model, wf):
    w, top = route(e["router"], h, m, wf)

    def held_expert(y, inp):  # one held expert on every token
        f, expert = inp
        w_e = jnp.where(top == expert, w, 0.0).sum(-1)  # (S,)
        return y + w_e[:, None] * _swiglu(f, h, wf), None

    y = _swiglu({k: v["kernel"] for k, v in e["shared"].items()}, h, wf)
    y, _ = jax.lax.scan(held_expert, y, (
        {k: e[k] for k in ("wi", "wg", "wo")},
        m.offset + jnp.arange(m.held)))
    return y


def hidden(params: dict, tokens: jax.Array, m: Model,
           weight_fn=None) -> jax.Array:
    """(S,) tokens -> (S, d) float32 final-norm output; float32 throughout,
    matrix products at ``highest`` precision.  ``weight_fn`` (if given)
    maps each projection, the router included, before use."""
    wf = _f32 if weight_fn is None else (lambda w: weight_fn(_f32(w)))
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"]["embedding"][tokens])

        def layer(x, p):
            x = x + _attention(p["attn"], _rms(x, _f32(p["ln1"]["scale"])), m, wf)
            h = _rms(x, _f32(p["ln2"]["scale"]))
            if "moe" in p:
                return x + _experts(p["moe"], h, m, wf), None
            return x + _swiglu({k: v["kernel"] for k, v in p["ffn"].items()},
                               h, wf), None

        x, _ = jax.lax.scan(layer, x, params["dense_layers"])
        x, _ = jax.lax.scan(layer, x, params["layers"])
        return _rms(x, _f32(params["final_norm"]["scale"]))


def logits(params: dict, tokens: jax.Array, m: Model) -> jax.Array:
    """(S,) tokens -> (S, V) float32 logits (small vocabularies: ``gaps``
    scores a large one in blocks)."""
    with jax.default_matmul_precision("highest"):
        return hidden(params, tokens, m) @ _f32(params["lm_head"]["kernel"])


def gaps(params: dict, tokens: jax.Array, targets: jax.Array, m: Model,
         control_bits: int = 0) -> tuple[jax.Array, jax.Array]:
    """Per position of ``tokens`` (S,): how far the logit of ``targets``
    (the token served there) lies below the reference's best, and, with
    ``control_bits``, how far the token that the reference with its weights
    rounded to that many bits puts first lies below the reference's best
    (zeros without).  The LM head is scored ``VOCAB_BLOCK`` columns at a
    time, so a 2,048-token sequence's logits are never held whole.  Run
    under jit; padding after a sequence's end is causally invisible to its
    real positions."""
    low = lambda w: requantize_blocks(w, control_bits)  # noqa: E731
    x = hidden(params, tokens, m)
    x_low = hidden(params, tokens, m, low) if control_bits else None
    head = params["lm_head"]["kernel"]
    vb = VOCAB_BLOCK if m.vocab % VOCAB_BLOCK == 0 else m.vocab
    s = tokens.shape[0]

    def block(carry, i):
        best, served, low_best, low_ref = carry
        w = _f32(jax.lax.dynamic_slice_in_dim(head, i * vb, vb, axis=1))
        with jax.default_matmul_precision("highest"):
            lg = x @ w
            ids = i * vb + jnp.arange(vb)
            best = jnp.maximum(best, lg.max(-1))
            served = served + jnp.where(ids[None, :] == targets[:, None],
                                        lg, 0.0).sum(-1)
            if control_bits:
                lo = x_low @ low(w)
                pick = lo.argmax(-1)
                ahead = lo.max(-1) > low_best  # first maximum kept on ties
                low_best = jnp.where(ahead, lo.max(-1), low_best)
                low_ref = jnp.where(ahead, jnp.take_along_axis(
                    lg, pick[:, None], axis=-1)[:, 0], low_ref)
        return (best, served, low_best, low_ref), None

    neg = jnp.full((s,), -jnp.inf, jnp.float32)
    zero = jnp.zeros((s,), jnp.float32)
    (best, served, _, low_ref), _ = jax.lax.scan(
        block, (neg, zero, neg, zero), jnp.arange(m.vocab // vb))
    ctrl = best - low_ref if control_bits else zero
    return best - served, ctrl
