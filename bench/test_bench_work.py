"""Required work, against hand counts at tiny configurations, and pinned
counts of the committed configurations."""
import dataclasses
import json

import pytest

from bench import spec, work

# 1 layer, d 8, 2 heads of 4, 1 KV head, FFN 16, vocab 32:
# q 8x8, k 8x4, v 8x4, o 8x8, wi/wg 8x16, wo 16x8
GQA_MATS = ((8, 8, 1.0), (8, 4, 1.0), (8, 4, 1.0), (8, 8, 1.0),
            (8, 16, 1.0), (8, 16, 1.0), (16, 8, 1.0))
GQA_LAYER = work.LayerGroup(layers=1, mats=GQA_MATS, kv_per_token=2 * 1 * 4,
                            attn_flops_per_pair=4 * 2 * 4)
DENSE = work.Shapes(groups=(GQA_LAYER,), d=8, vocab=32)
# the same with 4x4 blocks, half of each column of blocks kept
SPARSE = work.Shapes(groups=(GQA_LAYER,), d=8, vocab=32,
                     fmt="int8_block_sparse", sparsity=0.5, block=(4, 4))
# two groups: one dense layer (8x16, 16x8), then two layers of an 8x8
# matrix every token multiplies and an 8x16 one a quarter of them do
TWO_GROUPS = work.Shapes(groups=(
    work.LayerGroup(layers=1, mats=((8, 16, 1.0), (16, 8, 1.0)),
                    kv_per_token=8, attn_flops_per_pair=32),
    work.LayerGroup(layers=2, mats=((8, 8, 1.0), (8, 16, 0.25)),
                    kv_per_token=4, attn_flops_per_pair=16),
), d=8, vocab=32)
PK = {"bf16_flop_s": 1e3, "hbm_byte_s": 1e2}


def test_dense_counts():
    assert DENSE.layer_weights == 64 + 32 + 32 + 64 + 128 + 128 + 128 == 576
    assert DENSE.token_weights == 576
    assert DENSE.head_weights == 256
    for t in (1, 2.5, 32):
        assert DENSE.step_weight_bytes(t) == 2 * (576 + 256)
    assert DENSE.kv_bytes_per_token == 1 * 2 * 1 * 4 * 2
    assert DENSE.attn_flops_per_pair == 4 * 2 * 4


def test_sparse_counts_kept_blocks_and_scales():
    # each (k, n) matrix keeps half of its k/4 row blocks in each of n/4
    # columns: half its weights, one fp32 scale per kept 4x4 block
    assert SPARSE.layer_weights == 576 // 2
    assert SPARSE.head_weights == 128
    kept_blocks = (576 + 256) // 2 // 16
    assert SPARSE.step_weight_bytes(1) == (576 + 256) // 2 + 4 * kept_blocks


def test_decode_step():
    flops, nbytes = work.decode(DENSE, steps=2, contexts=[5, 6, 9])
    assert flops == 3 * 2 * (576 + 256) + 32 * (5 + 6 + 9)
    assert nbytes == 2 * 1664 + 3 * 8 * 2 + 16 * (20 + 3)
    assert work.token_flops(DENSE, 5) == 2 * (576 + 256) + 32 * 5


def test_prefill_launch_counts_real_tokens_and_last_logits():
    # row A: chunk of 3 after 4 cached tokens; row B: first chunk of 2
    flops, nbytes = work.prefill(DENSE, [(4, 3), (0, 2)])
    pairs = (3 * 4 + 6) + (0 + 3)
    assert flops == 2 * 576 * 5 + 2 * 2 * 256 + 32 * pairs
    assert nbytes == 1664 + 5 * 8 * 2 + 16 * (7 + 2)


def test_roofline_takes_the_larger_bound():
    assert work.roofline_s(2000.0, 100.0, PK) == pytest.approx(2.0)
    assert work.roofline_s(100.0, 500.0, PK) == pytest.approx(5.0)


def test_window_flops_from_records():
    from bench.window import Record

    recs = [Record(due=0.0, prompt_len=3, max_new=3, emit_t=[1.0, 2.0, 5.0]),
            Record(due=0.0, prompt_len=2, max_new=2, emit_t=[0.5, 1.5])]
    pre, dec = work.window_flops(DENSE, recs, 0.9, 4.0)
    assert pre == work.prefill(DENSE, [(0, 3)])[0]
    assert dec == work.token_flops(DENSE, 4) + work.token_flops(DENSE, 3)


def test_two_groups_stored_and_multiplied_weights():
    assert TWO_GROUPS.layer_weights == (128 + 128) + 2 * (64 + 128) == 640
    assert TWO_GROUPS.token_weights == (128 + 128) + 2 * (64 + 128 / 4) == 448
    assert TWO_GROUPS.kv_bytes_per_token == (8 + 2 * 4) * 2
    assert TWO_GROUPS.attn_flops_per_pair == 32 + 2 * 16
    # bf16: the dense layer 512 B, the share-1 matrices 256 B, the head 512
    # B, and the routed matrices' 512 B read with probability 1 - (3/4)^t
    assert TWO_GROUPS.step_weight_bytes(1) == 512 + 256 + 512 * 0.25 + 512
    assert TWO_GROUPS.step_weight_bytes(3) == 512 + 256 + 512 * (1 - 0.75 ** 3) + 512
    assert TWO_GROUPS.step_weight_bytes(1) == 1408
    assert TWO_GROUPS.step_weight_bytes(3) == 1576


@pytest.mark.parametrize("steps,contexts,flops,nbytes", [
    # t = 1: 2 x (448 + 256) + 64 x 5; 1408 + 8 x 2 + 32 x (5 + 1)
    (1, [5], 1728, 1616),
    # t = 6 / 2 = 3: 6 x 1408 + 64 x 34; 2 x 1576 + 6 x 16 + 32 x (34 + 6)
    (2, [5, 6, 9, 4, 7, 3], 10624, 4528),
])
def test_two_groups_decode(steps, contexts, flops, nbytes):
    assert work.decode(TWO_GROUPS, steps, contexts) == (flops, nbytes)


@pytest.mark.parametrize("rows,flops,nbytes", [
    # t = 1: 2 x 448 + 2 x 256 + 64 x 1 pair; 1408 + 16 + 32 x 1
    ([(0, 1)], 1472, 1456),
    # t = 3: row A 2 tokens after 4 (pairs 2 x 4 + 3), row B 1 token
    ([(4, 2), (0, 1)], (2 * 448 * 2 + 512 + 64 * 11) + 1472,
     1576 + (2 * 16 + 32 * 6) + (16 + 32 * 1)),
])
def test_two_groups_prefill(rows, flops, nbytes):
    assert work.prefill(TWO_GROUPS, rows) == (flops, nbytes)


# (layer_weights, head_weights, step_weight_bytes, kv_bytes_per_token,
# attn_flops_per_pair), and decode / prefill / token FLOPs at fixed
# arguments, as the counts read before the reference stated the shapes
PINNED = {
    "internlm2-1.8b": ((1_509_949_440, 189_530_112, 3_398_959_104, 98_304,
                        196_608),
                       (17115119616.0, 10257551360.0),
                       (657085169664.0, 3422650368.0), 3414097920.0),
    "mistral-nemo-12b-sonic": ((545_259_520, 335_544_320, 881_018_880, 16_384,
                                65_536),
                               (8848146432.0, 2653216768.0),
                               (238237253632.0, 887031808.0), 1766653952.0),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_committed_configs_count_as_pinned(name):
    config = json.loads((spec.BENCH_DIR / "configs" / f"{name}.json").read_text())
    s = spec.reference_module(config).work_shapes(config)
    counts, dec, pre, tok = PINNED[name]
    layer_w, head_w, step_b, kv_b, attn = counts
    assert (s.layer_weights, s.head_weights, s.kv_bytes_per_token,
            s.attn_flops_per_pair) == (layer_w, head_w, kv_b, attn)
    assert s.token_weights == layer_w
    for t in (1, 2.5, 32, 256):
        assert s.step_weight_bytes(t) == step_b
    assert work.decode(s, 3, [100, 200, 300, 5, 7]) == dec
    assert work.prefill(s, [(0, 200), (16, 16)]) == pre
    assert work.token_flops(s, 77) == tok


def test_reference_states_the_hand_built_shapes():
    from bench import tiny_cell

    config = {**tiny_cell.config(), "num_hidden_layers": 1, "hidden_size": 8,
              "num_attention_heads": 2, "num_key_value_heads": 1,
              "head_dim": 4, "intermediate_size": 16, "vocab_size": 32,
              "weights": {"format": "dense", "dtype": "float32"}}
    ref = spec.reference_module(config)
    assert ref.work_shapes(config) == DENSE
    config["weights"] = {"format": "int8_block_sparse", "dtype": "bfloat16",
                         "sparsity": 0.5, "block": [4, 4]}
    assert ref.work_shapes(config) == SPARSE


# windowed counts against an enumeration over the queries: a query at
# position p attends keys p - W + 1 ... p, itself included
def attended(p: int, window: int) -> set[int]:
    return {k for k in range(p + 1) if not window or k > p - window}


@pytest.mark.parametrize("window", [0, 1, 4, 7])
@pytest.mark.parametrize("start,real", [(0, 1), (0, 3), (0, 9), (2, 2),
                                        (3, 6), (5, 1), (6, 7), (9, 4)])
def test_window_pairs_and_prefix_enumerated(start, real, window):
    g = work.LayerGroup(layers=1, mats=(), kv_per_token=1,
                        attn_flops_per_pair=1, window=window)
    queries = range(start, start + real)
    assert g.pairs(start, real) == sum(len(attended(p, window)) for p in queries)
    assert g.prefix_read(start) == len(
        {k for p in queries for k in attended(p, window) if k < start})
    for p in queries:
        assert g.keys(p + 1) == len(attended(p, window))
    if not window or start + real <= window:
        assert g.pairs(start, real) == real * start + real * (real + 1) / 2


# TWO_GROUPS with its second group, two layers, attending a window of 4
WINDOWED = work.Shapes(groups=(
    TWO_GROUPS.groups[0], dataclasses.replace(TWO_GROUPS.groups[1], window=4),
), d=8, vocab=32)


@pytest.mark.parametrize("contexts", [[1, 3], [4, 4], [5, 9], [2, 4, 6, 11]])
def test_windowed_decode(contexts):
    # full group: 32 FLOPs a pair, 16 B of KV a token; windowed group:
    # 2 x 16 FLOPs a pair and 2 x 4 x 2 B a token, over min(c, 4) keys
    n = len(contexts)
    keys = [min(c, 4) for c in contexts]
    flops, nbytes = work.decode(WINDOWED, 1, contexts)
    assert flops == sum(2 * (448 + 256) + 32 * c + 32 * k
                        for c, k in zip(contexts, keys))
    assert nbytes == (WINDOWED.step_weight_bytes(n) + n * 16
                      + 16 * (sum(contexts) + n) + 16 * (sum(keys) + n))
    for c, k in zip(contexts, keys):
        assert work.token_flops(WINDOWED, c) == 2 * (448 + 256) + 32 * c + 32 * k


@pytest.mark.parametrize("rows", [[(0, 3)], [(0, 9)], [(2, 5), (6, 1)],
                                  [(7, 6), (0, 2)]])
def test_windowed_prefill_enumerated(rows):
    flops, nbytes = work.prefill(WINDOWED, rows)
    want_f = want_b = 0
    for start, real in rows:
        queries = range(start, start + real)
        full = sum(len(attended(p, 0)) for p in queries)
        win = sum(len(attended(p, 4)) for p in queries)
        prefix = len({k for p in queries for k in attended(p, 4) if k < start})
        want_f += 2 * 448 * real + 2 * 256 + 32 * full + 32 * win
        want_b += real * 16 + 16 * (start + real) + 16 * (prefix + real)
    assert flops == want_f
    assert nbytes == WINDOWED.step_weight_bytes(sum(r for _, r in rows)) + want_b


@pytest.mark.parametrize("window", [12, 13, 100])
def test_a_window_past_every_context_counts_as_none(window):
    wide = work.Shapes(groups=(
        TWO_GROUPS.groups[0],
        dataclasses.replace(TWO_GROUPS.groups[1], window=window),
    ), d=8, vocab=32)
    contexts, rows = [5, 12, 1, 9], [(4, 8), (0, 12), (11, 1)]
    assert work.decode(wide, 2, contexts) == work.decode(TWO_GROUPS, 2, contexts)
    assert work.prefill(wide, rows) == work.prefill(TWO_GROUPS, rows)
    assert all(work.token_flops(wide, c) == work.token_flops(TWO_GROUPS, c)
               for c in contexts)
    from bench.window import Record

    recs = [Record(due=0.0, prompt_len=7, max_new=6, emit_t=[0.5, 1.0, 2.0,
                                                             3.0, 4.0, 5.0])]
    assert (work.window_flops(wide, recs, 0.0, 9.0)
            == work.window_flops(TWO_GROUPS, recs, 0.0, 9.0))
