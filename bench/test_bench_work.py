"""Required work, against hand counts at a tiny configuration."""
import pytest

from bench import work

# 1 layer, d 8, 2 heads of 4, 1 KV head, FFN 16, vocab 32
DENSE = work.Shapes(layers=1, d=8, heads=2, kv_heads=1, head_dim=4, ffn=16,
                    vocab=32)
# the same with 4x4 blocks, half of each column of blocks kept
SPARSE = work.Shapes(layers=1, d=8, heads=2, kv_heads=1, head_dim=4, ffn=16,
                     vocab=32, fmt="int8_block_sparse", sparsity=0.5,
                     block=(4, 4))
PK = {"bf16_flop_s": 1e3, "hbm_byte_s": 1e2}


def test_dense_counts():
    # q 8x8, k 8x4, v 8x4, o 8x8, wi/wg 8x16, wo 16x8
    assert DENSE.layer_weights == 64 + 32 + 32 + 64 + 128 + 128 + 128 == 576
    assert DENSE.head_weights == 256
    assert DENSE.weight_bytes == 2 * (576 + 256)
    assert DENSE.kv_bytes_per_token == 1 * 2 * 1 * 4 * 2
    assert DENSE.attn_flops_per_pair == 4 * 2 * 4


def test_sparse_counts_kept_blocks_and_scales():
    # each (k, n) matrix keeps half of its k/4 row blocks in each of n/4
    # columns: half its weights, one fp32 scale per kept 4x4 block
    assert SPARSE.layer_weights == 576 // 2
    assert SPARSE.head_weights == 128
    kept_blocks = (576 + 256) // 2 // 16
    assert SPARSE.weight_bytes == (576 + 256) // 2 + 4 * kept_blocks


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
