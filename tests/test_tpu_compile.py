"""Every SONIC kernel entry point compiles for a TPU v5e chip.

Compiled for a described ``v5e:2x2`` topology (the TPU compiler is
installed; no chip is attached), at tinyllama-1.1b's FFN projection
(K 2048 × N 5632, 128×128 blocks, 75% block sparsity, 64 clusters, and 256
for a codebook wider than one 128-lane row), with
decode (M 4) and prefill (M 256) rows of bf16 activations.  Interpret mode
cannot show what these show: Mosaic refuses unaligned blocks, gathers it
cannot lower and vector loads of scalars.  Beside the kernels, the
held-expert layer at Moonlight's widths is compiled to show which of its
forms copies each layer's expert weights out of the stack.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.moonlight_16b_a3b import CONFIG as MOONLIGHT
from repro.core.sonic_layers import BlockSparseWeight, BlockSparseWeightInt8
from repro.kernels.block_sparse_matmul.ops import (
    block_sparse_matmul,
    block_sparse_matmul_int8,
)
from repro.kernels.clustered_matmul.ops import clustered_matmul
from repro.kernels.sonic_matmul.ops import (
    SonicWeight,
    sonic_matmul,
    sonic_matmul_int8,
    sonic_matvec,
    sonic_matvec_int8,
)
from repro.kernels.sparse_matvec.ops import sparse_matvec
from repro.models import moe as M

K, N, BLOCK, CLUSTERS = 2048, 5632, 128, 64
KB, NB, R = K // BLOCK, N // BLOCK, K // BLOCK // 4  # 75% of K-blocks pruned
KNZ = K // 4  # compressed activation width for sparse_matvec


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _entry_args(name, m, sds):
    """(entry point, abstract arguments) of one kernel entry point."""
    x = sds((m, K), jnp.bfloat16)
    sonic = SonicWeight(sds((NB, R, BLOCK, BLOCK), jnp.int8),
                        sds((CLUSTERS,), jnp.float32),
                        sds((NB, R), jnp.int32), KB)
    int8 = BlockSparseWeightInt8(sds((NB, R, BLOCK, BLOCK), jnp.int8),
                                 sds((NB, R), jnp.float32),
                                 sds((NB, R), jnp.int32), KB)
    dense = BlockSparseWeight(sds((NB, R, BLOCK, BLOCK), jnp.float32),
                              sds((NB, R), jnp.int32), KB)
    return {
        "sonic_matmul": (sonic_matmul, (x, sonic)),
        "sonic_matvec": (sonic_matvec, (x, sonic)),
        "sonic_matmul_int8": (sonic_matmul_int8, (x, int8)),
        "sonic_matvec_int8": (sonic_matvec_int8, (x, int8)),
        "block_sparse_matmul": (block_sparse_matmul, (x, dense)),
        "block_sparse_matmul_int8": (block_sparse_matmul_int8, (x, int8)),
        "clustered_matmul": (clustered_matmul,
                             (x, sds((K, N), jnp.int8),
                              sds((CLUSTERS,), jnp.float32))),
        "sparse_matvec": (sparse_matvec,
                          (sds((m, KNZ), jnp.bfloat16), sds((KNZ,), jnp.int32),
                           sds((K, 1, N), jnp.float32))),  # its row_table
        "clustered_matmul_256": (clustered_matmul,
                                 (x, sds((K, N), jnp.int32),
                                  sds((256,), jnp.float32))),
    }[name]


@pytest.mark.parametrize("m", [4, 256], ids=["decode", "prefill"])
@pytest.mark.parametrize("name", [
    "sonic_matmul", "sonic_matvec", "sonic_matmul_int8", "sonic_matvec_int8",
    "block_sparse_matmul", "block_sparse_matmul_int8", "clustered_matmul",
    "sparse_matvec", "clustered_matmul_256",
])
def test_kernel_entry_point_compiles_for_v5e(one_chip, name, m):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _entry_args(name, m, sds)
    compiled = fn.lower(*args).compile()  # raises what the chip would refuse
    assert "tpu_custom_call" in compiled.as_text()
    assert jax.eval_shape(fn, *args).shape == (m, N)


@pytest.mark.parametrize("c", [1, 5], ids=["decode", "verify"])
@pytest.mark.parametrize("layout", ["paged", "dense"])
def test_paged_decode_attention_compiles_for_v5e(one_chip, layout, c):
    """The decode attention kernel at internlm2-1.8b's widths (16 / 8 heads
    of 128, 32 slots, max_len 2,048): over the stacked bf16 pool (24
    layers, blocks of 16) and over the dense layout's rows.  The pool or
    cache goes into the kernel as it is: no copy of it is made."""
    from repro.kernels.paged_attention import (
        decode_walk,
        dense_decode_attention,
        paged_decode_attention,
    )

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    b, h, kh, dh, max_len = 32, 16, 8, 128, 2048
    q = sds((b, c, h, dh), jnp.bfloat16)
    pos = sds((b,), jnp.int32)
    live = sds((b,), jnp.bool_)
    if layout == "paged":
        bl = 16
        kv = sds((24, 2592, bl, kh, dh), jnp.bfloat16)
        table = sds((b, max_len // bl), jnp.int32)

        def fn(q, k, v, table, pos, live):
            return paged_decode_attention(q, k, v, jnp.int32(3), table, pos,
                                          decode_walk(pos, c, live, bl))

        args = (q, kv, kv, table, pos, live)
    else:
        kv = sds((b, max_len, kh, dh), jnp.bfloat16)
        fn, args = dense_decode_attention, (q, kv, kv, pos, live)
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    shape = ",".join(map(str, kv.shape))
    assert not re.search(rf"= bf16\[{shape}\]\S* (copy|transpose)\(", hlo)
    assert jax.eval_shape(fn, *args).shape == (b, c, h, dh)


def _top_level_ops(hlo: str) -> list[str]:
    """The instructions of a compiled module's computations that are not
    fused into another: the ops the chip runs as ops of their own."""
    fused = set(re.findall(r"\bcalls=(%[\w.\-]+)", hlo))
    out, keep = [], False
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(", line)
        if head:
            keep = head.group(1) not in fused
        elif keep and re.match(r"\s+(ROOT )?%\S+ = ", line):
            out.append(line)
    return out


@pytest.mark.parametrize("form", ["all_held", "grouped"])
def test_held_experts_read_their_stacked_weights_in_place(one_chip, form,
                                                          monkeypatch):
    """Moonlight's held-expert layer (16 of 64 experts held, d 2,048, f
    1,408, top-6) in a scan over 3 stacked layers, at a decode launch of 32
    tokens: the all-held form reads each layer's ``wi``/``wg``/``wo`` slice
    where it lies, so no op of its own writes a slice-shaped array.  The
    grouped form, which ``jax.lax.ragged_dot`` takes whole, shows the
    copies this looks for."""
    if form == "grouped":
        monkeypatch.setattr(M, "ALL_HELD_MAX_TOKENS", 0)
    cfg = dataclasses.replace(MOONLIGHT, param_dtype="bfloat16")
    n_layers, t, e, d, f = 3, 32, cfg.n_experts_held, cfg.d_model, cfg.d_ff
    assert (e, d, f, cfg.experts_per_token) == (16, 2048, 1408, 6)
    shapes = jax.eval_shape(lambda k: M.moe_held_init(k, cfg),
                            jax.random.PRNGKey(0))
    stacked = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct((n_layers, *a.shape), a.dtype,
                                       sharding=one_chip), shapes)
    x = jax.ShapeDtypeStruct((t, 1, d), jnp.bfloat16, sharding=one_chip)

    def layers(p, x):
        def body(x, lp):
            y, _ = M.moe_held_apply(lp, cfg, x)
            return x + y, None

        return jax.lax.scan(body, x, p)[0]

    hlo = jax.jit(layers).lower(stacked, x).compile().as_text()
    slice_shaped = [op for op in _top_level_ops(hlo)
                    if re.search(rf"= bf16\[({e},{d},{f}|{e},{f},{d})\]", op)]
    if form == "all_held":
        assert not slice_shaped, slice_shaped
    else:
        assert len(slice_shaped) == 3
