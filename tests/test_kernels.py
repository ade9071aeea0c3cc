"""Per-kernel allclose vs the pure-jnp oracle, sweeping shapes and dtypes
(interpret=True on CPU — the kernels target TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.clustering import ClusteringConfig, pack_clustered
from repro.core.sonic_layers import make_block_sparse
from repro.kernels.block_sparse_matmul.ops import block_sparse_matmul
from repro.kernels.block_sparse_matmul.ref import block_sparse_matmul_ref
from repro.kernels.clustered_matmul.ops import clustered_matmul
from repro.kernels.clustered_matmul.ref import clustered_matmul_ref
from repro.kernels.sonic_matmul.ops import (
    DECODE_M_THRESHOLD, make_sonic_weight, sonic_matmul, sonic_matvec,
)
from repro.kernels.sonic_matmul.ref import sonic_matmul_ref, sonic_matvec_ref
from repro.kernels.sparse_matvec.kernel import row_table
from repro.kernels.sparse_matvec.ops import sparse_matvec, topk_sparse_matmul
from repro.kernels.sparse_matvec.ref import sparse_matvec_ref

_TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5), jnp.bfloat16: dict(rtol=2e-2, atol=2e-1)}


@pytest.mark.parametrize("m,k,n,c", [(8, 128, 128, 8), (16, 256, 256, 64),
                                     (32, 512, 128, 16), (5, 256, 384, 64),
                                     (8, 256, 256, 300)])  # codebook > 1 row
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_clustered_matmul(m, k, n, c, dtype):
    w = jax.random.normal(jax.random.PRNGKey(0), (k, n))
    cw = pack_clustered(w, ClusteringConfig(num_clusters=c))
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k), dtype)
    got = clustered_matmul(x, cw.indices, cw.codebook, bm=8, bn=128, bk=128)
    want = clustered_matmul_ref(x, cw.indices, cw.codebook)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_TOL[dtype]
    )


@pytest.mark.parametrize("m,k,n,block,sp", [
    (8, 256, 128, (64, 64), 0.5),
    (16, 512, 256, (128, 128), 0.75),
    (8, 128, 256, (64, 128), 0.0),
    (3, 256, 128, (128, 64), 0.25),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_sparse_matmul(m, k, n, block, sp, dtype):
    w = jax.random.normal(jax.random.PRNGKey(0), (k, n))
    bw = make_block_sparse(w, sp, block)
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k), dtype)
    got = block_sparse_matmul(x, bw, bm=8)
    want = block_sparse_matmul_ref(x, bw.values, bw.indices, bw.k_blocks)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_TOL[dtype]
    )


@pytest.mark.parametrize("b,k,n,knz", [(1, 256, 512, 64), (4, 512, 1024, 100),
                                       (8, 128, 512, 128), (2, 256, 256, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sparse_matvec(b, k, n, knz, dtype):
    wt = jax.random.normal(jax.random.PRNGKey(0), (k, n))
    idx = jnp.sort(jax.random.permutation(jax.random.PRNGKey(2), k)[:knz]).astype(jnp.int32)
    x_nz = jax.random.normal(jax.random.PRNGKey(3), (b, knz), dtype)
    got = sparse_matvec(x_nz, idx, wt)
    want = sparse_matvec_ref(x_nz, idx, wt)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_TOL[dtype]
    )


@pytest.mark.parametrize("knz", [256, 300, 600])  # one chunk, padded, three
def test_sparse_matvec_row_table(knz):
    """The stored (K, 1, N) row table gives what the (K, N) weight gives,
    across whole and padded chunks of kept rows."""
    wt = jax.random.normal(jax.random.PRNGKey(0), (640, 256))
    idx = jnp.sort(
        jax.random.permutation(jax.random.PRNGKey(2), 640)[:knz]
    ).astype(jnp.int32)
    x_nz = jax.random.normal(jax.random.PRNGKey(3), (3, knz))
    got = sparse_matvec(x_nz, idx, row_table(wt))
    assert got.shape == (3, 256)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(sparse_matvec(x_nz, idx, wt)))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(sparse_matvec_ref(x_nz, idx, wt)),
                               rtol=2e-5, atol=2e-4)


def test_topk_sparse_matmul_exact_on_sparse_input():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 256))
    mask = jax.random.uniform(jax.random.PRNGKey(1), (256,)) < 0.3
    x = x * mask
    wt = jax.random.normal(jax.random.PRNGKey(2), (256, 512))
    got = topk_sparse_matmul(x, wt, k=int(mask.sum()))
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ wt), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("sp,c", [(0.5, 64), (0.75, 16), (0.0, 8)])
def test_sonic_matmul_fused(sp, c):
    w = jax.random.normal(jax.random.PRNGKey(0), (256, 256))
    sw = make_sonic_weight(w, sparsity=sp, block=(64, 64), num_clusters=c)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 256))
    got = sonic_matmul(x, sw, bm=8)
    want = sonic_matmul_ref(x, sw.idx_values, sw.codebook, sw.indices, sw.k_blocks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("m", [1, 2, 4, 7])
def test_sonic_matmul_decode_dispatch(m):
    """Flattened M below the tile threshold routes through the unpadded
    matvec kernel and stays exact."""
    assert m < DECODE_M_THRESHOLD
    w = jax.random.normal(jax.random.PRNGKey(0), (256, 128))
    sw = make_sonic_weight(w, sparsity=0.5, block=(64, 64), num_clusters=32)
    x = jax.random.normal(jax.random.PRNGKey(m), (m, 256))
    got = sonic_matmul(x, sw)
    want = sonic_matmul_ref(x, sw.idx_values, sw.codebook, sw.indices, sw.k_blocks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_sonic_matvec_shapes():
    w = jax.random.normal(jax.random.PRNGKey(0), (128, 128))
    sw = make_sonic_weight(w, sparsity=0.25, block=(32, 32), num_clusters=16)
    for shape in [(128,), (3, 128)]:
        x = jax.random.normal(jax.random.PRNGKey(1), shape)
        got = sonic_matvec(x, sw)
        want = sonic_matvec_ref(x, sw.idx_values, sw.codebook, sw.indices,
                                sw.k_blocks)
        assert got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_sparse_matvec_decode_leading_dims():
    """(B, 1, knz) decode activations flatten into kernel rows unpadded."""
    wt = jax.random.normal(jax.random.PRNGKey(0), (128, 256))
    idx = jnp.sort(
        jax.random.permutation(jax.random.PRNGKey(2), 128)[:32]
    ).astype(jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 1, 32))
    got = sparse_matvec(x, idx, wt)
    want = sparse_matvec_ref(x.reshape(3, 32), idx, wt).reshape(3, 1, 256)
    assert got.shape == (3, 1, 256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_sonic_mode_linear_apply_kernel_vs_fallback():
    """The 'sonic' execution path: Pallas kernel ≡ jnp fallback, decode and
    prefill shapes."""
    from repro.core.sonic_layers import (
        SonicExecutionConfig, convert_linear, sonic_linear_apply,
    )
    import dataclasses

    w = jax.random.normal(jax.random.PRNGKey(0), (128, 128))
    kcfg = SonicExecutionConfig(mode="sonic", use_kernel=True,
                                weight_sparsity=0.5, block=(32, 32))
    fcfg = dataclasses.replace(kcfg, use_kernel=False)
    p = convert_linear(w, kcfg)
    for shape in [(2, 1, 128), (4, 16, 128)]:
        x = jax.random.normal(jax.random.PRNGKey(2), shape)
        got = sonic_linear_apply(p, x, kcfg)
        want = sonic_linear_apply(p, x, fcfg)
        assert got.shape == want.shape == (*shape[:-1], 128)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_sonic_weight_bytes_shrink():
    w = jax.random.normal(jax.random.PRNGKey(0), (512, 512))
    sw = make_sonic_weight(w, sparsity=0.75, block=(128, 128), num_clusters=64)
    dense_bytes = 512 * 512 * 2  # bf16
    sonic_bytes = sw.idx_values.size + sw.indices.size * 4 + sw.codebook.size * 4
    assert sonic_bytes < dense_bytes / 6  # ≥6× weight-traffic reduction


def test_gradients_flow_through_fallback_paths():
    """The jnp fallbacks (used in training) must be differentiable."""
    from repro.core.sonic_layers import (
        SonicExecutionConfig, convert_linear, sonic_linear_apply,
    )
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64))
    cfg = SonicExecutionConfig(mode="topk", topk_frac=0.5)
    p = convert_linear(w, cfg)

    def loss(x):
        return sonic_linear_apply(p, x, cfg).sum()

    g = jax.grad(loss)(x)
    assert g.shape == x.shape and not bool(jnp.isnan(g).any())


# ------------------------------------------- decode-edge property sweeps
#
# ISSUE 3 satellite: kernel/ref equivalence at the shapes the paged serving
# decode path actually produces — M=1 rows, K/N that are not multiples of
# the 128-default tile, all-zero activation rows, and the density extremes
# (sparsity 0 keeps every block; sparsity→1 keeps the enforced minimum of
# one K-block per N-block).


@pytest.mark.parametrize("k,n,block", [
    (192, 320, (64, 64)),   # K/N not multiples of the 128 default
    (96, 128, (32, 64)),    # rectangular blocks
    (128, 384, (64, 128)),
])
@pytest.mark.parametrize("sp", [0.0, 0.5, 0.95])
def test_sonic_matvec_m1_offblock_shapes_and_density_extremes(k, n, block, sp):
    """M=1 (the decode row) through the matvec kernel at awkward K/N and
    both density extremes stays exact vs the oracle."""
    w = jax.random.normal(jax.random.PRNGKey(0), (k, n))
    sw = make_sonic_weight(w, sparsity=sp, block=block, num_clusters=16)
    if sp >= 0.95:  # balanced pruning floors at one kept K-block per N-block
        assert sw.indices.shape[1] == 1
    x = jax.random.normal(jax.random.PRNGKey(1), (1, k))
    got = sonic_matvec(x, sw)
    want = sonic_matvec_ref(x, sw.idx_values, sw.codebook, sw.indices,
                            sw.k_blocks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_sonic_matvec_all_zero_row_is_exactly_zero():
    """A fully-masked decode row (e.g. an eos-pinned slot with zeroed
    hidden state) must produce exactly 0.0, not accumulated noise."""
    w = jax.random.normal(jax.random.PRNGKey(0), (128, 128))
    sw = make_sonic_weight(w, sparsity=0.5, block=(32, 32), num_clusters=16)
    x = jnp.zeros((2, 128))
    got = np.asarray(sonic_matvec(x, sw))
    assert got.shape == (2, 128)
    assert (got == 0.0).all()


@pytest.mark.parametrize("b,k,n,knz", [
    (1, 100, 384, 1),    # M=1, single surviving activation, off-tile N
    (1, 64, 200, 64),    # dense survivor set (density 1), N % 128 != 0
    (3, 50, 96, 17),     # nothing a multiple of anything
])
def test_sparse_matvec_decode_edge_shapes(b, k, n, knz):
    wt = jax.random.normal(jax.random.PRNGKey(0), (k, n))
    idx = jnp.sort(
        jax.random.permutation(jax.random.PRNGKey(2), k)[:knz]
    ).astype(jnp.int32)
    x_nz = jax.random.normal(jax.random.PRNGKey(3), (b, knz))
    got = sparse_matvec(x_nz, idx, wt)
    want = sparse_matvec_ref(x_nz, idx, wt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_sparse_matvec_all_zero_rows_and_weights():
    wt = jax.random.normal(jax.random.PRNGKey(0), (64, 128))
    idx = jnp.arange(16, dtype=jnp.int32)
    assert (np.asarray(sparse_matvec(jnp.zeros((2, 16)), idx, wt)) == 0).all()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16))
    assert (np.asarray(sparse_matvec(x, idx, jnp.zeros((64, 128)))) == 0).all()


# --------------------------------------------- int8 weight-quant kernels
#
# ISSUE 10 satellite: the fused dequant-inside-kernel int8 variants at the
# same decode-edge shapes the fp32 sweeps above cover — M=1 rows, off-tile
# K/N, all-zero blocks (scale clamps to 1.0, dequantizes to exact zero),
# and the density extremes.


@pytest.mark.parametrize("m,k,n,block,sp", [
    (16, 192, 320, (64, 64), 0.5),   # K/N not multiples of the 128 default
    (1, 96, 128, (32, 64), 0.0),     # M=1 decode row, density 1
    (3, 128, 384, (64, 128), 0.95),  # near the one-block-per-column floor
    (5, 128, 128, (64, 64), 1.0),    # the floor itself
])
def test_block_sparse_int8_matmul_kernel_vs_ref(m, k, n, block, sp):
    from repro.core.sonic_layers import make_block_sparse_int8
    from repro.kernels.block_sparse_matmul.ops import block_sparse_matmul_int8
    from repro.kernels.block_sparse_matmul.ref import (
        block_sparse_matmul_int8_ref,
    )

    w = jax.random.normal(jax.random.PRNGKey(0), (k, n))
    qw = make_block_sparse_int8(w, sp, block)
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k))
    got = block_sparse_matmul_int8(x, qw, bm=8)
    want = block_sparse_matmul_int8_ref(x, qw.values, qw.scales, qw.indices,
                                        qw.k_blocks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("m", [1, 2, 7])
def test_sonic_matmul_int8_decode_dispatch(m):
    """Flattened M below the tile threshold routes through the unpadded
    int8 matvec kernel and stays exact vs the fp32 dequant oracle."""
    from repro.core.sonic_layers import make_block_sparse_int8
    from repro.kernels.sonic_matmul.ops import sonic_matmul_int8
    from repro.kernels.sonic_matmul.ref import sonic_matmul_int8_ref

    assert m < DECODE_M_THRESHOLD
    w = jax.random.normal(jax.random.PRNGKey(0), (192, 320))
    qw = make_block_sparse_int8(w, 0.5, (64, 64))
    x = jax.random.normal(jax.random.PRNGKey(m), (m, 192))
    got = sonic_matmul_int8(x, qw)
    want = sonic_matmul_int8_ref(x, qw.values, qw.scales, qw.indices,
                                 qw.k_blocks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_sonic_matvec_int8_shapes_and_zero_rows():
    """1-D entry squeezes like the fp32 matvec; an all-zero decode row
    produces exactly 0.0 through the int8 path."""
    from repro.core.sonic_layers import make_block_sparse_int8
    from repro.kernels.sonic_matmul.ops import sonic_matvec_int8
    from repro.kernels.sonic_matmul.ref import sonic_matvec_int8_ref

    w = jax.random.normal(jax.random.PRNGKey(0), (128, 128))
    qw = make_block_sparse_int8(w, 0.25, (32, 32))
    for shape in [(128,), (3, 128)]:
        x = jax.random.normal(jax.random.PRNGKey(1), shape)
        got = sonic_matvec_int8(x, qw)
        want = sonic_matvec_int8_ref(x, qw.values, qw.scales, qw.indices,
                                     qw.k_blocks)
        assert got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
    assert (np.asarray(sonic_matvec_int8(jnp.zeros((2, 128)), qw)) == 0).all()


def test_int8_all_zero_blocks_quantize_to_exact_zero():
    """An all-zero kept block gets scale 1.0 (not epsilon) and int8 value 0,
    so it dequantizes to exactly 0.0 — and a fully zero weight yields an
    exactly-zero product, not accumulated rounding noise."""
    from repro.core.sonic_layers import (
        make_block_sparse, make_block_sparse_int8, quantize_block_sparse,
    )
    from repro.kernels.block_sparse_matmul.ops import block_sparse_matmul_int8

    w = jax.random.normal(jax.random.PRNGKey(0), (128, 128))
    w = w.at[:32, :32].set(0.0)  # one all-zero block, kept at sparsity 0
    qw = quantize_block_sparse(make_block_sparse(w, 0.0, (32, 32)))
    scales = np.asarray(qw.scales)
    vals = np.asarray(qw.values)
    idx = np.asarray(qw.indices)
    zero_r = np.where(idx[0] == 0)[0]  # N-block 0 reading K-block 0
    assert len(zero_r) == 1
    assert scales[0, zero_r[0]] == 1.0
    assert (vals[0, zero_r[0]] == 0).all()

    qzero = make_block_sparse_int8(jnp.zeros((128, 128)), 0.5, (32, 32))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 128))
    assert (np.asarray(block_sparse_matmul_int8(x, qzero, bm=8)) == 0.0).all()


def test_int8_dequant_error_bounded_by_scale():
    """Per-block scale = absmax/127: every dequantized element sits within
    half a quantization step of the fp32 kept block."""
    from repro.core.sonic_layers import make_block_sparse, quantize_block_sparse

    w = jax.random.normal(jax.random.PRNGKey(0), (128, 256))
    bw = make_block_sparse(w, 0.5, (64, 64))
    qw = quantize_block_sparse(bw)
    deq = np.asarray(qw.values, np.float32) * np.asarray(qw.scales)[:, :, None, None]
    err = np.abs(deq - np.asarray(bw.values))
    bound = 0.5 * np.asarray(qw.scales)[:, :, None, None] + 1e-7
    assert (err <= bound).all()


def test_int8_mode_linear_apply_kernel_vs_fallback():
    """The 'block_sparse_int8' and 'sonic_int8' execution paths: Pallas
    kernel ≡ jnp fallback, decode and prefill shapes."""
    from repro.core.sonic_layers import (
        SonicExecutionConfig, convert_linear, sonic_linear_apply,
    )
    import dataclasses

    w = jax.random.normal(jax.random.PRNGKey(0), (128, 128))
    for mode in ("block_sparse_int8", "sonic_int8"):
        kcfg = SonicExecutionConfig(mode=mode, use_kernel=True,
                                    weight_sparsity=0.5, block=(32, 32))
        fcfg = dataclasses.replace(kcfg, use_kernel=False)
        p = convert_linear(w, kcfg)
        for shape in [(2, 1, 128), (4, 16, 128)]:
            x = jax.random.normal(jax.random.PRNGKey(2), shape)
            got = sonic_linear_apply(p, x, kcfg)
            want = sonic_linear_apply(p, x, fcfg)
            assert got.shape == want.shape == (*shape[:-1], 128)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("frac", [0.0, 1.0])
def test_topk_sparse_matmul_density_extremes(frac):
    """k = K reproduces the dense product exactly; k = 1 keeps only the
    single largest-magnitude column (still equal to the masked product)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 96))
    wt = jax.random.normal(jax.random.PRNGKey(1), (96, 160))
    k = max(int(96 * frac), 1)
    got = np.asarray(topk_sparse_matmul(x, wt, k=k))
    if frac == 1.0:
        want = np.asarray(x @ wt)
    else:
        keep = int(jnp.argmax(jnp.abs(x[0])))
        xm = np.zeros_like(np.asarray(x))
        xm[0, keep] = np.asarray(x)[0, keep]
        want = xm @ np.asarray(wt)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_no_module_queries_the_backend_at_import():
    """Importing every ``repro`` module and the HTTP client initialises no
    JAX backend: interpret mode is chosen when a kernel's caller is lowered,
    and a process that only imports leaves the chip to others."""
    import os
    import pathlib
    import subprocess
    import sys

    root = pathlib.Path(__file__).resolve().parents[1]
    code = (
        "import importlib, pkgutil, sys\n"
        "import repro\n"
        "from jax._src import xla_bridge\n"
        "for m in pkgutil.walk_packages(repro.__path__, 'repro.'):\n"
        "    importlib.import_module(m.name)\n"
        "importlib.import_module('serve_client')\n"
        "print(xla_bridge.backends_are_initialized())\n"
    )
    res = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, cwd=root,
        env={**os.environ,
             "PYTHONPATH": f"{root / 'src'}:{root / 'tools'}",
             "JAX_PLATFORMS": "cpu"},
    )
    assert res.returncode == 0, res.stderr[-3000:]
    assert res.stdout.strip() == "False"
