"""Pallas TPU kernels for SONIC's compute hot-spots.

clustered_matmul     — C2: weights as int8 cluster indices + codebook; dequant
                       fused into the MXU matmul in VMEM (the TPU analogue of
                       the 6-bit DAC driving the MR bank).
block_sparse_matmul  — C1+C4: balanced block-sparse weights; only nonzero
                       MXU-tile blocks are streamed HBM→VMEM (the TPU analogue
                       of VCSEL power gating, at tile granularity).
sparse_matvec        — C3: the FC zero-compression dataflow; gathered weight
                       rows × dense compressed activations.
sonic_matmul         — C1+C2 fused serving matmul, plus the decode-shaped
                       matvec variant (no M-tiling) that ``sonic_matmul``
                       auto-dispatches to when the flattened row count is
                       below DECODE_M_THRESHOLD (the generation hot path).

Each kernel ships kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
public wrapper), ref.py (pure-jnp oracle).  ``dispatch.run_kernel`` compiles
a kernel with Mosaic when its caller is lowered for the TPU and interprets
it on any other platform.
"""
