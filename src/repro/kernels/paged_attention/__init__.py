from repro.kernels.paged_attention.ops import (
    decode_walk,
    dense_decode_attention,
    paged_decode_attention,
)
