from repro.kernels.sparse_matvec.kernel import row_table
from repro.kernels.sparse_matvec.ops import sparse_matvec, topk_sparse_matmul
