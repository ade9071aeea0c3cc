"""Quantized serving against fp32: int8 block-sparse weights plus the int8
KV cache hold fewer bytes than the same block-pruned model served as dense
fp32 weights with an fp32 cache, and their greedy tokens stay close to
that fp32 oracle (both engines share one pruning support, so every
mismatch is int8 noise compounding through decode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sonic_layers import make_block_sparse
from repro.models.registry import get_arch
from repro.serve import ContinuousScheduler, ServeConfig, ServeEngine
from repro.sharding.mesh import MeshPlan

# the SONIC operating point; the block is explicit because at the reduced
# widths the automatic block covers a whole projection (one block per
# column is the pruning floor, so nothing would be pruned)
SPARSITY, BLOCK = 0.75, (16, 16)
LENS = [4, 12, 8, 6]
NEWS = [32, 16, 24, 12]
# a broken dequant path matches about 1/vocab; healthy runs land far above
MATCH_FLOOR = 0.4


def _densify_pruned(node):
    """fp32 weights with the pruning support the int8 path keeps."""
    if not isinstance(node, dict):
        return node
    out = {}
    for key, val in node.items():
        if key == "kernel" and getattr(val, "ndim", 0) == 2:
            out[key] = make_block_sparse(val, SPARSITY, BLOCK).dense()
        elif key == "kernel" and getattr(val, "ndim", 0) == 3:
            out[key] = jnp.stack([make_block_sparse(v, SPARSITY, BLOCK).dense()
                                  for v in val])
        else:
            out[key] = _densify_pruned(val)
    return out


def _bytes(tree) -> int:
    return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(tree))


@pytest.fixture(scope="module")
def served():
    arch = get_arch("tinyllama-1.1b", reduced=True)
    params = arch.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, arch.cfg.vocab_size, (n,)).astype(np.int32)
               for n in LENS]
    engines = {
        "dense": ServeEngine(arch, _densify_pruned(params), MeshPlan(),
                             ServeConfig(max_len=64, temperature=0.0)),
        "quant": ServeEngine(arch, params, MeshPlan(cache_quant_int8=True),
                             ServeConfig(max_len=64, temperature=0.0,
                                         weight_quant="int8",
                                         weight_quant_sparsity=SPARSITY,
                                         weight_quant_block=BLOCK)),
    }
    out = {}
    for name, eng in engines.items():
        sched = ContinuousScheduler(eng, n_slots=2, segment_len=8,
                                    segment_mode="while")
        reqs = [sched.submit(p, n) for p, n in zip(prompts, NEWS)]
        sched.run()
        out[name] = (eng, sched, [list(r.tokens) for r in reqs])
    return out


@pytest.mark.parametrize("what", ["weights", "cache"])
def test_int8_bytes_below_dense(served, what):
    def size(name):
        eng, sched, _ = served[name]
        return _bytes(eng.params if what == "weights" else sched.cache)

    assert size("quant") < size("dense"), (size("quant"), size("dense"))


def test_int8_greedy_tokens_track_fp32_oracle(served):
    _, _, dense = served["dense"]
    _, _, quant = served["quant"]
    assert [len(t) for t in quant] == NEWS
    matched = sum(int(a == b) for q, d in zip(quant, dense)
                  for a, b in zip(q, d))
    assert matched / sum(NEWS) >= MATCH_FLOOR, (matched, sum(NEWS))
