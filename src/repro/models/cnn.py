"""The paper's four custom CNNs (Table 1) — MNIST, CIFAR10, STL10, SVHN.

The paper gives layer counts and total parameters but not exact layer dims;
channel/hidden sizes below are chosen to land close to Table 1's parameter
counts (reported side-by-side by ``examples/photonic_paper_repro.py``).
All convs are 3×3/same with ReLU + 2×2 maxpool per stage (ReLU matters: it
is what creates the activation sparsity SONIC's dataflow compression
exploits).

These models exercise the CONV dataflow path (im2col + column compression,
paper §III.C) and are the workloads priced by the photonic simulator.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class CNNConfig:
    name: str
    input_hw: tuple[int, int, int]  # (H, W, C)
    conv_channels: Sequence[int]  # one conv layer per entry
    pool_after: Sequence[int]  # conv indices followed by 2×2 maxpool
    fc_dims: Sequence[int]  # hidden FC dims; final = n_classes appended
    n_classes: int = 10
    paper_params: int = 0
    paper_accuracy: float = 0.0


# Table 1 rows (paper_params / paper_accuracy are the paper's numbers)
MNIST_CNN = CNNConfig(
    name="mnist", input_hw=(28, 28, 1),
    conv_channels=(32, 64), pool_after=(0, 1), fc_dims=(456,),
    paper_params=1_498_730, paper_accuracy=0.932,
)
CIFAR10_CNN = CNNConfig(
    name="cifar10", input_hw=(32, 32, 3),
    conv_channels=(32, 48, 64, 96, 128, 192), pool_after=(1, 3, 5), fc_dims=(),
    paper_params=552_874, paper_accuracy=0.8605,
)
STL10_CNN = CNNConfig(
    name="stl10", input_hw=(96, 96, 3),
    conv_channels=(64, 64, 128, 128, 256, 256), pool_after=(1, 3), fc_dims=(512,),
    paper_params=77_787_738, paper_accuracy=0.746,
)
SVHN_CNN = CNNConfig(
    name="svhn", input_hw=(32, 32, 3),
    conv_channels=(32, 48, 64, 96), pool_after=(1, 3), fc_dims=(96, 64),
    paper_params=552_362, paper_accuracy=0.946,
)

PAPER_CNNS = {c.name: c for c in (MNIST_CNN, CIFAR10_CNN, STL10_CNN, SVHN_CNN)}


def init_params(cfg: CNNConfig, key) -> Params:
    params: Params = {"conv": [], "fc": []}
    c_in = cfg.input_hw[2]
    keys = jax.random.split(key, len(cfg.conv_channels) + len(cfg.fc_dims) + 1)
    ki = 0
    for c_out in cfg.conv_channels:
        fan_in = 3 * 3 * c_in
        params["conv"].append({
            "kernel": jax.random.normal(keys[ki], (3, 3, c_in, c_out)) * fan_in**-0.5,
            "bias": jnp.zeros((c_out,)),
        })
        c_in = c_out
        ki += 1
    h, w, _ = cfg.input_hw
    for idx in cfg.pool_after:
        h, w = h // 2, w // 2
    d = h * w * c_in
    for d_out in (*cfg.fc_dims, cfg.n_classes):
        params["fc"].append({
            "kernel": jax.random.normal(keys[ki], (d, d_out)) * d**-0.5,
            "bias": jnp.zeros((d_out,)),
        })
        d = d_out
        ki += 1
    return params


def forward(
    params: Params, cfg: CNNConfig, x: jax.Array, return_activations: bool = False
) -> jax.Array | tuple[jax.Array, list[jax.Array]]:
    """x: (B, H, W, C) → logits (B, n_classes).

    ``return_activations`` also yields every post-ReLU tensor — the photonic
    simulator measures activation sparsity there (paper Fig. 7).
    """
    acts: list[jax.Array] = []
    for i, cp in enumerate(params["conv"]):
        x = jax.lax.conv_general_dilated(
            x, cp["kernel"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ) + cp["bias"]
        x = jax.nn.relu(x)
        acts.append(x)
        if i in cfg.pool_after:
            x = jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
            )
    x = x.reshape(x.shape[0], -1)
    for j, fp in enumerate(params["fc"]):
        x = x @ fp["kernel"] + fp["bias"]
        if j < len(params["fc"]) - 1:
            x = jax.nn.relu(x)
            acts.append(x)
    if return_activations:
        return x, acts
    return x


def param_count(params: Params) -> int:
    import numpy as np

    return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
