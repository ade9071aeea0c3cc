"""Reproduce the paper's §V evaluation on the four paper CNNs:

  Table 1 / 3  CNN zoo: our vs the paper's parameter counts; sparsify +
               cluster accuracy retention on the MNIST teacher task
  Fig 6        sparsity × clusters design-space sweep (CIFAR10)
  Fig 7        per-layer weight + activation sparsity
  Figs 8-10    power, FPS/W and EPB of SONIC against the 7 baseline
               platforms, with the average SONIC advantage beside the
               paper's ratios

and then, on the CIFAR10 CNN, the workload after dataflow compression
(§III.C) and the ablation the paper implies: what each SONIC mechanism
contributes.

Run:  PYTHONPATH=src python examples/photonic_paper_repro.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.clustering import (ClusteringConfig, cluster_params,
                                   clustering_error)
from repro.core.sparsity import SparsityConfig, apply_masks, build_masks
from repro.data.teacher import TeacherTask
from repro.models import cnn as cnn_lib
from repro.photonic.accelerator import SonicAccelerator, SonicHWConfig
from repro.photonic.baselines import evaluate_all
from repro.photonic.mapper import cnn_workload

# the paper's average SONIC advantage over each platform (Figs 9 and 10)
PAPER_FPS_PER_W_X = {"NullHop": 5.81, "RSNN": 4.02, "LightBulb": 3.08,
                     "CrossLight": 2.94, "HolyLight": 13.8}
PAPER_EPB_X = {"NullHop": 8.4, "RSNN": 5.78, "LightBulb": 19.4,
               "CrossLight": 18.4, "HolyLight": 27.6}

# per-layer weight sparsity each paper CNN is priced at (Figs 8-10)
FIG_SPARSITY = {
    "mnist": {f"conv{i}": 0.6 for i in range(2)} | {f"fc{j}": 0.8 for j in range(2)},
    "cifar10": {f"conv{i}": 0.5 for i in range(6)} | {"fc0": 0.8},
    "stl10": {f"conv{i}": 0.5 for i in range(6)} | {f"fc{j}": 0.7 for j in range(2)},
    "svhn": {f"conv{i}": 0.5 for i in range(4)} | {f"fc{j}": 0.7 for j in range(3)},
}


def table1_table3():
    print("\n== Table 1 / Table 3: CNN zoo + sparsify/cluster accuracy ==")
    print(f"{'model':9s} {'ours params':>12s} {'paper params':>13s} {'Δ%':>6s}")
    for name, cfg in cnn_lib.PAPER_CNNS.items():
        p = cnn_lib.init_params(cfg, jax.random.PRNGKey(0))
        n = cnn_lib.param_count(p)
        d = 100 * (n - cfg.paper_params) / cfg.paper_params
        print(f"{name:9s} {n:12,d} {cfg.paper_params:13,d} {d:6.1f}")

    # accuracy retention on the MNIST teacher task (Table 3 regime: the
    # sparsified+clustered model stays comparable to the dense baseline)
    cfg = cnn_lib.MNIST_CNN
    task = TeacherTask(cfg)
    params = cnn_lib.init_params(cfg, jax.random.PRNGKey(0))

    def loss_fn(p, x, y):
        lg = cnn_lib.forward(p, cfg, x)
        return -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(lg), y[:, None], 1))

    @jax.jit
    def step(p, x, y):
        l, g = jax.value_and_grad(loss_fn)(p, x, y)
        return jax.tree_util.tree_map(lambda w, gw: w - 3e-3 * gw, p, g), l

    for i in range(150):
        x, y = task.batch(i)
        params, _ = step(params, x, y)
    acc0 = task.accuracy(params, n_batches=4)
    masks = build_masks(params, SparsityConfig(0.5, block=(1, 1), exclude=("bias",)))
    sparse = apply_masks(params, masks)
    clustered, _ = cluster_params(sparse, ClusteringConfig(64, exclude=("bias",)))
    acc1 = task.accuracy(clustered, n_batches=4)
    print(f"mnist teacher-task acc: dense={acc0:.3f}  sparse50%+64clusters={acc1:.3f}")


def fig6_dse():
    print("\n== Fig 6: sparsity × clusters design space (CIFAR10) ==")
    cfg = cnn_lib.CIFAR10_CNN
    params = cnn_lib.init_params(cfg, jax.random.PRNGKey(0))
    kprobe = params["conv"][3]["kernel"]
    w_probe = kprobe.reshape(-1, kprobe.shape[-1])
    print(f"{'sparsity':>8s} {'clusters':>8s} {'w-recon-err':>11s} {'FPS/W':>8s} {'EPB pJ/b':>9s}")
    rows = []
    for sp in (0.3, 0.5, 0.7):
        for c in (16, 64):
            ws = {f"conv{i}": sp for i in range(6)} | {"fc0": min(sp + 0.3, 0.9)}
            work = cnn_workload(cfg, params, ws)
            acc = SonicAccelerator(SonicHWConfig(weight_bits=int(np.ceil(np.log2(c)))))
            rep = acc.evaluate(work)
            err = clustering_error(w_probe, ClusteringConfig(num_clusters=c))
            rows.append((sp, c, err, rep.fps_per_w, rep.epb * 1e12))
            print(f"{sp:8.1f} {c:8d} {err:11.4f} {rep.fps_per_w:8.1f} {rep.epb*1e12:9.3f}")
    best = max(rows, key=lambda r: r[3])
    print(f"best (FPS/W): sparsity={best[0]} clusters={best[1]} — the paper's "
          "'max sparsity + min clusters, accuracy permitting' frontier")


def fig7_layerwise():
    print("\n== Fig 7: layer-wise weight/activation sparsity (all 4 CNNs) ==")
    for name, cfg in cnn_lib.PAPER_CNNS.items():
        params = cnn_lib.init_params(cfg, jax.random.PRNGKey(0))
        n_conv = len(cfg.conv_channels)
        ws = {f"conv{i}": 0.5 for i in range(n_conv)}
        ws |= {f"fc{j}": 0.7 for j in range(len(cfg.fc_dims) + 1)}
        print(f"  {name}:")
        for w in cnn_workload(cfg, params, ws):
            print(f"    {w.name:6s} weight_sp={w.weight_sparsity:.2f} "
                  f"act_sp={w.act_sparsity:.2f} veclen={w.vec_len}")


def _platform_table(reports, title, value):
    print(f"\n== {title} ==")
    plats = list(next(iter(reports.values())).keys())
    print(f"{'model':9s} " + " ".join(f"{p:>10s}" for p in plats))
    for m, r in reports.items():
        print(f"{m:9s} " + " ".join(value(r[p]) for p in plats))


def _advantage(reports, heading, paper, ratio):
    print(f"\n{heading}:")
    for p, expect in paper.items():
        r = float(np.mean([ratio(rr["SONIC"], rr[p]) for rr in reports.values()]))
        print(f"  vs {p:11s}: {r:5.2f}x   (paper: {expect}x)")


def figs8_10():
    reports = {}
    for name, cfg in cnn_lib.PAPER_CNNS.items():
        params = cnn_lib.init_params(cfg, jax.random.PRNGKey(0))
        reports[name] = evaluate_all(cnn_workload(cfg, params, FIG_SPARSITY[name]))
    _platform_table(reports, "Fig 8: power (W)", lambda r: f"{r.power_w:10.2f}")
    _platform_table(reports, "Fig 9: FPS/W", lambda r: f"{r.fps_per_w:10.2f}")
    _advantage(reports, "average SONIC advantage (ours vs paper)",
               PAPER_FPS_PER_W_X, lambda s, r: s.fps_per_w / r.fps_per_w)
    _platform_table(reports, "Fig 10: EPB (pJ / task bit)",
                    lambda r: f"{r.epb*1e12:10.3f}")
    # the paper does not publish how many bits a task's EPB counts; SONIC's
    # here counts weight_bits + 16 activation bits per MAC
    # (SonicHWConfig.epb_bits_per_mac)
    _advantage(reports, "average SONIC EPB advantage (ours vs paper; the "
               "paper does not publish its EPB bit accounting)",
               PAPER_EPB_X, lambda s, r: r.epb / s.epb)


def cifar10_ablation():
    cfg = cnn_lib.CIFAR10_CNN
    params = cnn_lib.init_params(cfg, jax.random.PRNGKey(0))
    work = cnn_workload(cfg, params, FIG_SPARSITY["cifar10"])

    print("\n== CIFAR10 workload after §III.C compression ==")
    for w in work:
        print(f"  {w.name:6s} {w.kind:4s} veclen={w.vec_len:5d} "
              f"products={w.n_products:7d} reuse={w.reuse}")

    print("\n== SONIC mechanism ablation (CIFAR10) ==")
    variants = {
        "full SONIC (5,50,50,10)": SonicHWConfig(),
        "no clustering (16b DACs)": SonicHWConfig(weight_bits=16),
        "no sparsity gating": SonicHWConfig(sparsity_gating=False),
        "no compression": SonicHWConfig(compression=False),
        "none (dense photonic)": SonicHWConfig(
            weight_bits=16, sparsity_gating=False, compression=False
        ),
    }
    print(f"{'variant':28s} {'FPS':>9s} {'W':>7s} {'FPS/W':>8s}")
    for name, hw in variants.items():
        r = SonicAccelerator(hw).evaluate(work)
        print(f"{name:28s} {r.fps:9.1f} {r.power_w:7.2f} {r.fps_per_w:8.2f}")


def main():
    table1_table3()
    fig6_dse()
    fig7_layerwise()
    figs8_10()
    cifar10_ablation()


if __name__ == "__main__":
    main()
