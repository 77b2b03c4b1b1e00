"""End-to-end driver: distributed GNN training the way the paper runs it —
a worker group (8 simulated workers here; 1,024 in the paper) jointly
computes every batch of an edge-attributed power-law "Alipay-like" graph
with the in-house GAT-E model, under all three training strategies.

Since PR 4 the loop is the compiled-once :class:`repro.core.Trainer`:
one jitted train step serves global-, mini- and cluster-batch alike while
a pool of prefetch workers builds (vectorized ViewBuilder, cached cluster
sets), shards (vectorized ``shard_view``) and stages upcoming views —
deterministically, since view i depends only on (seed, i) — and
``assert_compiled_once()`` certifies that no strategy switch ever
retraced the step.

    JAX_PLATFORMS=cpu PYTHONPATH=src python examples/distributed_training.py
    PYTHONPATH=src python examples/distributed_training.py --workers 4  # TPU

On the CPU, eight fake host devices stand in for the worker group; on an
accelerator host the workers are its chips.
"""
import os

if os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import argparse
import time

from repro.config import GNNConfig
from repro.core.clustering import label_propagation_clusters
from repro.core.engine import HybridParallelEngine
from repro.core.partition import build_partitions, partition_stats
from repro.core.strategies import global_batch_view, strategy_views
from repro.core.trainer import Trainer
from repro.graph import make_dataset
from repro.models import make_gnn
from repro.optim import adam


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--nodes", type=int, default=8000)
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--partition", default="1d_src",
                    choices=["1d_src", "1d_dst", "vertex_cut"])
    ap.add_argument("--backend", default=None,
                    choices=["reference", "csc"],
                    help="Sum-stage aggregation backend (default: csc on "
                    "TPU, reference elsewhere)")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="disable the host-side view prefetch pipeline")
    ap.add_argument("--prefetch-workers", type=int, default=None,
                    help="view-builder threads (default: min(4, cores-1); "
                    "any count yields a bit-identical loss trajectory)")
    ap.add_argument("--checkpoint-dir", default=None)
    args = ap.parse_args()

    g = make_dataset("alipay_like", num_nodes=args.nodes, seed=0)
    print(f"graph: {g.num_nodes} nodes, {g.num_edges} edges, "
          f"{g.edge_features.shape[1]} edge attrs, "
          f"max degree {g.in_degree().max()}")

    cfg = GNNConfig(model="gat_e", num_layers=2, hidden_dim=32,
                    num_classes=2, feature_dim=g.node_features.shape[1],
                    edge_feature_dim=g.edge_features.shape[1], num_heads=4,
                    aggregate_backend=args.backend)
    model = make_gnn(cfg)

    sg = build_partitions(g, args.workers, method=args.partition,
                          gcn_norm=False)
    print("partition stats:", partition_stats(sg))
    engine = HybridParallelEngine(model, sg)
    trainer = Trainer(engine, adam(5e-3), seed=0)

    clusters = label_propagation_clusters(
        g, max_cluster_size=max(200, g.num_nodes // 20), seed=0)
    eval_view = global_batch_view(g, 2)

    steps_per = max(1, args.steps // 3)
    for name in ("global", "mini", "cluster"):
        views = strategy_views(
            g, name, K=2, seed=0, batch_nodes=g.num_nodes // 50,
            clusters=clusters,
            clusters_per_batch=max(1, (int(clusters.max()) + 1) // 20))
        t0 = time.perf_counter()
        out = trainer.fit(views, steps=steps_per,
                          prefetch=not args.no_prefetch,
                          prefetch_workers=args.prefetch_workers,
                          checkpoint_every=steps_per if args.checkpoint_dir
                          else 0,
                          checkpoint_dir=args.checkpoint_dir)
        wall = time.perf_counter() - t0
        # distributed inference through the same engine (paper §4.3),
        # compiled once and shared by every eval
        acc = trainer.evaluate(eval_view)
        print(f"[{name:8s}] {steps_per} steps, {wall:.1f}s "
              f"({wall / steps_per * 1e3:.0f} ms/step), "
              f"loss {out['losses'][-1]:.4f}, test acc {acc:.4f}")
    trainer.assert_compiled_once()
    print("done: one engine, three strategies, one compiled train step "
          f"(traced {trainer.trace_counts['train_step']}x over "
          f"{trainer.step_num} steps).")


if __name__ == "__main__":
    main()
