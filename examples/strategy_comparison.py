"""The paper's flexible-training-strategy feature: train the same GCN with
global-, mini- and cluster-batch and compare accuracy / step cost / memory
proxies (Tables 2-4 in miniature).

Since PR 4 all three strategies run through one :class:`repro.core.Trainer`
over a 4-worker hybrid-parallel engine: ``trainer.reset()`` between
strategies keeps the compiled step, so the whole comparison — all
strategies, eval included — traces the train step exactly once
(``assert_compiled_once``).

PR 6's compact sampled-subgraph views ride the same engine: the
``compact`` rows feed :class:`~repro.core.views.CompactView` streams
through the identical compiled step (an O(view) shard scatter instead of
dense-mask gathers) — same trajectory, a fraction of the per-view host
bytes.

    JAX_PLATFORMS=cpu PYTHONPATH=src python examples/strategy_comparison.py

On the CPU, four fake host devices stand in for the workers; on an
accelerator host the workers are its chips (up to four).
"""
import os

if os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=4")

import time

import numpy as np

from repro.config import GNNConfig
from repro.core.clustering import label_propagation_clusters, modularity
from repro.core.engine import HybridParallelEngine
from repro.core.partition import build_partitions
from repro.core.strategies import global_batch_view, strategy_views
from repro.core.trainer import Trainer
from repro.graph import make_dataset
from repro.models import make_gnn
from repro.optim import adam


def _view_host_bytes(v) -> int:
    """Per-view host footprint: compact views own O(view) id arrays, a
    dense view owns (K, N)/(K, E) masks, the global view owns one (N,)."""
    if hasattr(v, "nbytes"):            # CompactView
        return v.nbytes()
    na = v.node_active.nbytes if v.node_active is not None else 0
    ea = v.edge_active.nbytes if v.edge_active is not None else 0
    return na + ea + v.loss_mask.nbytes


def run(trainer, g, clusters, strategy: str, steps: int,
        compact: bool = False):
    trainer.reset(seed=0)
    views = strategy_views(g, strategy, K=2, seed=0, batch_nodes=64,
                           clusters=clusters, clusters_per_batch=4,
                           compact=compact)
    t0 = time.perf_counter()
    trainer.fit(views, steps=steps)     # multi-stream prefetch pool
    wall = time.perf_counter() - t0
    acc = trainer.evaluate(global_batch_view(g, 2),
                           mask=g.test_mask.astype(np.float32))
    # view i is a pure function of (seed, i), so the exact views the run
    # consumed can be replayed off the timed path to measure the peak
    # active-set size (Table 4's memory proxy) and per-view host bytes
    builder = views.make_builder()
    replayed = [views.build(i, builder) for i in range(views.cursor)]
    peak = max((v.active_counts()["active_nodes"] for v in replayed),
               default=g.num_nodes)
    view_kb = max((_view_host_bytes(v) / 1024 for v in replayed),
                  default=_view_host_bytes(global_batch_view(g, 2)) / 1024)
    return {"strategy": strategy + ("+compact" if compact else ""),
            "acc": acc, "ms_per_step": wall / steps * 1e3,
            "peak_active_nodes": peak, "view_kb": view_kb}


def main():
    g = make_dataset("reddit_like", num_nodes=3000, seed=0).add_self_loops()
    cfg = GNNConfig(model="gcn", num_layers=2, hidden_dim=64, num_classes=8,
                    feature_dim=g.node_features.shape[1])
    model = make_gnn(cfg)
    print(f"graph: {g.num_nodes} nodes, {g.num_edges} edges")

    clusters = label_propagation_clusters(g, max_cluster_size=300, iters=4,
                                          seed=0)
    print(f"  [cluster] {clusters.max() + 1} communities, "
          f"modularity {modularity(g, clusters):.3f}")

    import jax
    P = min(4, len(jax.devices()))
    engine = HybridParallelEngine(model, build_partitions(g, P))
    trainer = Trainer(engine, adam(1e-2), seed=0)
    # warmup: pay the (single) trace+compile outside the timed windows so
    # the first strategy's ms/step isn't charged for it
    trainer.fit(strategy_views(g, "global", K=2), steps=2)

    print(f"{'strategy':16s} {'test_acc':>8s} {'ms/step':>8s} "
          f"{'peak_active':>11s} {'view_kb':>8s}")
    for strategy, compact in (("global", False), ("mini", False),
                              ("cluster", False), ("mini", True),
                              ("cluster", True)):
        r = run(trainer, g, clusters, strategy, steps=120, compact=compact)
        print(f"{r['strategy']:16s} {r['acc']:8.4f} "
              f"{r['ms_per_step']:8.1f} {r['peak_active_nodes']:11d} "
              f"{r['view_kb']:8.1f}")
    trainer.assert_compiled_once()
    print(f"one compiled train step served every strategy, dense AND "
          f"compact ({trainer.trace_counts['train_step']} trace, P={P}).")


if __name__ == "__main__":
    main()
