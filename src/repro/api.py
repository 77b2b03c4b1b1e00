"""The public facade: ``train()`` / ``infer()`` / ``serve()``.

One typed surface over what used to be scattered across ``Trainer`` vs
``CompactTrainer`` ctor kwargs, ``strategy_views(..., compact=...)`` and
the ``launch/train.py`` flag soup::

    import repro.api as api

    result = api.train(api.TrainJob(dataset="cora", strategy="mini",
                                    compact=True, steps=200))
    logits = api.infer(result, nodes=[3, 7, 11])
    server = api.serve(result, api.ServeConfig(max_batch=16))

``train`` routes to the right trainer from the job alone — the
distributed :class:`~repro.core.trainer.Trainer` when
``engine_partitions`` is set, the bucketed
:class:`~repro.core.trainer.CompactTrainer` otherwise (it drives dense
and compact views alike) — and every trainer is a
:class:`~repro.core.trainer.BaseTrainer`, so callers can keep training,
checkpointing or evaluating through one type. The old entrypoints
(``repro.launch.train.train_gnn``, direct trainer construction) remain
as thin shims; see the README migration table.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Optional, Sequence, Union

import jax
import numpy as np

from repro.graph.csr import Graph


@dataclass
class TrainJob:
    """Everything one GNN training run needs, in one place.

    ``dataset`` is a registered dataset name (``repro.graph.make_dataset``)
    or an already-built :class:`Graph` (used as-is — no self-loop edit).
    Strategy knobs that don't apply to the chosen strategy are ignored,
    matching the old ``strategy_views`` behavior.
    """
    dataset: Union[str, Graph] = "cora"
    model: str = "gcn"                 # gcn | sage | sage_max | gat | gat_e
    strategy: str = "global"           # global | mini | cluster
    steps: int = 100
    num_layers: int = 2
    hidden: int = 64
    lr: float = 1e-2
    weight_decay: float = 5e-4
    seed: int = 0
    eval_every: int = 20
    # view construction
    compact: bool = False              # compact views + bucketed trainer
    batch_nodes: int = 0               # mini (0 = 10% of labeled nodes)
    clusters_per_batch: int = 0        # cluster (0 = num_clusters // 20)
    halo_hops: int = 0
    neighbor_cap: int = 0
    # distributed engine
    engine_partitions: int = 0         # 0 = single-process bucketed path
    partition_method: str = "1d_src"
    prefetch_workers: Optional[int] = None
    prefetch_mode: str = "thread"      # thread | process (sampler procs)
    # fault tolerance / checkpointing (repro.runtime)
    fault_policy: Optional[Any] = None
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0
    resume: bool = False
    log_every: int = 1


@dataclass
class ServeConfig:
    """Knobs of the online inference server (:mod:`repro.serving`)."""
    max_batch: int = 32
    max_wait_ms: float = 2.0
    max_queue: Optional[int] = None    # bounded admission (None = 8*batch)
    cache: bool = True                 # historical-embedding cache
    staleness: int = 0                 # max version age for a cache hit
    buckets: Optional[Any] = None      # BucketSpec (None = graph ladder)
    slots: int = 2
    checkpoint_dir: Optional[str] = None   # serve params from a checkpoint


@dataclass
class TrainResult:
    """What ``train()`` hands back — and what ``infer()``/``serve()``
    consume, so the three entrypoints chain without the caller ever
    touching trainer internals."""
    params: Any
    model: Any
    graph: Graph
    history: list
    final_acc: float
    wall_s: float
    gcn_norm: bool = True
    trainer: Optional[Any] = None      # the BaseTrainer (engine or bucketed)

    def as_dict(self) -> dict:
        """The legacy ``launch.train.train_gnn`` return shape."""
        return {"history": self.history, "wall_s": self.wall_s,
                "params": self.params, "final_acc": self.final_acc,
                "model": self.model, "graph": self.graph}


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def _resolve_graph(job: TrainJob) -> Graph:
    if isinstance(job.dataset, Graph):
        return job.dataset
    from repro.graph import make_dataset
    g = make_dataset(job.dataset, seed=job.seed)
    # GCN's spectral norm assumes self-loops (named datasets only — a
    # caller-supplied Graph is trusted to be ready to train on)
    return g.add_self_loops() if job.model == "gcn" else g


def _build(job: TrainJob):
    """(graph, model, params, opt, views, eval_view, eval_mask) for a
    job — the shared front half of every training path."""
    from repro.core.strategies import global_batch_view, strategy_views
    from repro.models import make_gnn
    from repro.optim import adam
    from repro.config import GNNConfig

    g = _resolve_graph(job)
    edge_dim = (g.edge_features.shape[1]
                if g.edge_features is not None else 0)
    if job.model == "gat_e" and edge_dim == 0:
        raise ValueError("gat_e needs an edge-attributed dataset "
                         "(alipay_like)")
    cfg = GNNConfig(model=job.model, num_layers=job.num_layers,
                    hidden_dim=job.hidden,
                    num_classes=int(g.labels.max()) + 1,
                    feature_dim=g.node_features.shape[1],
                    edge_feature_dim=edge_dim, num_heads=4)
    model = make_gnn(cfg)
    params = model.init(jax.random.PRNGKey(job.seed), cfg.feature_dim)
    opt = adam(job.lr, weight_decay=job.weight_decay)

    labeled = int((g.train_mask if g.train_mask is not None
                   else np.ones(g.num_nodes, bool)).sum())
    clusters = None
    clusters_per_batch = 0
    if job.strategy == "cluster":
        from repro.core.clustering import label_propagation_clusters
        clusters = label_propagation_clusters(
            g, max_cluster_size=max(64, g.num_nodes // 50), seed=job.seed)
        clusters_per_batch = (job.clusters_per_batch
                              or max(1, (int(clusters.max()) + 1) // 20))
    # compact sampled-subgraph views apply to the sampling strategies;
    # the global view IS the graph
    compact = job.compact and job.strategy in ("mini", "cluster")
    views = strategy_views(
        g, job.strategy, job.num_layers, seed=job.seed,
        batch_nodes=job.batch_nodes or max(32, labeled // 10),
        clusters=clusters, clusters_per_batch=clusters_per_batch,
        halo_hops=job.halo_hops, neighbor_cap=job.neighbor_cap,
        compact=compact)
    eval_view = global_batch_view(g, job.num_layers)
    test_mask = (g.test_mask if g.test_mask is not None else g.train_mask)
    eval_mask = (test_mask if test_mask is None
                 else test_mask.astype(np.float32))
    return g, model, params, opt, views, eval_view, eval_mask


def make_trainer(job: TrainJob):
    """The job's :class:`~repro.core.trainer.BaseTrainer` plus its view
    stream and eval pieces — for callers that want the training loop's
    parts without running it. ``train()`` is this + ``fit`` + packaging."""
    g, model, params, opt, views, eval_view, eval_mask = _build(job)
    if job.engine_partitions:
        from repro.core.partition import build_partitions
        from repro.core.engine import HybridParallelEngine
        from repro.core.trainer import Trainer
        sg = build_partitions(g, job.engine_partitions,
                              method=job.partition_method,
                              gcn_norm=job.model == "gcn")
        trainer = Trainer(HybridParallelEngine(model, sg), opt,
                          params=params, fault_policy=job.fault_policy)
    else:
        from repro.core.trainer import CompactTrainer
        trainer = CompactTrainer(model, g, opt, params=params,
                                 gcn_norm=job.model == "gcn",
                                 fault_policy=job.fault_policy)
    return trainer, views, eval_view, eval_mask, g, model


def train(job: TrainJob, log=None) -> TrainResult:
    """Run the job end to end: build graph/model/views, fit the right
    trainer, certify its trace contract, evaluate. Deterministic in
    ``job.seed`` (prefetch parallelism never changes the trajectory)."""
    from repro.runtime.faults import TrainingInterrupted
    from repro.utils import get_logger
    log = log or get_logger("api").info
    trainer, views, eval_view, eval_mask, g, model = make_trainer(job)
    t0 = time.perf_counter()
    try:
        out = trainer.fit(views, steps=job.steps,
                          eval_every=job.eval_every,
                          eval_view=eval_view, eval_mask=eval_mask,
                          prefetch_workers=job.prefetch_workers,
                          prefetch_mode=job.prefetch_mode,
                          checkpoint_every=job.checkpoint_every,
                          checkpoint_dir=job.checkpoint_dir,
                          resume=job.resume,
                          log_every=job.log_every, log=log)
    except TrainingInterrupted:
        # a signal handler fired mid-fit: fit's finally already drained
        # the prefetch service (no orphaned sampler processes); persist
        # the progress so --resume can pick the run back up
        if job.checkpoint_dir:
            trainer.save(job.checkpoint_dir)
            log(f"interrupted at step {trainer.step_num} — checkpoint "
                f"saved to {job.checkpoint_dir}")
        raise
    wall = time.perf_counter() - t0
    trainer.assert_trace_contract()
    history = [{"step": e["step"], "loss": e["loss"],
                "test_acc": e["eval_acc"]} for e in out["evals"]]
    if history and history[-1]["step"] == trainer.step_num:
        final_acc = history[-1]["test_acc"]   # fit already evaluated
    else:
        final_acc = trainer.evaluate(eval_view, eval_mask)
        loss = out["losses"][-1] if out["losses"] else float("nan")
        history.append({"step": trainer.step_num, "loss": loss,
                        "test_acc": final_acc})
    return TrainResult(params=trainer.params, model=model, graph=g,
                       history=history, final_acc=final_acc, wall_s=wall,
                       gcn_norm=job.model == "gcn", trainer=trainer)


# ---------------------------------------------------------------------------
# infer / serve
# ---------------------------------------------------------------------------


def infer(result: TrainResult,
          nodes: Optional[Sequence[int]] = None) -> np.ndarray:
    """One-shot offline inference: full-graph logits (``(N, C)``), or the
    requested nodes' rows. For sustained request traffic use
    :func:`serve` — batching, bucketed compilation and the embedding
    cache live there."""
    from repro.core.aggregate import backend_name
    from repro.core.mpgnn import forward_block
    from repro.core.strategies import global_batch_view
    model, g = result.model, result.graph
    block = global_batch_view(g, model.K).as_block(
        gcn_norm=result.gcn_norm, csc_plan=backend_name(model) == "csc")
    logits = np.asarray(forward_block(model, result.params, block))
    logits = logits[:g.num_nodes]
    if nodes is None:
        return logits
    return logits[np.asarray(nodes, np.int64)]


def serve(result: TrainResult,
          config: Optional[ServeConfig] = None):
    """An online :class:`~repro.serving.server.GNNServer` over the
    trained model. ``config.checkpoint_dir`` serves the params stored in
    a checkpoint instead of the in-memory ones (the train -> checkpoint
    -> serve round trip)."""
    from repro.serving import GNNServer
    config = config or ServeConfig()
    params = result.params
    if config.checkpoint_dir:
        from repro.checkpoint import load_checkpoint
        params = load_checkpoint(config.checkpoint_dir)["params"]
    return GNNServer(result.model, params, result.graph,
                     buckets=config.buckets, cache=config.cache,
                     staleness=config.staleness,
                     max_batch=config.max_batch,
                     max_wait_ms=config.max_wait_ms,
                     max_queue=config.max_queue,
                     gcn_norm=result.gcn_norm, slots=config.slots)


__all__ = ["TrainJob", "ServeConfig", "TrainResult", "train", "infer",
           "serve", "make_trainer"]
