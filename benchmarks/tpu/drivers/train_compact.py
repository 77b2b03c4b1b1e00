"""Mini-batch training through ``CompactTrainer``: compact K-hop views
with a per-hop neighbor cap, bucketed padding from the graph's default
ladder, built by prefetch threads."""
from __future__ import annotations

import numpy as np

from training import PROLOGUE, TrainDriver


class Driver(TrainDriver):

    def job(self, graph):
        import repro.api as api
        m, t = self.model_cfg, self.traffic
        return api.TrainJob(
            dataset=graph, model=m["model"], strategy="mini", compact=True,
            num_layers=m["num_layers"], hidden=m["hidden_dim"],
            lr=t["lr"], weight_decay=t["weight_decay"], seed=self.view_seed,
            eval_every=0, batch_nodes=t["batch_nodes"],
            neighbor_cap=t["neighbor_cap"], log_every=0)

    def targets_per_step(self) -> int:
        return int(self.traffic["batch_nodes"])

    def warm(self, n_steps: int) -> None:
        """Find the bucket of every view the window will train on (views
        are pure functions of the seed and the index) and run one
        discarded step in each bucket the prologue did not touch."""
        import sys
        import jax
        tr = self.trainer
        builder = self.views.make_builder()
        self.window_views = []
        compared = set(tr.buckets_touched)
        for i in range(PROLOGUE, PROLOGUE + n_steps):
            v = self.views.build(i, builder)
            self.window_views.append(live_counts(v))
            shape = tr.stager.bucket_for(v)
            if shape not in compared:
                print(f"window step {i + 1} lands in rung {shape}, which "
                      f"no compared step used", file=sys.stderr)
            if shape in tr.buckets_touched:
                continue
            saved = (tr.params, tr.opt_state, tr.step_num, tr.view_cursor)
            tr.fit([v.copy_masks()], steps=1, eval_every=0, log_every=0)
            jax.block_until_ready(tr.params)
            tr.params, tr.opt_state, tr.step_num, tr.view_cursor = saved

    def work(self) -> list:
        """Per window step, the live nodes and edges of each layer."""
        return self.window_views

    def reference_batches(self):
        from reference.common import cross_entropy
        ref = self.ref
        builder = None
        for i in range(PROLOGUE):
            if builder is None:
                builder = self.views.make_builder()
            view = self.views.build(i, builder).copy_masks()
            layers, x, ex, y, mismatch = sample_layers(
                view, self.g, self.traffic["batch_nodes"])
            self.mismatch = max(getattr(self, "mismatch", 0), mismatch)

            def loss_fn(p, x, ex, y, layers, precision, n=len(y)):
                return cross_entropy(
                    ref.logits(p, x, ex, layers, n, precision), y)

            yield loss_fn, (x, ex, y, layers)

    def sample_numbers(self) -> dict:
        """Edges the sampler got wrong, in the compared steps' views and
        in every view the window trained on (views are pure functions of
        the seed and the index, so rebuilding one gives what the window
        got)."""
        builder = self.views.make_builder()
        worst = getattr(self, "mismatch", 0)
        for i in range(PROLOGUE, PROLOGUE + self.n_steps):
            view = self.views.build(i, builder)
            worst = max(worst, sample_layers(
                view, self.g, self.traffic["batch_nodes"])[-1])
        return {"sample_mismatch": float(worst)}


def live_counts(view) -> dict:
    """Live nodes and edges per layer of a compact view (the work the
    algorithm needs; padding is not counted)."""
    K, off = view.K, view.hop_offsets
    layers = []
    for k in range(K):
        d_bound, s_bound = int(off[K - 1 - k]), int(off[K - k])
        e = int(((view.dst_local < d_bound) & (view.src_local < s_bound)).sum())
        layers.append({"n_src": s_bound, "n_dst": d_bound, "edges": e})
    return {"layers": layers, "targets": int(off[0])}


def sample_layers(view, g: dict, batch_nodes: int):
    """The reference's inputs for one sampled view, taken from the
    benchmark's own graph: the view's node set and hops are the sample;
    its edges are recomputed as every graph edge into a node within K-1
    hops from a node of the view, and compared with the view's own.
    Returns (layers, x, edge_x, labels, number of mismatches)."""
    import jax.numpy as jnp
    K, off, nodes = view.K, view.hop_offsets, np.asarray(view.nodes)
    n = len(nodes)
    targets = nodes[:int(off[0])]
    mismatch = 0
    if len(np.unique(targets)) != batch_nodes or not g["train"][targets].all():
        mismatch += abs(batch_nodes - len(np.unique(targets))) + 1
    in_view = np.zeros(len(g["y"]), bool)
    in_view[nodes] = True
    inner = np.zeros(len(g["y"]), bool)
    inner[nodes[:int(off[K - 1])]] = True
    eids = np.where(inner[g["dst"]] & in_view[g["src"]])[0]
    mismatch += len(np.setxor1d(eids, np.asarray(view.edge_ids)))
    g2l = np.full(len(g["y"]), -1, np.int64)
    g2l[nodes] = np.arange(n)
    src_l, dst_l = g2l[g["src"][eids]], g2l[g["dst"][eids]]
    layers = []
    for k in range(K):
        d_bound, s_bound = int(off[K - 1 - k]), int(off[K - k])
        sel = (dst_l < d_bound) & (src_l < s_bound)
        layers.append({"src": jnp.asarray(src_l[sel], jnp.int32),
                       "dst": jnp.asarray(dst_l[sel], jnp.int32),
                       "eid": jnp.asarray(np.where(sel)[0], jnp.int32),
                       "n_out": d_bound})
    x = jnp.asarray(g["x"][nodes])
    ex = jnp.asarray(g["edge_x"][eids])
    y = jnp.asarray(g["y"][targets])
    return layers, x, ex, y, mismatch
