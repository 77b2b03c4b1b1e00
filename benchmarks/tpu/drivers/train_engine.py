"""Global-batch training through the hybrid-parallel engine ``Trainer``
over ``engine_partitions`` chips: every labeled node is a target in
every step, and the step is compiled once."""
from __future__ import annotations

import numpy as np

from training import TrainDriver


class Driver(TrainDriver):

    def job(self, graph):
        import repro.api as api
        m, t = self.model_cfg, self.traffic
        return api.TrainJob(
            dataset=graph, model=m["model"], strategy="global",
            num_layers=m["num_layers"], hidden=m["hidden_dim"],
            lr=t["lr"], weight_decay=t["weight_decay"], seed=self.view_seed,
            eval_every=0, engine_partitions=t["engine_partitions"],
            log_every=0)

    def targets_per_step(self) -> int:
        return int(self.g["train"].sum())

    def work(self) -> list:
        """Per window step, the live nodes and edges of each layer: the
        whole graph in every layer."""
        n, e = len(self.g["y"]), len(self.g["src"])
        layer = {"n_src": n, "n_dst": n, "edges": e}
        return [{"layers": [layer] * self.model_cfg["num_layers"],
                 "targets": self.targets_per_step()}] * self.n_steps

    def reference_batches(self):
        import jax.numpy as jnp
        from reference.common import cross_entropy
        ref, g = self.ref, self.g
        n = len(g["y"])
        w = ref.edge_norm(g["src"], g["dst"], n)
        edges = tuple(jnp.asarray(a) for a in ref.chunked_edges(
            g["src"], g["dst"], w))
        rows = jnp.asarray(np.where(g["train"])[0], jnp.int32)
        x, y = jnp.asarray(g["x"]), jnp.asarray(g["y"])[rows]

        def loss_fn(p, x, edges, rows, y, precision):
            return cross_entropy(ref.logits(p, x, edges, rows, precision), y)

        for _ in range(3):
            yield loss_fn, (x, edges, rows, y)
