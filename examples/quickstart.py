"""Quickstart: train a GCN with GraphTheta-style global-batch in ~30 lines.

    PYTHONPATH=src python examples/quickstart.py
    PYTHONPATH=src python examples/quickstart.py --backend csc   # Pallas Sum stage
"""
import argparse

import jax

from repro.config import GNNConfig
from repro.core.aggregate import backend_name
from repro.core.mpgnn import accuracy_block, loss_block
from repro.core.strategies import global_batch_view
from repro.graph import make_dataset
from repro.models import make_gnn
from repro.optim import adam


def main(backend=None):
    g = make_dataset("cora", seed=0).add_self_loops()
    cfg = GNNConfig(model="gcn", num_layers=2, hidden_dim=32, num_classes=7,
                    feature_dim=g.node_features.shape[1],
                    aggregate_backend=backend)
    model = make_gnn(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg.feature_dim)
    opt = adam(1e-2, weight_decay=5e-4)
    state = opt.init(params)
    block = global_batch_view(g, cfg.num_layers).as_block(
        csc_plan=backend_name(model) == "csc")

    @jax.jit
    def step(params, state):
        loss, grads = jax.value_and_grad(
            lambda p: loss_block(model, p, block))(params)
        params, state = opt.update(grads, state, params)
        return params, state, loss

    for i in range(100):
        params, state, loss = step(params, state)
        if i % 20 == 0:
            print(f"step {i:3d}  loss {float(loss):.4f}")
    acc = accuracy_block(model, params, block,
                         mask=g.test_mask.astype("float32"))
    print(f"test accuracy: {float(acc):.4f}")

    # ... or the facade: one typed job, the right trainer picked for
    # you (compiled-once, trace contract certified), then chain straight
    # into offline inference and online serving
    import repro.api as api

    result = api.train(api.TrainJob(dataset="cora", steps=100, hidden=32,
                                    eval_every=100))
    print(f"facade test accuracy: {result.final_acc:.4f}")
    server = api.serve(result, api.ServeConfig(max_batch=8))
    preds = server.submit([0, 1, 2, 3]).argmax(-1)
    print(f"online predictions for nodes 0..3: {preds}")
    server.assert_compiled_per_bucket()


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default=None,
                    choices=["reference", "csc"],
                    help="Sum-stage aggregation backend (default: csc on "
                    "TPU, reference elsewhere)")
    main(ap.parse_args().backend)
