"""GCN's normalisation, D^-1/2 Â D^-1/2 over the graph's own edges (Â =
A + I as edges, each loop counted once), and the global-batch engine
``Trainer`` tied to a plain float32 ``jax.numpy`` GCN on seeded weights.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.api as api
from repro.core import aggregate
from repro.graph import Graph, sbm_graph


def _graph(src, dst, n, f=4, seed=0):
    rng = np.random.default_rng(seed)
    return Graph(np.asarray(src, np.int32), np.asarray(dst, np.int32), n,
                 rng.normal(size=(n, f)).astype(np.float32),
                 rng.integers(0, 3, n).astype(np.int32))


def dense_norm(g: Graph) -> np.ndarray:
    """D^-1/2 Â D^-1/2 from a dense Â (row = destination), d the row sums
    of Â (a node with no in-edges counts as 1)."""
    a = np.zeros((g.num_nodes, g.num_nodes))
    np.add.at(a, (g.dst, g.src), 1.0)
    d = np.maximum(a.sum(1), 1.0)
    return a / np.sqrt(d[:, None] * d[None, :])


def program_norm(g: Graph) -> np.ndarray:
    """The program's per-edge weights laid out densely, (dst, src)."""
    w = g.gcn_norm()
    assert w.dtype == np.float32 and np.isfinite(w).all()
    out = np.zeros((g.num_nodes, g.num_nodes))
    np.add.at(out, (g.dst, g.src), w.astype(np.float64))
    return out


def _loops_once():
    g = sbm_graph(num_nodes=40, num_classes=3, feature_dim=4, p_in=0.2,
                  p_out=0.03, seed=3).add_self_loops()
    assert (np.bincount(g.src[g.src == g.dst], minlength=40) == 1).all()
    return g


def _no_loops():
    g = sbm_graph(num_nodes=40, num_classes=3, feature_dim=4, p_in=0.2,
                  p_out=0.03, seed=4)
    assert not (g.src == g.dst).any()
    return g


def _named_dataset():
    # api adds the loops to a named dataset for GCN, once a node
    g = api._resolve_graph(api.TrainJob(dataset="cora", model="gcn"))
    assert (np.bincount(g.src[g.src == g.dst],
                        minlength=g.num_nodes) == 1).all()
    return g


def _zero_in_degree_source():
    # node 0 only sends; node 4 sends and receives nothing but its loop
    return _graph([0, 0, 1, 2, 3, 4, 1, 2, 3],
                  [1, 2, 2, 3, 1, 4, 1, 2, 3], 5)


def _two_cycle_with_loops():
    # each node's in-degree is 2 (its neighbour and its loop), so every
    # weight is 1/2, not 1/sqrt(3 * 3)
    g = _graph([0, 1, 0, 1], [1, 0, 0, 1], 2)
    np.testing.assert_array_equal(dense_norm(g), np.full((2, 2), 0.5))
    return g


CASES = {"loops_once": _loops_once, "no_loops": _no_loops,
         "named_dataset": _named_dataset,
         "zero_in_degree_source": _zero_in_degree_source,
         "two_cycle_with_loops": _two_cycle_with_loops}


@pytest.mark.parametrize("case", list(CASES))
def test_gcn_norm_is_the_dense_symmetric_norm(case):
    g = CASES[case]()
    np.testing.assert_allclose(program_norm(g), dense_norm(g), rtol=1e-6,
                               atol=0)


# ---------------------------------------------------------------------------
# the global-batch engine Trainer against a plain float32 jnp GCN
# ---------------------------------------------------------------------------

LR, WD, B1 = 0.01, 5e-4, 0.9
HI = jax.lax.Precision.HIGHEST


def plain_loss(params, x, src, dst, w, rows, y):
    """Two GCN layers h' = Â_norm (h W) + b (ReLU between), a dense
    decoder, mean cross-entropy over ``rows``."""
    n = x.shape[0]
    h = x
    for k, p in enumerate(params["layers"]):
        hw = jnp.dot(h, p["w"], precision=HI)
        h = jax.ops.segment_sum(hw[src] * w[:, None], dst, n) + p["b"]
        if k < len(params["layers"]) - 1:
            h = jax.nn.relu(h)
    dec = params["decoder"]
    logits = jnp.dot(h[rows], dec["w"], precision=HI) + dec["b"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(logz - jnp.take_along_axis(logits, y[:, None], -1)[:, 0])


def plain_adam(params, grads, state):
    """Adam with L2 weight decay folded into the gradient."""
    tm = jax.tree_util.tree_map
    g = tm(lambda g_, p: g_ + WD * p, grads, params)
    t = state["t"] + 1
    m = tm(lambda m_, g_: B1 * m_ + (1 - B1) * g_, state["m"], g)
    v = tm(lambda v_, g_: 0.999 * v_ + 0.001 * g_ * g_, state["v"], g)
    new = tm(lambda p, m_, v_: p - LR * (m_ / (1 - B1 ** t))
             / (jnp.sqrt(v_ / (1 - 0.999 ** t)) + 1e-8), params, m, v)
    return new, {"t": t, "m": m, "v": v}, g


def seeded_params(key, f, hid, classes):
    k = jax.random.split(key, 3)

    def normal(kk, shape):
        return jax.random.normal(kk, shape) / np.sqrt(shape[0])

    return {"layers": [{"w": normal(k[0], (f, hid)), "b": jnp.zeros(hid)},
                       {"w": normal(k[1], (hid, hid)), "b": jnp.zeros(hid)}],
            "decoder": {"w": normal(k[2], (hid, classes)),
                        "b": jnp.zeros(classes)}}


def _leaves(tree):
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(tree)]


def test_global_engine_trainer_matches_plain_gcn(monkeypatch):
    """``api.make_trainer`` with the global batch on the engine, the csc
    kernels (interpreted here): first loss, first gradient (Adam's first
    moment over 1 - b1) and the parameters after 3 steps, against the
    plain GCN on the same seeded weights, with its own per-edge norm."""
    monkeypatch.setattr(aggregate, "default_backend_name", lambda: "csc")
    g = sbm_graph(num_nodes=96, num_classes=3, feature_dim=12, p_in=0.15,
                  p_out=0.02, seed=7).add_self_loops()
    job = api.TrainJob(dataset=g, model="gcn", strategy="global",
                       num_layers=2, hidden=16, lr=LR, weight_decay=WD,
                       seed=0, eval_every=0, engine_partitions=1,
                       log_every=0)
    trainer, views, *_ = api.make_trainer(job)
    assert "csc_gather" in trainer.engine._device_data
    params0 = seeded_params(jax.random.PRNGKey(11), 12, 16, 3)
    trainer.reset(params=params0)

    losses = list(trainer.fit(views, steps=1, eval_every=0,
                              log_every=0)["losses"])
    grad1 = [m / (1 - B1) for m in _leaves(trainer.opt_state["m"])]
    losses += trainer.fit(views, steps=2, eval_every=0,
                          log_every=0)["losses"]
    p3 = _leaves(trainer.params)
    trainer.assert_compiled_once()

    # the plain side's norm, from the edge list alone
    deg = np.bincount(g.dst, minlength=g.num_nodes).astype(np.float64)
    w = jnp.asarray(1.0 / np.sqrt(deg[g.src] * deg[g.dst]), jnp.float32)
    rows = jnp.asarray(np.where(g.train_mask)[0])
    args = (jnp.asarray(g.node_features), jnp.asarray(g.src),
            jnp.asarray(g.dst), w, rows, jnp.asarray(g.labels)[rows])
    vg = jax.jit(jax.value_and_grad(plain_loss))
    params = params0
    state = {"t": 0, "m": jax.tree_util.tree_map(jnp.zeros_like, params0),
             "v": jax.tree_util.tree_map(jnp.zeros_like, params0)}
    want_losses = []
    for i in range(3):
        loss, grads = vg(params, *args)
        params, state, seen = plain_adam(params, grads, state)
        want_losses.append(float(loss))
        if i == 0:
            want_grad1 = _leaves(seen)

    np.testing.assert_allclose(losses, want_losses, rtol=1e-5)
    for got, want in zip(grad1, want_grad1):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    for got, want in zip(p3, _leaves(params)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
