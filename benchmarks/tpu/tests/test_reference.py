"""The GCN reference against Kipf & Welling's equation written out densely
in numpy: D^-1/2 (A + I) D^-1/2 with D the degree of A + I, on a tiny
graph of the configuration's generator."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench
import graphs

gcn = bench.load_module(bench.HERE / "reference" / "gcn.py")


def test_gcn_reference_follows_the_published_norm():
    cfg = dict(bench.load_json(bench.HERE / "configs" / "gcn-reddit.json"),
               num_nodes=120, avg_degree=12)
    g = graphs.make_graph(cfg)
    n = len(g["y"])
    p = gcn.init(jax.random.PRNGKey(3), cfg["model"], g["x"].shape[1])
    w = gcn.edge_norm(g["src"], g["dst"], n)
    edges = tuple(jnp.asarray(a) for a in gcn.chunked_edges(
        g["src"], g["dst"], w))
    rows = jnp.arange(n)
    got = np.asarray(gcn.logits(p, jnp.asarray(g["x"]), edges, rows,
                                "highest"))

    a_hat = np.zeros((n, n))
    np.add.at(a_hat, (g["dst"], g["src"]), 1.0)      # graph holds A + I
    assert np.array_equal(np.diag(a_hat), np.ones(n))
    d = a_hat.sum(axis=1)
    a_norm = a_hat / np.sqrt(d[:, None] * d[None, :])
    h = g["x"].astype(np.float64)
    for k, layer in enumerate(p["layers"]):
        h = a_norm @ (h @ np.asarray(layer["w"], np.float64)) + np.asarray(
            layer["b"])
        if k < len(p["layers"]) - 1:
            h = np.maximum(h, 0.0)
    want = h @ np.asarray(p["decoder"]["w"], np.float64) + np.asarray(
        p["decoder"]["b"])
    assert got == pytest.approx(want, rel=1e-4, abs=1e-5)
