"""The graphs the configurations name, generated from each
configuration's own fixed graph seed.

These are copies of ``repro.graph.datasets.powerlaw_graph`` and
``sbm_graph`` (same draws, same order), kept here so that the data a
cell runs on cannot move with the program. ``sbm`` takes a mean degree
and an intra-community share in place of raw edge probabilities, so a
configuration can state a published degree at any node count.

A graph is a dict of numpy arrays: ``src``, ``dst`` (both directions
present, deduplicated), ``x`` (N, F), ``y`` (N,), ``train`` (N,) bool,
and ``edge_x`` (E, Fe) where the graph has edge attributes.
"""
from __future__ import annotations

import numpy as np


def _bidirect(src, dst):
    s = np.concatenate([src, dst]).astype(np.int32)
    d = np.concatenate([dst, src]).astype(np.int32)
    key = s.astype(np.int64) * (max(int(s.max()), int(d.max())) + 1) + d
    _, idx = np.unique(key, return_index=True)
    return s[idx], d[idx], idx


def _masks(n, rng, train=0.6, val=0.2):
    order = rng.permutation(n)
    tr = np.zeros(n, bool)
    tr[order[:int(n * train)]] = True
    return tr


def powerlaw(num_nodes, avg_degree, feature_dim, edge_feature_dim,
             num_classes, seed) -> dict:
    """Preferential attachment with edge attributes and planted risk
    labels (the Alipay stand-in)."""
    rng = np.random.default_rng(seed)
    m = max(1, avg_degree // 2)
    targets = list(range(m))
    repeated = []
    src_l, dst_l = [], []
    for v in range(m, num_nodes):
        if repeated:
            idx = rng.integers(0, len(repeated), m)
            chosen = {repeated[i] for i in idx}
        else:
            chosen = set(targets[:m])
        for t in chosen:
            src_l.append(v)
            dst_l.append(t)
            repeated.extend((v, t))
    src, dst, _ = _bidirect(np.array(src_l, np.int64),
                            np.array(dst_l, np.int64))
    M = len(src)
    ef = rng.normal(size=(M, edge_feature_dim)).astype(np.float32)
    rel = rng.integers(0, edge_feature_dim // 2, M)
    ef[np.arange(M), rel] += 2.0
    risk = np.zeros(num_nodes, np.float32)
    seeds = rng.choice(num_nodes, max(2, num_nodes // 100), replace=False)
    risk[seeds] = 1.0
    strength = 1.0 / (1.0 + np.exp(-ef[:, 0]))
    for _ in range(2):
        spread = np.zeros(num_nodes, np.float32)
        np.add.at(spread, dst, risk[src] * strength)
        risk = np.clip(risk + 0.5 * spread, 0, 4)
    labels = (risk > np.quantile(risk, 0.85)).astype(np.int32)
    if num_classes != 2:
        raise ValueError("powerlaw graphs carry binary risk labels")
    feats = rng.normal(size=(num_nodes, feature_dim)).astype(np.float32)
    feats[:, 0] += risk * 0.5
    train = _masks(num_nodes, rng, train=0.5, val=0.0)
    return {"src": src, "dst": dst, "x": feats, "y": labels,
            "train": train, "edge_x": ef}


def sbm(num_nodes, num_classes, feature_dim, avg_degree, intra_share,
        seed, feature_noise=1.0) -> dict:
    """Stochastic block model with class-prototype features; the edge
    probabilities give ``avg_degree`` directed in-edges per node, of which
    ``intra_share`` stay inside a node's community (before duplicates
    drawn twice are merged)."""
    n, C = num_nodes, num_classes
    p_in = min(1.0, intra_share * avg_degree * C / (2.0 * n))
    p_out = min(1.0, (1 - intra_share) * avg_degree / (n * (1 - 1 / C)))
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, C, n).astype(np.int32)
    srcs, dsts = [], []
    for a in range(C):
        ia = np.where(labels == a)[0]
        for b in range(a, C):
            ib = np.where(labels == b)[0]
            p = p_in if a == b else p_out
            n_edges = rng.binomial(len(ia) * len(ib), p)
            if n_edges == 0:
                continue
            s = ia[rng.integers(0, len(ia), n_edges)]
            d = ib[rng.integers(0, len(ib), n_edges)]
            keep = s != d
            srcs.append(s[keep])
            dsts.append(d[keep])
    src, dst, _ = _bidirect(np.concatenate(srcs), np.concatenate(dsts))
    protos = rng.normal(size=(C, feature_dim)).astype(np.float32)
    feats = (protos[labels] + feature_noise * rng.normal(
        size=(n, feature_dim)).astype(np.float32)).astype(np.float32)
    train = _masks(n, rng)
    return {"src": src, "dst": dst, "x": feats, "y": labels, "train": train}


GENERATORS = {"powerlaw": powerlaw, "sbm": sbm}


def make_graph(config: dict) -> dict:
    """The configuration's graph: its generator, sizes and graph seed;
    ``self_loops`` adds one loop per node after generation (GCN)."""
    gen = GENERATORS[config["generator"]]
    args = gen.__code__.co_varnames[:gen.__code__.co_argcount]
    g = gen(**{k: config[k] for k in args if k in config})
    loops = config.get("self_loops", False)
    if loops:
        n = len(g["y"])
        ar = np.arange(n, dtype=np.int32)
        g["src"] = np.concatenate([g["src"], ar])
        g["dst"] = np.concatenate([g["dst"], ar])
        if "edge_x" in g:
            g["edge_x"] = np.concatenate(
                [g["edge_x"], np.zeros((n, g["edge_x"].shape[1]),
                                       np.float32)])
    return g


def to_program_graph(g: dict):
    """The same arrays as the program's ``Graph`` (the system's input
    format); every array is a copy, so nothing the program does to its
    graph reaches the reference."""
    from repro.graph.csr import Graph
    return Graph(g["src"].copy(), g["dst"].copy(), len(g["y"]),
                 g["x"].copy(), g["y"].copy(),
                 edge_features=(g["edge_x"].copy() if "edge_x" in g
                                else None),
                 train_mask=g["train"].copy(), test_mask=~g["train"],
                 name="bench")
