"""The open-loop request schedule of a serving cell, from the traffic
file's parameters and the run seed.

The shape is that of ``repro.launch.serve_gnn.request_trace`` (a hot set
that takes most requests, the rest spread over all nodes), with send
times added. The hot set and the multiset of requested nodes come from
the traffic file's own ``mix_seed``, so every run seed asks for the same
work; the run seed draws the order of the requests and their send times:
a fixed count, ``rate x seconds``, at times drawn uniformly over the
window and sorted (Poisson arrivals conditioned on their count)."""
from __future__ import annotations

import numpy as np


def hot_set(traffic: dict, num_nodes: int) -> np.ndarray:
    rng = np.random.default_rng([traffic["mix_seed"], 1])
    return rng.choice(num_nodes, size=min(traffic["hot_nodes"], num_nodes),
                      replace=False)


def schedule(traffic: dict, num_nodes: int, seed: int, seconds: float):
    """(send offsets in seconds, sorted; node ids), one node per request."""
    count = int(round(traffic["rate_per_s"] * seconds))
    mix = np.random.default_rng([traffic["mix_seed"], 2])
    hot = hot_set(traffic, num_nodes)
    is_hot = mix.random(count) < traffic["hot_share"]
    nodes = np.where(is_hot, hot[mix.integers(0, len(hot), count)],
                     mix.integers(0, num_nodes, count))
    rng = np.random.default_rng([seed, 2])
    offsets = np.sort(rng.uniform(0.0, seconds, count))
    return offsets, rng.permutation(nodes).astype(np.int64)
