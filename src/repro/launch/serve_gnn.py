"""Online GNN serving entrypoint + load-test harness.

Trains a quick model (or loads a checkpoint), stands up a
:class:`~repro.serving.server.GNNServer`, then replays a seeded request
trace from concurrent client threads and prints the latency/QPS/cache
report. Everything is deterministic in ``--seed``: the trace is a
skewed categorical draw (a few hot nodes dominate, the realistic serving
regime for a cache), and the per-request logits are independent of the
client count.

    PYTHONPATH=src python -m repro.launch.serve_gnn --dataset cora \
        --steps 100 --requests 500 --clients 4 --max-batch 16
"""
from __future__ import annotations

import argparse
import sys
import threading
import time

import numpy as np

from repro.utils import get_logger
from repro.utils.compile_cache import enable_compile_cache

log = get_logger("serve_gnn")


def request_trace(g, n_requests: int, seed: int = 0,
                  hot_frac: float = 0.1, hot_mass: float = 0.8):
    """A seeded, skewed node-id trace: ``hot_frac`` of the nodes receive
    ``hot_mass`` of the requests (cache-friendly, like production fan-in
    on popular entities); the rest spread uniformly."""
    rng = np.random.default_rng(seed)
    n = g.num_nodes
    n_hot = max(1, int(n * hot_frac))
    hot = rng.choice(n, size=n_hot, replace=False)
    p = np.full(n, (1.0 - hot_mass) / max(1, n - n_hot))
    p[hot] = hot_mass / n_hot
    p /= p.sum()
    return rng.choice(n, size=n_requests, p=p)


def run_clients(server, trace: np.ndarray, clients: int,
                timeout: float = 60.0):
    """Replay ``trace`` through ``clients`` threads against the armed
    server's batching queue. The trace is split round-robin; each thread
    issues its slice in order. Returns (logits aligned to ``trace``,
    wall seconds)."""
    out = np.empty((len(trace), server.model.num_classes), np.float32)
    errors: list = []

    def client(cid: int):
        try:
            for i in range(cid, len(trace), clients):
                out[i] = server.request(int(trace[i]), timeout=timeout)
        except BaseException as e:      # surface, don't hang the join
            errors.append(e)

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    return out, wall


def print_report(server, wall: float, n_requests: int) -> None:
    s = server.server_stats()
    lat, stage = s["latency_ms"], s["stage_s"]
    print(f"served {s['requests']} requests in {s['batches']} batches "
          f"(mean batch {s['mean_batch']:.1f}) in {wall:.2f}s "
          f"-> {n_requests / wall:.0f} QPS")
    print(f"latency ms: p50={lat['p50']:.2f} p99={lat['p99']:.2f} "
          f"mean={lat['mean']:.2f}")
    print(f"stage s: queue_wait={stage['queue_wait']:.2f} "
          f"view_build={stage['view_build']:.2f} "
          f"device_step={stage['device_step']:.2f} "
          f"gather={stage['gather']:.3f}")
    cache = s["cache"]
    if cache.get("enabled", True):
        print(f"cache: hit_rate={cache['hit_rate']:.2f} "
              f"hits={cache['hits']} misses={cache['misses']} "
              f"entries={cache['entries']} staleness={cache['staleness']}")
    else:
        print("cache: disabled")
    tr = s["trace"]
    print(f"trace contract: full={tr['full']['traces']} traces over "
          f"{len(tr['full']['buckets'])} buckets, "
          f"hit={tr['hit']['traces']} over "
          f"{len(tr['hit']['buckets'])} buckets")


def main(argv=None):
    import repro.api as api

    ap = argparse.ArgumentParser(
        description="serve a trained GNN and load-test it")
    ap.add_argument("--dataset", default="cora")
    ap.add_argument("--model", default="gcn",
                    choices=["gcn", "sage", "gat", "gat_e"])
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--steps", type=int, default=100,
                    help="quick training run before serving (ignored "
                         "with --checkpoint-dir)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="serve params from this checkpoint instead of "
                         "the fresh training run's")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=500)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--no-cache", action="store_true",
                    help="disable the historical-embedding cache "
                         "(every request takes the K-hop path)")
    ap.add_argument("--staleness", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    job = api.TrainJob(dataset=args.dataset, model=args.model,
                       num_layers=args.layers, hidden=args.hidden,
                       steps=args.steps, seed=args.seed,
                       eval_every=max(1, args.steps - 1))
    log.info("training %s/%s for %d steps ...", args.model, args.dataset,
             args.steps)
    result = api.train(job)
    log.info("trained: final_acc=%.4f (%.1fs)", result.final_acc,
             result.wall_s)

    cfg = api.ServeConfig(max_batch=args.max_batch,
                          max_wait_ms=args.max_wait_ms,
                          cache=not args.no_cache,
                          staleness=args.staleness,
                          checkpoint_dir=args.checkpoint_dir)
    server = api.serve(result, cfg).start()
    try:
        trace = request_trace(result.graph, args.requests, seed=args.seed)
        _, wall = run_clients(server, trace, args.clients)
    finally:
        server.stop()
    server.assert_compiled_per_bucket()
    print_report(server, wall, args.requests)
    return 0


if __name__ == "__main__":
    sys.exit(main())
