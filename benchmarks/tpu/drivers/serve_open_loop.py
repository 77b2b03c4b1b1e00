"""Online serving through ``repro.api.serve`` (``GNNServer``): clients
call ``request()`` on an open-loop schedule (``traffic.py``), each timed
from its scheduled send time. Set-up compiles every bucket rung of both
device paths (the K-hop miss path and the one-layer cache-hit path),
then serves the hot set once, so the window starts with the cache
holding the hot set and nothing else."""
from __future__ import annotations

import gc
import math
import threading
import time

import numpy as np

import bench
import check
import graphs
import traffic as traffic_mod


class Driver:
    def __init__(self, cell: dict, seed: int, seconds: float):
        self.cell = cell
        self.config = cell["config_data"]
        self.traffic = cell["traffic_data"]
        self.model_cfg = self.config["model"]
        self.seconds = float(seconds)
        self.seed = int(seed)
        self.w_seed, self.t_seed, self.warm_seed = bench.sub_seeds(seed, 3)
        self.ref = bench.load_module(
            bench.HERE / "reference" / f"{self.model_cfg['model']}.py")

    # -- set-up --------------------------------------------------------------

    def setup(self) -> None:
        import jax
        import repro.api as api
        from repro.config import GNNConfig
        from repro.models import make_gnn
        m, t = self.model_cfg, self.traffic
        self.g = graphs.make_graph(self.config)
        G = graphs.to_program_graph(self.g)
        F = self.g["x"].shape[1]
        model = make_gnn(GNNConfig(
            model=m["model"], num_layers=m["num_layers"],
            hidden_dim=m["hidden_dim"], num_classes=m["num_classes"],
            feature_dim=F, edge_feature_dim=m.get("edge_feature_dim", 0),
            num_heads=m.get("num_heads", 1)))
        init = jax.jit(lambda k: self.ref.init(k, m, F))
        params = init(jax.random.PRNGKey(self.w_seed))
        self.params = jax.device_get(params)
        result = api.TrainResult(params=params, model=model, graph=G,
                                 history=[], final_acc=0.0, wall_s=0.0,
                                 gcn_norm=m["model"] == "gcn")
        self.server = api.serve(result, api.ServeConfig(
            max_batch=t["max_batch"], max_wait_ms=t["max_wait_ms"],
            staleness=0, cache=True))
        self.warm()
        self.offsets, self.nodes = traffic_mod.schedule(
            t, len(self.g["y"]), self.t_seed, self.seconds)
        self.before = self._stats()
        self.server.start()

    def warm(self) -> None:
        """Compile every rung of both paths, then leave the cache holding
        exactly the hot set's coverage. A target set's rung is the one the
        server itself picks: its view builder and bucket lookup."""
        srv = self.server
        rungs = list(srv.buckets.shapes)
        n = len(self.g["y"])
        rng = np.random.default_rng(self.warm_seed)
        hot = traffic_mod.hot_set(self.traffic, n)
        order = rng.permutation(n)

        def rung_full(targets):
            view = srv._builder.khop_compact(np.unique(targets))
            return rungs.index(srv._stager.bucket_for(view))

        def rung_hit(targets):
            view = srv._hit_builder.khop_compact(np.unique(targets))
            return rungs.index(srv._hit_stager.bucket_for(view))

        for i in range(len(rungs)):
            # miss path: targets the cache does not cover, whose view
            # lands in rung i; the top rung takes every node left, so
            # that afterwards every node can be served as a hit
            free = order[~srv.cache.coverage(order)]
            last = i + 1 == len(rungs)
            srv.submit(np.sort(free) if last else
                       pick_prefix(free, rungs, i, rung_full))
        covered = np.where(srv.cache.coverage(np.arange(n)))[0]
        rng.shuffle(covered)
        for i in range(len(rungs)):
            srv.submit(pick_prefix(covered, rungs, i, rung_hit))
        srv.cache.invalidate()
        srv.submit(np.sort(hot))
        touched = srv.server_stats()["trace"]
        for path in ("full", "hit"):
            got = {tuple(b) for b in touched[path]["buckets"]}
            if got != set(rungs):
                raise bench.BenchError(
                    f"warm-up touched {sorted(got)} of the {path} path, "
                    f"not every rung {rungs}")
        self.traces = {p: touched[p]["traces"] for p in ("full", "hit")}

    def _stats(self) -> dict:
        s, c = self.server.stats, self.server.cache
        return {"requests": s.requests, "batches": s.batches,
                "queue_wait_s": s.queue_wait_s,
                "view_build_s": s.view_build_s,
                "device_step_s": s.device_step_s,
                "hits": c.hits, "misses": c.misses}

    # -- the window ----------------------------------------------------------

    def window(self) -> None:
        count = len(self.nodes)
        self.sent = np.full(count, np.nan)
        self.done = np.full(count, np.nan)
        self.answers = np.full((count, self.model_cfg["num_classes"]),
                               np.nan, np.float32)
        self.errors = {}
        nxt = iter(range(count))
        lock = threading.Lock()
        t0 = time.perf_counter() + 0.05
        timeout = self.seconds + 60.0
        srv = self.server

        def client():
            while True:
                with lock:
                    i = next(nxt, None)
                if i is None:
                    return
                due = t0 + self.offsets[i]
                wait = due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                self.sent[i] = time.perf_counter()
                try:
                    self.answers[i] = srv.request(int(self.nodes[i]),
                                                  timeout=timeout)
                    self.done[i] = time.perf_counter()
                except Exception as e:  # noqa: BLE001 — counted as missing
                    self.errors[i] = type(e).__name__

        threads = [threading.Thread(target=client, daemon=True)
                   for _ in range(self.traffic["clients"])]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        self.t0 = t0
        self.window_s = self.seconds
        self.after = self._stats()
        now = srv.server_stats()["trace"]
        for p in ("full", "hit"):
            if now[p]["traces"] != self.traces[p]:
                raise bench.BenchError(
                    f"the {p} serving path compiled inside the window")

    def latencies_ms(self) -> np.ndarray:
        lat = (self.done - (self.t0 + self.offsets)) * 1e3
        return np.where(np.isfinite(lat), lat, math.inf)

    def e2e(self) -> dict:
        lat = list(self.latencies_ms())
        return {"serve_p50_ms": bench.percentile(lat, 50),
                "serve_p95_ms": bench.percentile(lat, 95)}

    def counts(self) -> tuple:
        return len(self.nodes), int((~np.isfinite(self.done)).sum())

    def delta(self, key: str) -> float:
        return self.after[key] - self.before[key]

    def arrival_lag_ms(self) -> np.ndarray:
        return (self.sent - (self.t0 + self.offsets)) * 1e3

    def release(self) -> None:
        self.server.close()
        self.server = None
        gc.collect()

    # -- the comparison ------------------------------------------------------

    def reference_logits(self, precision: str) -> np.ndarray:
        import jax
        import jax.numpy as jnp
        g, K = self.g, self.model_cfg["num_layers"]
        n = len(g["y"])
        src, dst = jnp.asarray(g["src"]), jnp.asarray(g["dst"])
        eid = jnp.arange(len(g["src"]), dtype=jnp.int32)
        layers = [{"src": src, "dst": dst, "eid": eid, "n_out": n}] * K
        fn = jax.jit(lambda p, x, ex: self.ref.logits(
            p, x, ex, layers, n, precision))
        return np.asarray(fn(self.params, jnp.asarray(g["x"]),
                             jnp.asarray(g["edge_x"])))

    def compare(self, precision: str, served=None) -> dict:
        ok = np.isfinite(self.done)
        ref = self.reference_logits(precision)[self.nodes[ok]]
        served = self.answers[ok] if served is None else served[self.nodes[ok]]
        return check.serve_numbers(served, ref, int((~ok).sum()))


def pick_prefix(pool, rungs, i, rung_of) -> np.ndarray:
    """The shortest prefix of ``pool`` whose view lands in rung i, by
    ``rung_of(targets)``, the index of the rung a target set's view
    takes (for i=0: the first single node that lands there). Raises if
    rung i is out of reach."""
    if i == 0:
        for k in range(min(len(pool), 1000)):
            if rung_of(pool[k:k + 1]) == 0:
                return pool[k:k + 1]
        raise bench.BenchError(f"no single node lands in rung {rungs[0]}")
    lo, hi = 1, len(pool)
    if rung_of(pool[:hi]) < i:
        raise bench.BenchError(f"no target set reaches rung {rungs[i]}")
    while lo < hi:
        mid = (lo + hi) // 2
        if rung_of(pool[:mid]) >= i:
            hi = mid
        else:
            lo = mid + 1
    if rung_of(pool[:lo]) != i:
        raise bench.BenchError(f"no target set lands in rung {rungs[i]}")
    return np.sort(pool[:lo])
