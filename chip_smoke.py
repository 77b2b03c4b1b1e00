#!/usr/bin/env python3
"""Bring-up check of GraphTheta on a TPU: train, compare, serve.

Runs the system's main path once through the entry points a user calls
(``repro.api`` train / infer / serve, the engine ``Trainer``) with the
paper's flagship configuration at full width: GAT-E, 2 layers, 4 heads x
32 hidden, on the ``alipay_like`` power-law graph (20,000 nodes, about
1.2e5 directed edges with 8 edge attributes). Weights are random from
``--seed``. Every phase checks its result:

  a. training — ``api.train`` as global batch (bucketed trainer over the
     dense view) and as compact mini-batch, then the engine ``Trainer``
     at P=1 across global, mini and cluster batches; losses are finite
     and every trainer's trace contract holds (the engine step compiles
     once for all three strategies);
  b. the Sum-stage kernels (``csc``) against the float32 ``reference``
     backend on the same chip: first-step loss and gradients of GAT-E
     under ``jax.default_matmul_precision("highest")``, within the
     tolerance of ``tests/test_aggregate_backends.py``; the train step's
     HLO must hold ``tpu_custom_call``;
  c. one ``csc`` train step each of gcn (segment sum) and sage_max
     (segment max) on the same graph, so all six kernels run compiled;
  d. ``api.serve`` answers a few dozen requests; at staleness 0 the
     answers equal ``api.infer`` bit for bit.

With ``--chips 4`` it runs only the distributed engine: GAT-E on the
engine ``Trainer`` at P=4 over the host's four chips and the same job at
P=1, and checks that the two loss trajectories agree.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips, engine parity only

Progress and timings go to standard output; the last line is one JSON
object naming the device. The script exits non-zero, without that line,
when JAX finds no TPU or any phase fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

STEPS = 3                  # training steps per phase
REQUESTS = 48              # serving requests in phase d
TOL = 1e-5                 # tests/test_aggregate_backends.py: loss, grads
PRECISION = "highest"      # matmul precision of the comparisons


def log(msg: str) -> None:
    print(msg, flush=True)


class Smoke:
    """Runs named phases, timing each and recording failures."""

    def __init__(self):
        self.failed = []

    def phase(self, name: str, fn, *args):
        log(f"== {name}")
        t0 = time.perf_counter()
        try:
            out = fn(*args)
        except Exception:  # noqa: BLE001 — reported, the script fails
            traceback.print_exc()
            log(f"   FAIL {name} after {time.perf_counter() - t0:.1f}s")
            self.failed.append(name)
            return None
        log(f"   ok {name} in {time.perf_counter() - t0:.1f}s")
        return out


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def finite(xs) -> bool:
    import numpy as np
    return bool(np.all(np.isfinite(np.asarray(xs, np.float64))))


def gat_e_job(seed: int, **kw):
    import repro.api as api
    return api.TrainJob(dataset="alipay_like", model="gat_e", hidden=32,
                        num_layers=2, lr=5e-3, steps=STEPS,
                        eval_every=STEPS, seed=seed, **kw)


def strategy_streams(g, seed: int, strategies=("global", "mini", "cluster")):
    """View streams built as ``api.train`` builds them for this graph."""
    import numpy as np
    from repro.core.clustering import label_propagation_clusters
    from repro.core.strategies import strategy_views
    labeled = int(g.train_mask.sum())
    clusters = label_propagation_clusters(
        g, max_cluster_size=max(64, g.num_nodes // 50), seed=seed)
    per_batch = max(1, (int(np.max(clusters)) + 1) // 20)
    return {s: strategy_views(g, s, 2, seed=seed,
                              batch_nodes=max(32, labeled // 10),
                              clusters=clusters,
                              clusters_per_batch=per_batch)
            for s in strategies}


def timed_fit(trainer, views, label: str):
    """First step (compile included) and the rest, timed apart."""
    t0 = time.perf_counter()
    first = trainer.fit(views, steps=1)["losses"]
    t1 = time.perf_counter()
    rest = trainer.fit(views, steps=STEPS - 1)["losses"]
    t2 = time.perf_counter()
    losses = list(first) + list(rest)
    log(f"   {label}: first step {t1 - t0:.2f}s (compile included), "
        f"then {(t2 - t1) / max(1, STEPS - 1):.3f}s/step; "
        f"losses {[round(float(x), 6) for x in losses]}")
    check(len(losses) == STEPS and finite(losses),
          f"{label}: non-finite or missing losses {losses}")
    return losses


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def phase_train(seed: int):
    import repro.api as api
    results = {}
    for label, kw in (("global", {}),
                      ("compact mini", {"strategy": "mini",
                                        "compact": True})):
        t0 = time.perf_counter()
        res = api.train(gat_e_job(seed, **kw))
        losses = [h["loss"] for h in res.history]
        log(f"   api.train {label}: {time.perf_counter() - t0:.2f}s "
            f"(compile included), backend "
            f"{res.model.aggregate_backend or 'platform'}, "
            f"loss {losses}, test acc {res.final_acc:.4f}")
        check(finite(losses), f"{label}: non-finite loss {losses}")
        results[label] = res
    trainer, _, _, _, g, _ = api.make_trainer(
        gat_e_job(seed, engine_partitions=1))
    for strategy, views in strategy_streams(g, seed).items():
        timed_fit(trainer, views, f"engine P=1 {strategy}")
    trainer.assert_trace_contract()
    log(f"   engine trace counts {trainer.trace_counts}")
    return results["global"]


def _train_step(model, opt):
    import jax
    from repro.core.mpgnn import loss_block

    def step(params, state, block):
        loss, grads = jax.value_and_grad(
            lambda p: loss_block(model, p, block))(params)
        params, state = opt.update(grads, state, params)
        return params, state, loss, grads

    return jax.jit(step)


def _compile_step(model, params, state, block, label: str):
    """Compile a train step; a csc step must hold a Pallas kernel."""
    from repro.optim import adam
    step = _train_step(model, adam(1e-2))
    t0 = time.perf_counter()
    compiled = step.lower(params, state, block).compile()
    log(f"   {label}: compile {time.perf_counter() - t0:.2f}s")
    check(model.aggregate_backend != "csc"
          or "tpu_custom_call" in compiled.as_text(),
          f"{label}: no tpu_custom_call in the compiled train step")
    return compiled


def _model(g, name: str, backend: str, heads: int = 1):
    from repro.config import GNNConfig
    from repro.models import make_gnn
    edge_dim = g.edge_features.shape[1] if name == "gat_e" else 0
    return make_gnn(GNNConfig(
        model=name, num_layers=2, hidden_dim=32,
        num_classes=int(g.labels.max()) + 1,
        feature_dim=g.node_features.shape[1], edge_feature_dim=edge_dim,
        num_heads=heads, aggregate_backend=backend))


def phase_reference(seed: int):
    import jax
    import numpy as np
    from repro.core.strategies import global_batch_view
    from repro.graph import make_dataset
    from repro.optim import adam
    g = make_dataset("alipay_like", seed=seed)
    block = global_batch_view(g, 2).as_block(gcn_norm=False, csc_plan=True)
    csc = _model(g, "gat_e", "csc", heads=4)
    ref = dataclasses.replace(csc, aggregate_backend="reference")
    params = csc.init(jax.random.PRNGKey(seed), g.node_features.shape[1])
    state = adam(1e-2).init(params)
    log(f"   matmul precision for the comparison: {PRECISION}")
    with jax.default_matmul_precision(PRECISION):
        out = {}
        for label, model in (("csc", csc), ("reference", ref)):
            compiled = _compile_step(model, params, state, block,
                                     f"gat_e {label} step")
            t0 = time.perf_counter()
            _, _, loss, grads = compiled(params, state, block)
            loss = float(loss)
            log(f"   gat_e {label}: first-step loss {loss:.8f}, step "
                f"{time.perf_counter() - t0:.3f}s")
            out[label] = (loss, grads)
    (l_csc, g_csc), (l_ref, g_ref) = out["csc"], out["reference"]
    dl = abs(l_csc - l_ref)
    dg = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
             for a, b in zip(jax.tree_util.tree_leaves(g_csc),
                             jax.tree_util.tree_leaves(g_ref)))
    log(f"   |loss csc - reference| = {dl:.3e}, max |grad diff| = "
        f"{dg:.3e} (tolerance {TOL:g} each)")
    check(dl < TOL and dg < TOL, "csc disagrees with the reference")


def phase_kernels(seed: int):
    import jax
    from repro.core.strategies import global_batch_view
    from repro.graph import make_dataset
    from repro.optim import adam
    base = make_dataset("alipay_like", seed=seed)
    for name in ("gcn", "sage_max"):
        g = base.add_self_loops() if name == "gcn" else base
        block = global_batch_view(g, 2).as_block(gcn_norm=name == "gcn",
                                                 csc_plan=True)
        model = _model(g, name, "csc")
        params = model.init(jax.random.PRNGKey(seed),
                            g.node_features.shape[1])
        state = adam(1e-2).init(params)
        compiled = _compile_step(model, params, state, block,
                                 f"{name} csc step")
        t0 = time.perf_counter()
        _, _, loss, _ = compiled(params, state, block)
        loss = float(loss)
        log(f"   {name}: loss {loss:.6f}, step "
            f"{time.perf_counter() - t0:.3f}s")
        check(finite([loss]), f"{name}: non-finite loss")


def phase_serve(result, seed: int):
    import numpy as np
    import repro.api as api
    rng = np.random.default_rng(seed)
    n = result.graph.num_nodes
    server = api.serve(result, api.ServeConfig(max_batch=16, staleness=0))
    t0 = time.perf_counter()
    expected = api.infer(result)
    log(f"   api.infer full graph: {time.perf_counter() - t0:.2f}s "
        f"(compile included)")
    served = 0
    for batch in range(REQUESTS // 12):
        nodes = rng.choice(n, 12, replace=False)
        t0 = time.perf_counter()
        got = server.submit(nodes)
        dt = time.perf_counter() - t0
        served += len(nodes)
        same = np.array_equal(got, expected[nodes])
        log(f"   batch {batch}: {len(nodes)} requests in {dt:.3f}s, "
            f"bit-exact vs api.infer: {same}")
        check(same, f"served logits differ from api.infer (max "
              f"{float(np.max(np.abs(got - expected[nodes]))):.3e})")
    server.assert_compiled_per_bucket()
    log(f"   served {served} requests; cache {server.cache.stats()}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def phase_engine_parity(seed: int, chips: int):
    import jax
    import numpy as np
    import repro.api as api
    log(f"   matmul precision for the comparison: {PRECISION}")
    trajectories = {}
    with jax.default_matmul_precision(PRECISION):
        for p in (chips, 1):
            trainer, _, _, _, g, _ = api.make_trainer(
                gat_e_job(seed, engine_partitions=p))
            log(f"   P={p} on {trainer.engine.mesh.devices.size} devices")
            losses = []
            streams = strategy_streams(g, seed, ("global", "mini"))
            for strategy, views in streams.items():
                losses += timed_fit(trainer, views, f"P={p} {strategy}")
            trainer.assert_trace_contract()
            trajectories[p] = np.asarray(losses)
    a, b = trajectories[chips], trajectories[1]
    err = float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-6)))
    log(f"   max relative loss difference P={chips} vs P=1: {err:.3e}")
    check(err < 1e-4, f"P={chips} and P=1 trajectories disagree")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    from repro.utils.compile_cache import enable_compile_cache
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {enable_compile_cache()}")

    smoke = Smoke()
    if args.chips == 4:
        smoke.phase("engine P=4 vs P=1", phase_engine_parity, args.seed,
                    args.chips)
    else:
        result = smoke.phase("a. train", phase_train, args.seed)
        smoke.phase("b. csc vs reference", phase_reference, args.seed)
        smoke.phase("c. sum and max kernels", phase_kernels, args.seed)
        if result is not None:
            smoke.phase("d. serve", phase_serve, result, args.seed)
        else:
            smoke.failed.append("d. serve (no trained model)")
    if smoke.failed:
        print(f"chip_smoke: failed phases: {smoke.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
