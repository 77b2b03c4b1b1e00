"""Trainer contracts (PR 4): compiled-once stepping across strategy
switches, prefetch-pipeline ordering (identical results with prefetch
on/off), vectorized shard_view parity with the per-partition loop, and
checkpoint save/restore resuming mid-stream without a retrace.

The fast lane runs everything in-process on a 1-partition engine (the
single CPU device); the P=4 distributed sweep is a ``slow`` subprocess
test like the other engine suites.
"""
import itertools

import numpy as np
import pytest

from conftest import run_with_devices

from repro.config import GNNConfig
from repro.core.clustering import label_propagation_clusters
from repro.core.engine import HybridParallelEngine
from repro.core.partition import build_partitions
from repro.core.strategies import (global_batch_view, shard_view,
                                   shard_view_loop, strategy_views)
from repro.core.trainer import RetraceError, Trainer
from repro.graph import sbm_graph
from repro.models import make_gnn
from repro.optim import adam


def _graph(n=220, seed=0):
    return sbm_graph(num_nodes=n, num_classes=4, feature_dim=8,
                     p_in=0.05, p_out=0.005, seed=seed).add_self_loops()


@pytest.fixture(scope="module")
def setup():
    g = _graph()
    cfg = GNNConfig(model="gcn", num_layers=2, hidden_dim=16,
                    num_classes=4, feature_dim=8)
    engine = HybridParallelEngine(make_gnn(cfg), build_partitions(g, 1))
    clusters = label_propagation_clusters(g, max_cluster_size=60, seed=0)
    return g, engine, clusters


def _views(g, strategy, clusters, seed=0):
    return strategy_views(g, strategy, K=2, seed=seed, batch_nodes=24,
                          clusters=clusters, clusters_per_batch=2)


# ---------------------------------------------------------------------------
# vectorized shard_view == per-partition loop (multi-partition plan,
# no devices needed)
# ---------------------------------------------------------------------------


def test_shard_view_parity_all_strategies():
    g = _graph(seed=3)
    plan = build_partitions(g, 3).plan
    clusters = label_propagation_clusters(g, max_cluster_size=60, seed=0)
    for strategy in ("global", "mini", "cluster"):
        v = next(iter(_views(g, strategy, clusters, seed=5)))
        a, b = shard_view(plan, v), shard_view_loop(plan, v)
        assert set(a) == set(b)
        for k in a:
            assert a[k].shape == b[k].shape
            assert a[k].dtype == b[k].dtype
            assert np.array_equal(a[k], b[k]), (strategy, k)


def test_global_strategy_view_is_static():
    g = _graph(seed=4)
    it = strategy_views(g, "global", K=2)
    v1, v2 = next(it), next(it)
    assert v1 is v2   # the Trainer stages a static stream exactly once


# ---------------------------------------------------------------------------
# compiled-once contract
# ---------------------------------------------------------------------------


def test_compiled_once_across_strategy_switches(setup):
    g, engine, clusters = setup
    trainer = Trainer(engine, adam(1e-2), seed=0)
    for strategy in ("global", "mini", "cluster", "mini", "global"):
        trainer.fit(_views(g, strategy, clusters), steps=2)
    assert trainer.step_num == 10
    assert trainer.trace_counts["train_step"] == 1
    trainer.assert_compiled_once()


def test_assert_compiled_once_raises(setup):
    g, engine, clusters = setup
    trainer = Trainer(engine, adam(1e-2), seed=0)
    with pytest.raises(RetraceError):      # never stepped
        trainer.assert_compiled_once()
    trainer.fit(_views(g, "global", clusters), steps=1)
    trainer.assert_compiled_once()
    trainer.trace_counts["train_step"] = 2  # simulate a retrace
    with pytest.raises(RetraceError):
        trainer.assert_compiled_once()


def test_compiled_once_across_reset_and_restore(setup, tmp_path):
    """Fresh state from reset() and checkpointed state from restore()
    enter the step with the same types as the step's own outputs, so
    neither retraces."""
    g, engine, clusters = setup
    trainer = Trainer(engine, adam(1e-2), seed=0)
    trainer.fit(_views(g, "global", clusters), steps=2)
    trainer.save(str(tmp_path))
    trainer.reset(seed=1)
    trainer.fit(_views(g, "mini", clusters), steps=2)
    trainer.restore(str(tmp_path))
    trainer.fit(_views(g, "cluster", clusters), steps=2)
    assert trainer.step_num == 4
    assert trainer.trace_counts["train_step"] == 1
    trainer.assert_compiled_once()


# ---------------------------------------------------------------------------
# prefetch pipeline
# ---------------------------------------------------------------------------


def test_multi_stream_prefetch_deterministic(setup):
    """The acceptance contract of the view engine: loss trajectory with
    prefetch_workers=4 is bit-identical to workers=1 and to the
    no-prefetch path (per-index RNG streams + in-order emit)."""
    import jax
    g, engine, clusters = setup
    for strategy in ("mini", "cluster"):
        ref_losses, ref_params = None, None
        for kwargs in ({"prefetch": False},
                       {"prefetch": True, "prefetch_workers": 1},
                       {"prefetch": True, "prefetch_workers": 4}):
            trainer = Trainer(engine, adam(1e-2), seed=0)
            out = trainer.fit(_views(g, strategy, clusters, seed=13),
                              steps=6, **kwargs)
            if ref_losses is None:
                ref_losses, ref_params = out["losses"], trainer.params
                continue
            assert out["losses"] == ref_losses, (strategy, kwargs)
            for a, b in zip(jax.tree_util.tree_leaves(ref_params),
                            jax.tree_util.tree_leaves(trainer.params)):
                assert np.array_equal(np.asarray(a), np.asarray(b))


def test_multi_stream_pool_emits_in_index_order(setup):
    """The pool path consumes the stream by index: the staged sequence
    equals sequential construction even with many workers racing."""
    g, engine, clusters = setup
    stream = _views(g, "mini", clusters, seed=21)
    # copy inside the loop: builder views alias the 2-slot buffer ring
    expected = [shard_view(engine.plan, stream.build(i).copy_masks())
                for i in range(5)]
    from repro.core.trainer import _MultiStreamPrefetcher
    stream.seek(0)
    pool = _MultiStreamPrefetcher(
        stream, lambda v: shard_view(engine.plan, v), steps=5, workers=4)
    got = list(pool)
    assert len(got) == 5
    assert stream.cursor == 5
    for a, b in zip(got, expected):
        for k in a:
            assert np.array_equal(a[k], b[k])


def test_prefetch_on_off_identical(setup):
    g, engine, clusters = setup
    outs, params = [], []
    for prefetch in (True, False):
        trainer = Trainer(engine, adam(1e-2), seed=0)
        out = trainer.fit(_views(g, "mini", clusters, seed=7), steps=6,
                          prefetch=prefetch)
        outs.append(out["losses"])
        params.append(trainer.params)
    assert outs[0] == outs[1]
    import jax
    for a, b in zip(jax.tree_util.tree_leaves(params[0]),
                    jax.tree_util.tree_leaves(params[1])):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_prefetch_propagates_iterator_errors(setup):
    g, engine, clusters = setup
    trainer = Trainer(engine, adam(1e-2), seed=0)

    def broken():
        yield from itertools.islice(_views(g, "mini", clusters), 2)
        raise RuntimeError("stream died")

    with pytest.raises(RuntimeError, match="stream died"):
        trainer.fit(broken(), steps=10)
    assert trainer.step_num == 2   # the two good views were trained on


def test_bounded_in_flight_matches_unbounded(setup):
    g, engine, clusters = setup
    losses = []
    for mif in (1, 0):
        trainer = Trainer(engine, adam(1e-2), seed=0)
        out = trainer.fit(_views(g, "cluster", clusters, seed=2), steps=4,
                          max_in_flight=mif)
        losses.append(out["losses"])
    assert losses[0] == losses[1]


# ---------------------------------------------------------------------------
# eval / infer hooks
# ---------------------------------------------------------------------------


def test_eval_hook_and_infer_compiled_once(setup):
    g, engine, clusters = setup
    trainer = Trainer(engine, adam(1e-2), seed=0)
    gv = global_batch_view(g, 2)
    out = trainer.fit(_views(g, "mini", clusters), steps=6, eval_every=3,
                      eval_view=gv)
    assert [e["step"] for e in out["evals"]] == [3, 6]
    assert all(0.0 <= e["eval_acc"] <= 1.0 for e in out["evals"])
    # a second fit reuses the compiled infer
    trainer.fit(_views(g, "global", clusters), steps=3, eval_every=3,
                eval_view=gv)
    assert trainer.trace_counts["infer"] == 1
    trainer.assert_compiled_once()


# ---------------------------------------------------------------------------
# checkpoint resume
# ---------------------------------------------------------------------------


def test_checkpoint_resume_midstream(setup, tmp_path):
    g, engine, clusters = setup
    ckdir = str(tmp_path / "ck")

    straight = Trainer(engine, adam(1e-2), seed=0)
    straight.fit(_views(g, "mini", clusters, seed=11), steps=8,
                 checkpoint_every=4, checkpoint_dir=ckdir)

    resumed = Trainer(engine, adam(1e-2), seed=99)   # different init
    assert resumed.restore(ckdir, step=4) == 4
    views = _views(g, "mini", clusters, seed=11)
    for _ in range(4):                               # fast-forward the stream
        next(views)
    resumed.fit(views, steps=4)
    resumed.assert_compiled_once()                   # restore didn't retrace

    assert resumed.step_num == straight.step_num == 8
    import jax
    for a, b in zip(jax.tree_util.tree_leaves(straight.params),
                    jax.tree_util.tree_leaves(resumed.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_view_cursor_fast_forwards_stream(setup, tmp_path):
    """restore() records the view-stream cursor from the checkpoint, and
    the next fit() over a ViewStream fast-forwards the stream itself —
    no caller-side ``next()`` skipping (the ROADMAP item)."""
    import jax
    g, engine, clusters = setup
    ckdir = str(tmp_path / "ck")

    straight = Trainer(engine, adam(1e-2), seed=0)
    straight.fit(_views(g, "mini", clusters, seed=31), steps=8,
                 checkpoint_every=4, checkpoint_dir=ckdir)
    assert straight.view_cursor == 8

    resumed = Trainer(engine, adam(1e-2), seed=99)   # different init
    assert resumed.restore(ckdir, step=4) == 4
    assert resumed.view_cursor == 4
    # fresh stream, cursor 0 — fit seeks it to 4 automatically
    resumed.fit(_views(g, "mini", clusters, seed=31), steps=4)
    resumed.assert_compiled_once()
    assert resumed.step_num == straight.step_num == 8
    for a, b in zip(jax.tree_util.tree_leaves(straight.params),
                    jax.tree_util.tree_leaves(resumed.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_restore_cursor_consumed_by_any_fit(setup, tmp_path):
    """A fit over a plain iterator consumes a pending restore cursor —
    it must not stay armed and silently fast-forward a later stream."""
    g, engine, clusters = setup
    ckdir = str(tmp_path / "ck")
    t = Trainer(engine, adam(1e-2), seed=0)
    t.fit(_views(g, "mini", clusters, seed=41), steps=4,
          checkpoint_every=4, checkpoint_dir=ckdir)
    t2 = Trainer(engine, adam(1e-2), seed=0)
    t2.restore(ckdir)
    # legacy path: plain generator, caller fast-forwards by hand
    legacy = iter([v for v in itertools.islice(
        _views(g, "mini", clusters, seed=41), 5)][4:])
    t2.fit(legacy, steps=1)
    # a later unrelated stream must start at ITS cursor, not index 4
    fresh = _views(g, "cluster", clusters, seed=42)
    t2.fit(fresh, steps=2)
    assert fresh.cursor == 2


def test_global_stream_multiworker_staging(setup):
    """The shared staging cache is safe under the worker pool: the static
    global view never yields a half-written (None) staged batch."""
    g, engine, clusters = setup
    for _ in range(3):
        trainer = Trainer(engine, adam(1e-2), seed=0)
        out = trainer.fit(_views(g, "global", clusters), steps=5,
                          prefetch=True, prefetch_workers=4)
        assert len(out["losses"]) == 5
        assert all(np.isfinite(l) for l in out["losses"])


def test_checkpoint_latest_roundtrip(setup, tmp_path):
    g, engine, clusters = setup
    trainer = Trainer(engine, adam(1e-2), seed=0)
    trainer.fit(_views(g, "global", clusters), steps=3)
    trainer.save(str(tmp_path))
    other = Trainer(engine, adam(1e-2), seed=1)
    assert other.restore(str(tmp_path)) == 3
    import jax
    for a, b in zip(jax.tree_util.tree_leaves(trainer.params),
                    jax.tree_util.tree_leaves(other.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# compact bucketed trainer: once-per-bucket trace contract (PR 6)
# ---------------------------------------------------------------------------


def test_compact_trainer_bucket_retrace_contract():
    """The bucketed analog of compiled-once: exactly one trace per
    *touched* (n_pad, e_pad) shape, and repeat epochs over the same
    buckets add zero traces."""
    from repro.core.trainer import CompactTrainer
    from repro.models import make_gnn
    g = _graph(seed=6)
    cfg = GNNConfig(model="gcn", num_layers=2, hidden_dim=16,
                    num_classes=4, feature_dim=8)
    trainer = CompactTrainer(make_gnn(cfg), g, adam(1e-2), seed=0)
    with pytest.raises(RetraceError, match="never ran"):
        trainer.assert_compiled_per_bucket()    # no step yet

    # tiny capped mini views land in the smallest bucket; the dense
    # global stream passes through at the full-graph shape — two
    # guaranteed-distinct staged shapes
    mini = strategy_views(g, "mini", 2, seed=0, steps=3, batch_nodes=4,
                          neighbor_cap=2, compact=True)
    trainer.fit(mini, prefetch=False)
    trainer.fit(strategy_views(g, "global", 2, steps=2), prefetch=False)
    assert (g.num_nodes, g.num_edges) in trainer.buckets_touched
    assert len(trainer.buckets_touched) == 2
    assert trainer.trace_counts["train_step"] == 2
    trainer.assert_compiled_per_bucket()

    # repeat epochs: same buckets, ZERO new traces
    trainer.fit(strategy_views(g, "mini", 2, seed=1, steps=3,
                               batch_nodes=4, neighbor_cap=2,
                               compact=True), prefetch=False)
    trainer.fit(strategy_views(g, "global", 2, steps=1), prefetch=False)
    assert trainer.trace_counts["train_step"] == 2
    assert trainer.step_num == 9
    trainer.assert_compiled_per_bucket()
    # reset keeps the compiled steps
    trainer.reset(seed=1)
    trainer.fit(strategy_views(g, "mini", 2, seed=2, steps=2,
                               batch_nodes=4, neighbor_cap=2,
                               compact=True), prefetch=False)
    assert trainer.trace_counts["train_step"] == 2

    trainer.trace_counts["train_step"] = 5      # simulate a retrace
    with pytest.raises(RetraceError, match="traced 5 times"):
        trainer.assert_compiled_per_bucket()


def test_compact_trainer_prefetch_deterministic():
    """Compact staging under the worker pool: identical trajectories for
    no-prefetch / 1 worker / 4 workers (the staged block is detached from
    the per-bucket ring before the stage lock releases)."""
    from repro.core.trainer import CompactTrainer
    from repro.models import make_gnn
    g = _graph(seed=7)
    cfg = GNNConfig(model="gcn", num_layers=2, hidden_dim=16,
                    num_classes=4, feature_dim=8)
    model = make_gnn(cfg)
    import jax
    params = model.init(jax.random.PRNGKey(0), 8)
    ref = None
    for kwargs in ({"prefetch": False},
                   {"prefetch": True, "prefetch_workers": 1},
                   {"prefetch": True, "prefetch_workers": 4}):
        trainer = CompactTrainer(model, g, adam(1e-2), params=params)
        out = trainer.fit(strategy_views(g, "mini", 2, seed=13, steps=6,
                                         batch_nodes=16, compact=True),
                          **kwargs)
        if ref is None:
            ref = out["losses"]
        else:
            assert out["losses"] == ref, kwargs


def test_engine_trainer_compact_stream_parity(setup):
    """The distributed engine consumes compact streams through
    _shard_compact bit-exactly: same losses as the dense stream, and the
    engine's compiled-once contract holds (sharded shapes come from the
    PartitionPlan, not the view)."""
    g, engine, clusters = setup
    losses = {}
    for compact in (False, True):
        trainer = Trainer(engine, adam(1e-2), seed=0)
        out = trainer.fit(
            strategy_views(g, "mini", 2, seed=17, batch_nodes=24,
                           compact=compact), steps=4)
        out2 = trainer.fit(
            strategy_views(g, "cluster", 2, seed=17, clusters=clusters,
                           clusters_per_batch=2, halo_hops=1,
                           compact=compact), steps=3)
        trainer.assert_compiled_once()
        losses[compact] = out["losses"] + out2["losses"]
    assert losses[False] == losses[True]


# ---------------------------------------------------------------------------
# distributed (P=4) sweep — subprocess with fake devices, slow lane
# ---------------------------------------------------------------------------

_DIST = r"""
import numpy as np, jax
from repro.config import GNNConfig
from repro.core.clustering import label_propagation_clusters
from repro.core.engine import HybridParallelEngine
from repro.core.partition import build_partitions
from repro.core.strategies import (global_batch_view, shard_view,
                                   shard_view_loop, strategy_views)
from repro.core.trainer import Trainer
from repro.graph import sbm_graph
from repro.models import make_gnn
from repro.optim import adam

g = sbm_graph(num_nodes=400, num_classes=4, feature_dim=8, p_in=0.05,
              p_out=0.005, seed=0).add_self_loops()
clusters = label_propagation_clusters(g, max_cluster_size=80, seed=0)
sg = build_partitions(g, 4)
for backend in ("reference", "csc"):
    cfg = GNNConfig(model="gcn", num_layers=2, hidden_dim=16, num_classes=4,
                    feature_dim=8, aggregate_backend=backend)
    engine = HybridParallelEngine(make_gnn(cfg), sg)
    trainer = Trainer(engine, adam(1e-2), seed=0)

    # naive reference loop == Trainer, step for step
    params = engine.model.init(jax.random.PRNGKey(0), 8)
    opt = adam(1e-2)
    opt_state = opt.init(params)
    step_fn = engine.make_train_step(opt)
    naive_losses = []
    views = strategy_views(g, "mini", 2, seed=3, batch_nodes=40,
                           clusters=clusters)
    trainer_losses = trainer.fit(
        strategy_views(g, "mini", 2, seed=3, batch_nodes=40,
                       clusters=clusters), steps=4)["losses"]
    for _ in range(4):
        params, opt_state, loss = step_fn(
            params, opt_state, shard_view_loop(sg.plan, next(views)))
        naive_losses.append(float(loss))
    assert np.allclose(naive_losses, trainer_losses, atol=1e-6), (
        backend, naive_losses, trainer_losses)

    for strategy in ("global", "cluster"):
        trainer.fit(strategy_views(g, strategy, 2, seed=1,
                                   clusters=clusters), steps=2)
    trainer.assert_compiled_once()
    acc = trainer.evaluate(global_batch_view(g, 2))
    assert 0.0 <= acc <= 1.0
    print(backend, "ok", trainer.trace_counts)
print("distributed trainer ok")
"""


@pytest.mark.slow
def test_trainer_distributed_p4():
    out = run_with_devices(_DIST, n_devices=4)
    assert "distributed trainer ok" in out
