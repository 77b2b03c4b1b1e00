"""The span recorder (``repro.utils.timing``) and the spans the trainer,
the prefetch workers, the stager and the server open.

A span always enters a profiler annotation; only while a profiler
session collects does it append a record, timed on ``perf_counter_ns``.
These tests check that nothing is recorded (or kept) without a session,
that records nest per thread and respect the buffer's bound, that a
record's start and its annotation in the written trace differ by one
constant, and that each instrumented stage records what it should.
"""
import contextlib
import glob
import gc
import os
import threading
import time
import tracemalloc

import jax
import numpy as np
import pytest

from repro.config import GNNConfig
from repro.core.engine import HybridParallelEngine
from repro.core.partition import build_partitions
from repro.core.strategies import shard_view, strategy_views
from repro.core.trainer import CompactTrainer, Trainer
from repro.core.views import CompactBlockBuilder, ViewBuilder
from repro.graph import sbm_graph
from repro.models import make_gnn
from repro.optim import adam
from repro.serving import GNNServer, ServeStats
from repro.serving.server import LATENCY_WINDOW
from repro.utils import timing
from repro.utils.timing import annotate, span


def _graph(n=220, seed=0):
    return sbm_graph(num_nodes=n, num_classes=4, feature_dim=8,
                     p_in=0.05, p_out=0.005, seed=seed).add_self_loops()


def _model():
    return make_gnn(GNNConfig(model="gcn", num_layers=2, hidden_dim=16,
                              num_classes=4, feature_dim=8))


@contextlib.contextmanager
def profiling(trace_dir):
    """A profiler session around the block, the buffer emptied first."""
    timing.clear()
    jax.profiler.start_trace(str(trace_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def trace_events(trace_dir) -> dict:
    """The written trace's host events, by name (the last of each)."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    assert paths
    events = {}
    for plane in ProfileData.from_file(paths[0]).planes:
        for line in plane.lines:
            for ev in line.events:
                events[ev.name] = ev
    return events


def by_name(records, name):
    return [r for r in records if r.name == name]


def children(records, parent, name=None):
    return [r for r in records if r.parent == parent.id
            and (name is None or r.name == name)]


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------


def test_no_session_records_nothing_and_keeps_nothing():
    timing.clear()
    assert not timing.recording()
    for _ in range(100):              # warm any lazily built state
        with span("t.outer", k=1):
            with span("t.inner"):
                annotate(x=1)
    gc.collect()
    tracemalloc.start()
    try:
        before, _ = tracemalloc.get_traced_memory()
        for i in range(20000):
            with span("t.outer", k=i):
                with span("t.inner"):
                    annotate(x=i)
        gc.collect()
        after, _ = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert timing.recorded() == []
    assert timing.dropped() == 0
    # 20,000 spans keep nothing alive: a record would be ~100 bytes each
    assert after - before < 4096, after - before


def test_span_times_itself_without_a_session():
    with span("t.timed") as s:
        time.sleep(0.002)
    assert s.end_ns - s.start_ns >= 2_000_000
    assert s.seconds == pytest.approx((s.end_ns - s.start_ns) / 1e9)


def test_parents_nest_per_thread(tmp_path):
    ready = threading.Barrier(2)

    def work(tag):
        with span("t.outer", tag=tag):
            ready.wait(timeout=10)    # both outers open at once
            with span("t.mid"):
                with span("t.leaf"):
                    annotate(done=tag)

    with profiling(tmp_path):
        threads = [threading.Thread(target=work, args=(t,), name=f"w{t}")
                   for t in range(2)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=30)
        assert not any(th.is_alive() for th in threads)
    recs = timing.recorded()
    assert len(recs) == 6
    ids = {r.id: r for r in recs}
    for outer in by_name(recs, "t.outer"):
        assert outer.parent is None
        (mid,) = children(recs, outer)
        (leaf,) = children(recs, mid)
        assert (mid.name, leaf.name) == ("t.mid", "t.leaf")
        # the thread's own stack, whatever the other thread had open
        assert outer.thread == mid.thread == leaf.thread == \
            f"w{outer.attrs['tag']}"
        assert leaf.attrs == {"done": outer.attrs["tag"]}
        assert outer.start_ns <= mid.start_ns <= leaf.start_ns
        assert leaf.end_ns <= mid.end_ns <= outer.end_ns
    assert all(r.parent is None or r.parent in ids for r in recs)


def test_buffer_bound_counts_drops(tmp_path, monkeypatch):
    monkeypatch.setattr(timing, "CAPACITY", 5)
    with profiling(tmp_path):
        for i in range(8):
            with span("t.many", i=i):
                pass
    recs = timing.recorded()
    assert [r.attrs["i"] for r in recs] == [0, 1, 2, 3, 4]
    assert timing.dropped() == 3
    timing.clear()
    assert timing.recorded() == [] and timing.dropped() == 0


def test_spans_share_the_profiler_clock(tmp_path):
    """Each record's start equals its annotation's start in the written
    trace minus one constant (within 50 us)."""
    names = [f"t.clock{i}" for i in range(6)]
    with profiling(tmp_path):
        for name in names:
            with span(name):
                time.sleep(0.003)
    recs = {r.name: r for r in timing.recorded()}
    events = trace_events(tmp_path)
    assert set(names) <= set(events)
    offsets = [events[n].start_ns - recs[n].start_ns for n in names]
    assert max(offsets) - min(offsets) < 50_000, offsets


def test_entry_attrs_reach_the_profiler_trace(tmp_path):
    """Attrs given when the span opens are the trace event's stats, under
    the span's own name; counters added later live in the buffer alone."""
    with profiling(tmp_path):
        with span("t.attrs", view=3, path="full"):
            annotate(late=1)
    (rec,) = timing.recorded()
    assert rec.attrs == {"view": 3, "path": "full", "late": 1}
    stats = dict(trace_events(tmp_path)["t.attrs"].stats)
    assert stats["view"] == 3 and stats["path"] == "full"
    assert "late" not in stats


@pytest.mark.parametrize("between", ["read", "span"])
def test_a_new_session_replaces_the_last_ones_records(tmp_path, between):
    """Two sessions in one process: once a read or a span has seen the
    first one end, the second one's records stand alone."""
    timing.clear()
    jax.profiler.start_trace(str(tmp_path / "one"))
    with span("t.first"):
        pass
    outlived = span("t.outlives").__enter__()
    jax.profiler.stop_trace()
    if between == "read":
        assert [r.name for r in timing.recorded()] == ["t.first"]
    else:
        with span("t.off"):
            pass
    jax.profiler.start_trace(str(tmp_path / "two"))
    with span("t.second"):
        with span("t.inner"):
            pass
    outlived.__exit__(None, None, None)   # opened in the first session
    jax.profiler.stop_trace()
    recs = timing.recorded()
    assert [r.name for r in recs] == ["t.inner", "t.second"]
    assert recs[0].parent == recs[1].id and recs[1].parent is None
    assert timing.dropped() == 0


# ---------------------------------------------------------------------------
# the instrumented stages
# ---------------------------------------------------------------------------


def _trainer(g):
    return CompactTrainer(_model(), g, adam(1e-2), seed=0)


def _mini(g, seed=0):
    return strategy_views(g, "mini", 2, seed=seed, batch_nodes=24,
                          neighbor_cap=4, compact=True)


def test_fit_records_its_steps(tmp_path):
    g = _graph(seed=1)
    tr = _trainer(g)
    tr.fit(_mini(g), steps=1, prefetch_workers=2)      # compile outside
    with profiling(tmp_path):
        tr.fit(_mini(g, seed=1), steps=3, prefetch_workers=2)
    recs = timing.recorded()
    (fit,) = by_name(recs, "train.fit")
    # the reference backend runs no backward kernel
    assert fit.attrs == {"steps": 3, "bwd_vmem": 0, "bwd_dma": 0}
    waits = children(recs, fit, "train.view_wait")
    assert sorted(r.attrs["view"] for r in waits) == [0, 1, 2]
    assert len(children(recs, fit, "train.dispatch")) == 3
    assert len(by_name(recs, "train.backpressure")) == 1   # 3 steps, 2 ahead
    builds = by_name(recs, "prefetch.build")
    assert sorted(r.attrs["view"] for r in builds) == [0, 1, 2]
    for b in builds:
        assert b.thread != fit.thread          # built on a worker thread
        assert len(children(recs, b, "view.sample")) == 1
        (stage,) = children(recs, b, "view.stage")
        assert stage.attrs["plan_lanes"] == 0   # reference backend: no plan
        assert stage.attrs["live_edges"] > 0
        assert fit.start_ns <= b.start_ns and b.end_ns <= fit.end_ns


def test_view_stage_counts_plan_lanes_and_live_edges(tmp_path):
    g = _graph(seed=2)
    stager = CompactBlockBuilder(g, 2, csc_plan=True)
    view = ViewBuilder(g, 2, compact=True).khop_compact(np.arange(5))
    with profiling(tmp_path):
        block = stager.stage(view)
    (stage,) = by_name(timing.recorded(), "view.stage")
    plan = block.csc_plan
    assert stage.attrs["plan_lanes"] == plan.gather_idx.size
    # the packed plan's lanes as built: the bucket's chunk bound
    from repro.kernels.ops import bucket_plan_chunks
    assert plan.gather_idx.size == plan.block_e * bucket_plan_chunks(
        plan.num_segments, plan.num_edges, plan.block_n, plan.block_e)
    assert stage.attrs["live_edges"] == len(view.dst_local)


@pytest.mark.parametrize("strategy,stagings", [("global", 1), ("mini", 3)])
def test_engine_trainer_spans_its_staging(tmp_path, strategy, stagings):
    """The engine ``Trainer`` shards and stages each view inside its
    ``prefetch.build``; ``staged_bytes`` is what the staging puts on the
    device. Without donation (the CPU) the static global view is staged
    once a fit, a mini-batch stream once a step."""
    g = _graph(seed=3)
    sg = build_partitions(g, 1)
    tr = Trainer(HybridParallelEngine(_model(), sg), adam(1e-2), seed=0)
    views = lambda: strategy_views(g, strategy, 2, seed=1,  # noqa: E731
                                   batch_nodes=24)
    # one worker: two may both miss the global view's cache and stage it
    tr.fit(views(), steps=1, prefetch_workers=1)        # compile outside
    with profiling(tmp_path):
        tr.fit(views(), steps=3, prefetch_workers=1)
    recs = timing.recorded()
    builds = {r.id: r for r in by_name(recs, "prefetch.build")}
    assert len(builds) == 3
    shards = by_name(recs, "engine.shard")
    stages = by_name(recs, "engine.stage")
    assert len(shards) == len(stages) == stagings
    for shard, stage in zip(sorted(shards, key=lambda r: r.start_ns),
                            sorted(stages, key=lambda r: r.start_ns)):
        assert shard.parent == stage.parent and shard.parent in builds
        assert shard.end_ns <= stage.start_ns
        view = views().build(builds[stage.parent].attrs["view"])
        want = sum(a.nbytes for a in shard_view(sg.plan, view).values())
        assert stage.attrs == {"staged_bytes": want}


def _gcn_engine_trainer():
    g = _graph(seed=4)
    model = make_gnn(GNNConfig(model="gcn", num_layers=2, hidden_dim=16,
                               num_classes=4, feature_dim=8,
                               aggregate_backend="csc"))
    tr = Trainer(HybridParallelEngine(model, build_partitions(g, 1)),
                 adam(1e-2), seed=0)
    return tr, lambda: strategy_views(g, "global", 2, seed=1)


def _gat_e_compact_trainer():
    from repro.graph import make_dataset
    g = make_dataset("alipay_like", num_nodes=300, seed=0)
    model = make_gnn(GNNConfig(
        model="gat_e", num_layers=2, hidden_dim=16, num_classes=2,
        feature_dim=g.node_features.shape[1], num_heads=4,
        edge_feature_dim=g.edge_features.shape[1], aggregate_backend="csc"))
    tr = CompactTrainer(model, g, adam(1e-2), seed=0, gcn_norm=False)
    return tr, lambda: strategy_views(g, "mini", 2, seed=0, batch_nodes=24,
                                      neighbor_cap=4, compact=True)


@pytest.mark.parametrize("path", ["vmem", "dma"])
@pytest.mark.parametrize("make", [_gcn_engine_trainer,
                                  _gat_e_compact_trainer],
                         ids=["engine_gcn", "compact_gat_e"])
def test_fit_counts_backward_launches_by_path(tmp_path, monkeypatch, make,
                                              path):
    """``train.fit`` carries the backward Sum-stage launches of the
    compiled step by path: two layers, two launches (segment sums in the
    engine's GCN, edge softmaxes in the compact GAT-E), all resident at
    these sizes, or all on row DMAs under a zero budget."""
    from repro.kernels import backward
    if path == "dma":
        monkeypatch.setattr(backward, "VMEM_BUDGET", 0)
    jax.clear_caches()          # jit keys on shapes, not on the budget
    tr, views = make()
    try:
        tr.fit(views(), steps=1, prefetch_workers=1)     # compile outside
        with profiling(tmp_path):
            tr.fit(views(), steps=2, prefetch_workers=1)
    finally:
        jax.clear_caches()
    (fit,) = by_name(timing.recorded(), "train.fit")
    other = "dma" if path == "vmem" else "vmem"
    assert fit.attrs == {"steps": 2, f"bwd_{path}": 2, f"bwd_{other}": 0}


@pytest.fixture(scope="module")
def served():
    g = _graph(seed=3)
    model = _model()
    params = model.init(jax.random.PRNGKey(0), 8)
    return g, model, params


def _stream(srv, nodes, clients=4):
    it = iter(nodes)
    lock = threading.Lock()

    def client():
        while True:
            with lock:
                n = next(it, None)
            if n is None:
                return
            srv.request(int(n), timeout=60)

    threads = [threading.Thread(target=client) for _ in range(clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
    assert not any(th.is_alive() for th in threads)


def test_server_records_each_batch_and_feeds_its_stats(tmp_path, served):
    g, model, params = served
    srv = GNNServer(model, params, g, max_batch=4, max_wait_ms=1.0)
    rng = np.random.default_rng(0)
    hot = rng.choice(g.num_nodes, 6, replace=False)
    srv.submit(hot)                        # compile, fill the cache
    srv.submit(hot)
    srv.start()
    before = srv.stats.summary()["stage_s"], srv.stats.batches
    misses_before = srv.cache.misses
    with profiling(tmp_path):
        _stream(srv, rng.choice(hot, 40).tolist()
                + rng.integers(0, g.num_nodes, 20).tolist())
        srv.submit(hot[:3])
    srv.stop()
    recs = timing.recorded()
    batches = by_name(recs, "serve.batch")
    assert len(batches) == srv.stats.batches - before[1]
    assert sorted(b.attrs["batch"] for b in batches) == \
        list(range(2, 2 + len(batches)))
    assert sum(b.attrs["requests"] for b in batches) == 63
    for b in batches:
        names = [r.name for r in children(recs, b)]
        for stage in ("serve.lock", "serve.cover", "serve.gather"):
            assert names.count(stage) == 1, (stage, names)
        paths = [r.attrs["path"] for r in children(recs, b, "serve.device")]
        assert paths in (["full"], ["hit"], ["full", "hit"])
        assert (paths[0] == "full") == (b.attrs["misses"] > 0)
        assert names.count("serve.view") == names.count("serve.stage") \
            == len(paths)
        assert names.count("serve.writeback") == (b.attrs["misses"] > 0)
        for st in children(recs, b, "serve.stage"):
            assert len(children(recs, st, "view.stage")) == 1
    # the batches' miss counters are the cache's own, split by batch
    assert sum(b.attrs["misses"] for b in batches) == \
        srv.cache.misses - misses_before
    responded = [b for b in batches if children(recs, b, "serve.respond")]
    assert len(responded) == len(batches) - 1       # all but submit()'s
    assert by_name(recs, "serve.collect")
    # the stage totals are the spans' own durations
    after = srv.stats.summary()["stage_s"]
    dev_s = sum((r.end_ns - r.start_ns) / 1e9
                for r in by_name(recs, "serve.device"))
    assert after["device_step"] - before[0]["device_step"] == \
        pytest.approx(dev_s, abs=1e-6)
    gather_s = sum((r.end_ns - r.start_ns) / 1e9
                   for r in by_name(recs, "serve.gather"))
    assert after["gather"] - before[0]["gather"] == \
        pytest.approx(gather_s, abs=1e-6)


def test_no_session_fit_and_serving_record_nothing(served):
    timing.clear()
    g = _graph(seed=4)
    tr = _trainer(g)
    tr.fit(_mini(g), steps=3, prefetch_workers=2)
    g, model, params = served
    srv = GNNServer(model, params, g, max_batch=4, max_wait_ms=1.0).start()
    _stream(srv, list(range(30)))
    srv.submit([1, 2, 3])
    srv.close()
    assert timing.recorded() == []
    assert timing.dropped() == 0


def test_serve_latencies_keep_the_recent_window():
    st = ServeStats()
    st.latencies_s.extend([1.0] * LATENCY_WINDOW)
    st.latencies_s.extend([0.002] * 10)
    assert len(st.latencies_s) == LATENCY_WINDOW
    assert st.latencies_s[-1] == 0.002
    lat = st.summary()["latency_ms"]
    assert lat["p50"] == pytest.approx(1e3)
    assert lat["mean"] == pytest.approx(
        1e3 * (1.0 * (LATENCY_WINDOW - 10) + 0.02) / LATENCY_WINDOW)
