"""Cell 1's train step at its largest bucket rung, and cell 2's Sum-stage
kernels at its edge count, compile for a described TPU v5e (Mosaic, not
interpret mode), so the first chip run meets no compiler refusal.
Nothing runs. The topology is described inside a module fixture: the
TPU compiler's library may be loaded by one process at a time."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import bench
import graphs


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU lib
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def mosaic(monkeypatch):
    """Kernels compile with Mosaic although the process runs on the CPU
    (both modules that ask the platform are told it is a TPU's)."""
    from repro.core import aggregate
    from repro.kernels import ops
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    monkeypatch.setattr(aggregate, "default_interpret", lambda: False)


def _specs(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                       sharding=sharding), tree)


def test_cell1_step_at_largest_rung_compiles(one_chip, mosaic):
    from repro.config import GNNConfig
    from repro.core.trainer import CompactTrainer
    from repro.core.strategies import strategy_views
    from repro.models import make_gnn
    from repro.optim import adam
    cell = bench.find_cell("gat_e-alipay.mini-train")
    cfg, t = cell["config_data"], cell["traffic_data"]
    G = graphs.to_program_graph(graphs.make_graph(cfg))
    m = cfg["model"]
    model = make_gnn(GNNConfig(
        model="gat_e", num_layers=m["num_layers"], hidden_dim=m["hidden_dim"],
        num_classes=m["num_classes"], feature_dim=G.node_features.shape[1],
        edge_feature_dim=m["edge_feature_dim"], num_heads=m["num_heads"],
        aggregate_backend="csc"))
    tr = CompactTrainer(model, G, adam(t["lr"]), gcn_norm=False)
    views = strategy_views(G, "mini", m["num_layers"], seed=1,
                           batch_nodes=t["batch_nodes"],
                           neighbor_cap=t["neighbor_cap"], compact=True)
    top = tr.buckets.shapes[-1]
    block = views.build(0, views.make_builder()).as_block(
        gcn_norm=False, csc_plan=True, bucket=top)
    compiled = tr._step.lower(_specs(tr.params, one_chip),
                              _specs(tr.opt_state, one_chip),
                              _specs(block, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9


def test_cell2_sum_stage_kernels_compile(one_chip, mosaic):
    from repro.kernels import ops
    cfg = bench.load_json(bench.HERE / "configs" / "gcn-reddit.json")
    g = graphs.make_graph(cfg)
    n, e, d = len(g["y"]), len(g["src"]), cfg["model"]["hidden_dim"]
    plan = ops.build_csc_plan(g["dst"], n)
    spec = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    fwd = ops._segment_reduce_planned.lower(
        spec((e, d)), spec(plan.gather_idx.shape, jnp.int32),
        spec(plan.local_ids.shape, jnp.int32), num_segments=n,
        block_n=plan.block_n, block_e=plan.block_e, interpret=False,
        op="sum").compile()
    bwd = ops._segment_sum_bwd_planned.lower(
        spec((n, d)), spec(plan.edge_dst.shape, jnp.int32), num_edges=e,
        block_e=plan.block_e, interpret=False).compile()
    for c in (fwd, bwd):
        assert "tpu_custom_call" in c.as_text()
