"""Parity of the Sum-stage aggregation backends: "csc" (Pallas CSC-blocked
kernels) == "reference" (jnp segment ops) across every registered combine
mode, on the raw combine engine, the single-block forward path, and the
4-way distributed engine."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_with_devices
from repro.config import GNNConfig
from repro.core.aggregate import (COMBINE_SPECS, MissingPlanError,
                                  backend_name, combine, get_backend)
from repro.core.mpgnn import loss_block
from repro.core.strategies import global_batch_view, mini_batch_views
from repro.graph import sbm_graph
from repro.kernels.ops import build_csc_plan
from repro.models import make_gnn

MODES = sorted(COMBINE_SPECS)


def _edge_problem(seed, E=400, N=90, H=2, D=8, mask_frac=0.3,
                  empty_tail=True):
    """Random messages with masked edges and (when empty_tail) a run of
    destinations receiving no edges at all."""
    rng = np.random.default_rng(seed)
    hi = N // 2 if empty_tail else N
    ids = rng.integers(0, hi, E).astype(np.int32)
    msg = {"value": jnp.asarray(rng.normal(size=(E, H, D)), jnp.float32),
           "logit": jnp.asarray(rng.normal(size=(E, H)) * 3, jnp.float32)}
    mask = jnp.asarray(rng.random(E) > mask_frac, jnp.float32)
    return msg, jnp.asarray(ids), ids, mask


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("H,D", [(1, 16), (2, 8)])
def test_combine_parity(mode, H, D):
    # deterministic seed (str hash is randomized per process)
    seed = sum(mode.encode()) * 7 + H
    msg, dst, ids_np, mask = _edge_problem(seed=seed, H=H, D=D)
    N = 90
    plan = build_csc_plan(ids_np, N, block_n=32, block_e=64)
    ref = combine(mode, msg, dst, N, mask, backend="reference")
    csc = combine(mode, msg, dst, N, mask, backend="csc", plan=plan)
    np.testing.assert_allclose(np.asarray(csc), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mode", MODES)
def test_combine_gradient_parity(mode):
    msg, dst, ids_np, mask = _edge_problem(seed=7, H=2, D=8)
    N = 90
    plan = build_csc_plan(ids_np, N, block_n=32, block_e=64)

    def loss(value, logit, backend, plan):
        out = combine(mode, {"value": value, "logit": logit}, dst, N, mask,
                      backend=backend, plan=plan)
        return jnp.sum(out * out)

    g_ref = jax.grad(loss, argnums=(0, 1))(msg["value"], msg["logit"],
                                           "reference", None)
    g_csc = jax.grad(loss, argnums=(0, 1))(msg["value"], msg["logit"],
                                           "csc", plan)
    for a, b in zip(g_ref, g_csc):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=1e-5)


def test_combine_all_edges_masked():
    """Fully masked input: every mode must produce exact zeros (and not
    NaN/inf from empty-segment softmax or -inf max identities)."""
    msg, dst, ids_np, _ = _edge_problem(seed=3, H=2, D=4)
    N = 90
    mask = jnp.zeros(ids_np.shape[0], jnp.float32)
    plan = build_csc_plan(ids_np, N, block_n=32, block_e=64)
    for mode in MODES:
        for be, pl_ in (("reference", None), ("csc", plan)):
            out = np.asarray(combine(mode, msg, dst, N, mask, backend=be,
                                     plan=pl_))
            assert np.all(np.isfinite(out)), (mode, be)
            np.testing.assert_allclose(out, 0.0, atol=1e-6,
                                       err_msg=f"{mode}/{be}")


def test_unknown_mode_and_backend_raise():
    msg, dst, ids_np, mask = _edge_problem(seed=1, H=1, D=4)
    with pytest.raises(ValueError, match="combine mode"):
        combine("median", msg, dst, 90, mask)
    with pytest.raises(ValueError, match="backend"):
        get_backend("cuda")


def test_csc_without_plan_raises_typed_error():
    """The kernel backend never degrades to the reference ops in silence:
    a combine without a CSCPlan is a typed error, in every mode."""
    msg, dst, ids_np, mask = _edge_problem(seed=2, H=1, D=4)
    for mode in MODES:
        with pytest.raises(MissingPlanError, match="CSCPlan"):
            combine(mode, msg, dst, 90, mask, backend="csc")


def test_backend_default_follows_platform(monkeypatch):
    """No configured name: the kernels on TPU, the reference elsewhere;
    an explicit name is honoured on any platform."""
    cfg = GNNConfig(model="gcn", num_classes=3, feature_dim=8)
    here = "csc" if jax.default_backend() == "tpu" else "reference"
    assert cfg.aggregate_backend is None
    assert backend_name(make_gnn(cfg)) == get_backend(None).name == here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert backend_name(make_gnn(cfg)) == get_backend(None).name == "csc"
    pinned = dataclasses.replace(cfg, aggregate_backend="reference")
    assert backend_name(make_gnn(pinned)) == "reference"


@pytest.mark.parametrize("model_name,heads",
                         [("gcn", 1), ("sage", 1), ("sage_max", 1),
                          ("gat", 2)])
def test_block_forward_backend_parity(model_name, heads):
    """loss + grads of the single-block path agree between backends, on
    global-batch and (masked-edge) mini-batch views."""
    g = sbm_graph(num_nodes=200, num_classes=3, feature_dim=16,
                  p_in=0.05, p_out=0.01, seed=0).add_self_loops()
    gcn_norm = model_name == "gcn"
    cfg = GNNConfig(model=model_name, num_layers=2, hidden_dim=8,
                    num_classes=3, feature_dim=16, num_heads=heads)
    model_ref = make_gnn(cfg)
    model_csc = dataclasses.replace(model_ref, aggregate_backend="csc")
    params = model_ref.init(jax.random.PRNGKey(0), 16)
    views = [global_batch_view(g, 2),
             next(mini_batch_views(g, 2, batch_nodes=12, seed=1))]
    for view in views:
        l_ref, g_ref = jax.value_and_grad(
            lambda p: loss_block(model_ref, p,
                                 view.as_block(gcn_norm=gcn_norm)))(params)
        l_csc, g_csc = jax.value_and_grad(
            lambda p: loss_block(model_csc, p,
                                 view.as_block(gcn_norm=gcn_norm,
                                               csc_plan=True)))(params)
        assert abs(float(l_ref) - float(l_csc)) < 1e-5, view.strategy
        err = max(float(jnp.abs(a - b).max()) for a, b in zip(
            jax.tree_util.tree_leaves(g_ref),
            jax.tree_util.tree_leaves(g_csc)))
        assert err < 1e-5, (model_name, view.strategy, err)


def test_block_csc_plan_is_cached_and_reused():
    """The paper's reused-CSC-indexing claim: every view of one graph
    shares the same plan object."""
    g = sbm_graph(num_nodes=120, num_classes=3, feature_dim=8,
                  p_in=0.06, p_out=0.02, seed=4)
    b1 = global_batch_view(g, 2).as_block(csc_plan=True)
    b2 = next(mini_batch_views(g, 2, batch_nodes=10, seed=0)).as_block(
        csc_plan=True)
    assert b1.csc_plan is b2.csc_plan
    assert b1.csc_plan is g.csc_plan(b1.num_nodes_padded,
                                     b1.num_edges_padded)


_DISTRIBUTED = r"""
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.config import GNNConfig
from repro.core.mpgnn import loss_block
from repro.core.strategies import global_batch_view, mini_batch_views, \
    shard_view
from repro.core.partition import build_partitions
from repro.core.engine import HybridParallelEngine
from repro.graph import sbm_graph
from repro.models import make_gnn

g = sbm_graph(num_nodes=250, num_classes=3, feature_dim=16, p_in=0.05,
              p_out=0.01, seed=2).add_self_loops()
# one model per combine mode: sum (gcn), mean (sage), max (sage_max),
# softmax (gat, multi-head)
for model_name, heads in (("gcn", 1), ("sage", 1), ("sage_max", 1),
                          ("gat", 2)):
    gcn_norm = model_name == "gcn"
    cfg = GNNConfig(model=model_name, num_layers=2, hidden_dim=8,
                    num_classes=3, feature_dim=16, num_heads=heads,
                    aggregate_backend="csc")
    model = make_gnn(cfg)
    params = model.init(jax.random.PRNGKey(0), 16)
    model_ref = dataclasses.replace(model, aggregate_backend="reference")
    sg = build_partitions(g, 4, gcn_norm=gcn_norm)
    eng = HybridParallelEngine(model, sg)
    assert "csc_gather" in eng._device_data    # kernels actually staged
    lg = eng.make_loss_and_grad()
    views = [global_batch_view(g, 2),
             next(mini_batch_views(g, 2, batch_nodes=24, seed=1))]
    for view in views:
        ref_l, ref_g = jax.value_and_grad(
            lambda p: loss_block(model_ref, p,
                                 view.as_block(gcn_norm=gcn_norm)))(params)
        loss, grads = lg(params, eng._device_data,
                         eng.stage_view(shard_view(sg.plan, view)))
        assert abs(float(ref_l) - float(loss)) < 1e-4, \
            (model_name, view.strategy, float(ref_l), float(loss))
        err = max(float(jnp.abs(a - b).max()) for a, b in zip(
            jax.tree_util.tree_leaves(ref_g),
            jax.tree_util.tree_leaves(grads)))
        assert err < 1e-4, (model_name, view.strategy, err)
    print(model_name, "ok")
print("ALL_OK")
"""


@pytest.mark.slow
def test_distributed_csc_backend_parity_4workers():
    """P=4 hybrid-parallel engine with the csc backend == single-block
    reference, for all four combine modes, global and mini-batch views."""
    out = run_with_devices(_DISTRIBUTED, n_devices=4, timeout=900)
    assert "ALL_OK" in out


_DISTRIBUTED_GRAD = r"""
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.config import GNNConfig
from repro.core.strategies import global_batch_view, shard_view
from repro.core.partition import build_partitions
from repro.core.engine import HybridParallelEngine
from repro.graph import sbm_graph
from repro.models import make_gnn

# jax.grad THROUGH the P=4 engine, csc backend vs reference backend —
# the sharded grad path runs the fused backward kernels (plans threaded
# into the custom_vjp residuals), the reference engine runs jnp segment
# ops; gradients of the replicated params must match per combine mode.
g = sbm_graph(num_nodes=220, num_classes=3, feature_dim=12, p_in=0.05,
              p_out=0.01, seed=5).add_self_loops()
for model_name, heads in (("gcn", 1), ("sage", 1), ("sage_max", 1),
                          ("gat", 2)):
    gcn_norm = model_name == "gcn"
    cfg = GNNConfig(model=model_name, num_layers=2, hidden_dim=8,
                    num_classes=3, feature_dim=12, num_heads=heads,
                    aggregate_backend="csc")
    model = make_gnn(cfg)
    params = model.init(jax.random.PRNGKey(1), 12)
    model_ref = dataclasses.replace(model, aggregate_backend="reference")
    sg = build_partitions(g, 4, gcn_norm=gcn_norm)
    eng_csc = HybridParallelEngine(model, sg)
    eng_ref = HybridParallelEngine(model_ref, sg)
    assert "csc_dst" in eng_csc._device_data   # backward plans staged
    view = eng_csc.stage_view(shard_view(sg.plan, global_batch_view(g, 2)))
    l_csc, g_csc = eng_csc.make_loss_and_grad()(
        params, eng_csc._device_data, view)
    l_ref, g_ref = eng_ref.make_loss_and_grad()(
        params, eng_ref._device_data, view)
    assert abs(float(l_csc) - float(l_ref)) < 1e-4, (model_name,)
    err = max(float(jnp.abs(a - b).max()) for a, b in zip(
        jax.tree_util.tree_leaves(g_csc),
        jax.tree_util.tree_leaves(g_ref)))
    assert err < 1e-4, (model_name, err)
    print(model_name, "grads ok", err)
print("GRADS_OK")
"""


@pytest.mark.slow
def test_distributed_grad_parity_csc_vs_reference_4workers():
    """jax.grad through the P=4 engine: csc-backend gradients (fused
    Pallas backward kernels under shard_map) == reference-backend
    gradients for sum/mean/max/softmax."""
    out = run_with_devices(_DISTRIBUTED_GRAD, n_devices=4, timeout=900)
    assert "GRADS_OK" in out
