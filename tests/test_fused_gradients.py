"""jax.grad parity of the fused-gather CSC kernels vs the reference
backend, plus the fused-path memory contracts:

- no (nb, L_pad, D) pre-gather tensor in the jaxpr (forward AND backward)
- no reference segment scatter / g[segment_ids] backward gather on the
  csc path (the fused backward kernels of kernels/backward.py), asserted
  via ``assert_sum_stage_fused`` on value_and_grad jaxprs

Covers multi-head messages, empty segments, masked edges, pad lanes and
D > 64 (the d-tiled grid axes), for every combine mode the kernels
accelerate, with the backward kernels on both of their paths: the node
table resident in VMEM, and one row DMA per lane (forced by a zero
budget); the two give the same bits."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregate import combine
from repro.kernels import backward
from repro.kernels.ops import (assert_pregather_free,
                               assert_sum_stage_fused, build_csc_plan,
                               count_segment_scatters, edge_softmax_op,
                               segment_max_op, segment_sum_op)

KERNEL_MODES = ["sum", "max", "softmax"]
PATHS = ["vmem", "dma"]


def _grads_on(path, monkeypatch, loss, *args):
    """``jax.grad`` of ``loss`` with the backward kernels on ``path``; the
    tally shows each launch took it. jit caches key on shapes, not on the
    budget, so they are cleared on both sides."""
    monkeypatch.setattr(backward, "VMEM_BUDGET",
                        backward.DEFAULT_VMEM_BUDGET if path == "vmem" else 0)
    jax.clear_caches()
    with backward.launch_tally() as launches:
        grads = jax.grad(loss, argnums=tuple(range(len(args))))(*args)
    jax.clear_caches()
    assert launches[f"bwd_{path}"] > 0
    assert launches[f"bwd_{_other(path)}"] == 0
    return [np.asarray(a) for a in grads]


def _other(path):
    return PATHS[1 - PATHS.index(path)]


def _problem(seed, E=400, N=90, H=2, D=8, mask_frac=0.3):
    """Messages with masked edges and a run of empty destinations."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, N // 2, E).astype(np.int32)   # empty tail
    msg = {"value": jnp.asarray(rng.normal(size=(E, H, D)), jnp.float32),
           "logit": jnp.asarray(rng.normal(size=(E, H)) * 3, jnp.float32)}
    mask = jnp.asarray(rng.random(E) > mask_frac, jnp.float32)
    return msg, jnp.asarray(ids), ids, mask


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("mode", KERNEL_MODES)
@pytest.mark.parametrize("H,D", [(1, 8), (3, 16), (2, 80)])
def test_fused_kernel_gradient_parity(mode, H, D, path, monkeypatch):
    """csc grads == reference grads for multi-head messages, masked edges,
    empty segments and pad lanes (400 edges in 64-lane chunks), with the
    backward on ``path``, and the same bits on the other path; (2, 80)
    folds to lane width 160 > 64, exercising the d-tiled max kernel (both
    the max combine and softmax's max pass) and a packed softmax row
    wider than one 128-lane tile."""
    msg, dst, ids_np, mask = _problem(seed=11 + H + D, H=H, D=D)
    N = 90
    plan = build_csc_plan(ids_np, N, block_n=32, block_e=64)

    def loss(value, logit, backend, pln):
        out = combine(mode, {"value": value, "logit": logit}, dst, N, mask,
                      backend=backend, plan=pln)
        return jnp.sum(jnp.sin(out) * out)

    g_ref = jax.grad(loss, argnums=(0, 1))(msg["value"], msg["logit"],
                                           "reference", None)
    csc = lambda v, lg: loss(v, lg, "csc", plan)  # noqa: E731
    g_csc = _grads_on(path, monkeypatch, csc, msg["value"], msg["logit"])
    g_other = _grads_on(_other(path), monkeypatch, csc, msg["value"],
                        msg["logit"])
    for name, a, b, c in zip(("value", "logit"), g_ref, g_csc, g_other):
        np.testing.assert_allclose(b, np.asarray(a), rtol=1e-4, atol=1e-5,
                                   err_msg=f"{mode}/{name}")
        np.testing.assert_array_equal(b, c, err_msg=f"{mode}/{name}")


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("mode", KERNEL_MODES)
def test_fused_kernel_gradient_all_masked(mode, path, monkeypatch):
    """Gradients through a fully masked combine stay finite (no NaN from
    empty-segment softmax or NEG max identities) on either backward path,
    and agree to the bit."""
    msg, dst, ids_np, _ = _problem(seed=5, H=2, D=8)
    N = 90
    mask = jnp.zeros(ids_np.shape[0], jnp.float32)
    plan = build_csc_plan(ids_np, N, block_n=32, block_e=64)

    def loss(value, logit):
        out = combine(mode, {"value": value, "logit": logit}, dst, N, mask,
                      backend="csc", plan=plan)
        return jnp.sum(out * out)

    g = _grads_on(path, monkeypatch, loss, msg["value"], msg["logit"])
    g_other = _grads_on(_other(path), monkeypatch, loss, msg["value"],
                        msg["logit"])
    for arr, other in zip(g, g_other):
        assert np.all(np.isfinite(arr)), mode
        np.testing.assert_array_equal(arr, other, err_msg=mode)


# ---------------------------------------------------------------------------
# the fused-gather memory contract
# ---------------------------------------------------------------------------


def test_forward_jaxpr_has_no_pregather_tensor():
    """The tentpole claim: none of the kernel wrappers materializes the
    (nb, L_pad, D) pre-gathered message layout."""
    msg, dst, ids_np, mask = _problem(seed=3, H=2, D=8)
    N = 90
    plan = build_csc_plan(ids_np, N, block_n=32, block_e=64)
    flat = msg["value"].reshape(msg["value"].shape[0], -1)

    assert_pregather_free(
        jax.make_jaxpr(lambda d: segment_sum_op(d, plan))(flat), plan)
    assert_pregather_free(
        jax.make_jaxpr(lambda d: segment_max_op(d, plan))(flat), plan)
    assert_pregather_free(
        jax.make_jaxpr(lambda l, v: edge_softmax_op(l, v, plan))(
            msg["logit"], msg["value"]), plan)


def test_grad_jaxpr_has_no_pregather_tensor():
    """...and neither does the backward pass through the combine engine."""
    msg, dst, ids_np, mask = _problem(seed=4, H=2, D=8)
    N = 90
    plan = build_csc_plan(ids_np, N, block_n=32, block_e=64)

    for mode in KERNEL_MODES:
        def loss(value, logit):
            out = combine(mode, {"value": value, "logit": logit}, dst, N,
                          mask, backend="csc", plan=plan)
            return jnp.sum(out * out)

        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(
            msg["value"], msg["logit"])
        assert_pregather_free(jaxpr, plan)


@pytest.mark.parametrize("mode", KERNEL_MODES + ["mean"])
@pytest.mark.parametrize("H,D", [(2, 8), (2, 80)])
def test_value_and_grad_jaxpr_backward_contract(mode, H, D):
    """The full fused contract of the tentpole: the value_and_grad jaxpr
    of the csc combine path contains no (nb, L_pad, ...) float tensor, no
    reference segment scatter, and no g[segment_ids] backward gather —
    the backward runs through the Pallas kernels, not reference math.
    (2, 80) folds to lane width 160 > the d-tile caps of both the
    forward max and the backward gather kernels."""
    msg, dst, ids_np, mask = _problem(seed=13 + H + D, H=H, D=D)
    N = 90
    plan = build_csc_plan(ids_np, N, block_n=32, block_e=64)

    def loss(value, logit):
        out = combine(mode, {"value": value, "logit": logit}, dst, N,
                      mask, backend="csc", plan=plan)
        return jnp.sum(jnp.sin(out) * out)

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1)))(
        msg["value"], msg["logit"])
    assert_sum_stage_fused(jaxpr, plan)


def test_backward_contract_ignores_in_kernel_gathers():
    """Regression: when E == block_e the kernels' own on-chip block
    gathers have edge-sized outputs; the contract must skip pallas
    bodies rather than flag them as reference fallbacks."""
    rng = np.random.default_rng(17)
    E, N, H, D = 64, 32, 2, 8
    ids = rng.integers(0, N, E).astype(np.int32)
    dst = jnp.asarray(ids)
    msg = {"value": jnp.asarray(rng.normal(size=(E, H, D)), jnp.float32),
           "logit": jnp.asarray(rng.normal(size=(E, H)), jnp.float32)}
    mask = jnp.ones(E, jnp.float32)
    plan = build_csc_plan(ids, N, block_n=32, block_e=64)
    assert plan.num_edges == plan.block_e

    for mode in KERNEL_MODES:
        def loss(value, logit):
            out = combine(mode, {"value": value, "logit": logit}, dst, N,
                          mask, backend="csc", plan=plan)
            return jnp.sum(out * out)

        jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1)))(
            msg["value"], msg["logit"])
        assert_sum_stage_fused(jaxpr, plan)


def test_backward_contract_catches_reference_fallback():
    """assert_sum_stage_fused must flag the reference path (which runs
    segment scatters and, under grad, the g[segment_ids] gather)."""
    msg, dst, ids_np, mask = _problem(seed=14, H=2, D=8)
    N = 90
    plan = build_csc_plan(ids_np, N, block_n=32, block_e=64)

    def ref_loss(value, logit):
        out = combine("sum", {"value": value, "logit": logit}, dst, N,
                      mask, backend="reference")
        return jnp.sum(out * out)

    jaxpr = jax.make_jaxpr(jax.value_and_grad(ref_loss, argnums=(0,)))(
        msg["value"], msg["logit"])
    assert count_segment_scatters(jaxpr, plan) > 0
    with pytest.raises(AssertionError, match="reference"):
        assert_sum_stage_fused(jaxpr, plan)


@pytest.mark.parametrize("model_name,heads", [("gcn", 1), ("gat", 2)])
def test_model_value_and_grad_pregather_free_and_fewer_scatters(
        model_name, heads):
    """End-to-end train-step certificate: value_and_grad of the block
    loss on the csc path stays pre-gather-free, and its segment-scatter
    count sits strictly below the reference backend's (the only
    remaining edge-axis scatters are the NN-Gather transposes, which
    both backends share — the Sum-stage fallbacks are gone)."""
    import dataclasses

    from repro.config import GNNConfig
    from repro.core.mpgnn import loss_block
    from repro.core.strategies import global_batch_view
    from repro.graph import sbm_graph
    from repro.models import make_gnn

    g = sbm_graph(num_nodes=150, num_classes=3, feature_dim=8,
                  p_in=0.06, p_out=0.02, seed=3).add_self_loops()
    cfg = GNNConfig(model=model_name, num_layers=2, hidden_dim=8,
                    num_classes=3, feature_dim=8, num_heads=heads)
    model_ref = make_gnn(cfg)
    model_csc = dataclasses.replace(model_ref, aggregate_backend="csc")
    params = model_ref.init(jax.random.PRNGKey(0), 8)
    view = global_batch_view(g, 2)
    gcn_norm = model_name == "gcn"
    block_csc = view.as_block(gcn_norm=gcn_norm, csc_plan=True)
    block_ref = view.as_block(gcn_norm=gcn_norm)
    plan = block_csc.csc_plan

    jaxpr_csc = jax.make_jaxpr(jax.value_and_grad(
        lambda p: loss_block(model_csc, p, block_csc)))(params)
    jaxpr_ref = jax.make_jaxpr(jax.value_and_grad(
        lambda p: loss_block(model_ref, p, block_ref)))(params)
    assert_pregather_free(jaxpr_csc, plan)
    n_csc = count_segment_scatters(jaxpr_csc, plan)
    n_ref = count_segment_scatters(jaxpr_ref, plan)
    assert n_csc < n_ref, (n_csc, n_ref)


def test_assert_pregather_free_catches_materialization():
    """The assertion helper itself must flag a pre-gathered layout."""
    msg, dst, ids_np, mask = _problem(seed=6, H=1, D=8)
    plan = build_csc_plan(ids_np, 90, block_n=32, block_e=64)
    flat = msg["value"].reshape(msg["value"].shape[0], -1)

    def pregather(d):
        return jnp.concatenate([d, jnp.zeros((1, 8), d.dtype)])[
            jnp.asarray(plan.gather_idx)]

    with pytest.raises(AssertionError, match="pre-gather"):
        assert_pregather_free(jax.make_jaxpr(pregather)(flat), plan)

    # the 2-D *float* layout (the old edge-softmax gathered logits) must
    # be flagged too, while the int32 plan arrays themselves are allowed
    def pregather_logits(l):
        return jnp.concatenate([l, jnp.full((1,), -1.0, l.dtype)])[
            jnp.asarray(plan.gather_idx)]

    logits = flat[:, 0]
    with pytest.raises(AssertionError, match="pre-gather"):
        assert_pregather_free(jax.make_jaxpr(pregather_logits)(logits),
                              plan)


# ---------------------------------------------------------------------------
# mini-batch strategy guard
# ---------------------------------------------------------------------------


def test_mini_batch_views_empty_labeled_set_raises():
    """A graph whose train_mask selects nothing must fail loudly instead
    of yielding empty (zero-target) views forever."""
    from repro.core.strategies import mini_batch_views
    from repro.graph import sbm_graph

    g = sbm_graph(num_nodes=40, num_classes=2, feature_dim=4,
                  p_in=0.1, p_out=0.02, seed=0)
    g.train_mask = np.zeros(g.num_nodes, bool)
    with pytest.raises(ValueError, match="no labeled"):
        next(mini_batch_views(g, 2, batch_nodes=4))
