"""Explicit NN-TGAR backward schedule (paper §3.3, App. A.2/A.3).

GraphTheta implements auto-differentiation by pairing every stage with a
backward version and executing K+2 reverse passes of NN-TGAR: the gradient
of a node flows to its in-neighbors along reversed edges ("if a node
aggregates information from its neighbor along every out-edge in the
forward, it aggregates gradient along every in-edge in the backward").

This module materializes that schedule explicitly — stage-by-stage VJPs
orchestrated in the paper's order — instead of letting ``jax.grad`` trace
the whole model. Tests assert it produces bit-comparable gradients to
``jax.grad``, which is the reproduction of the paper's App. A.2 equivalence
proof. (The production engine uses ``jax.grad``; this is the reference
semantics.)
"""
from __future__ import annotations

from typing import Any, List

import jax
import jax.numpy as jnp

from repro.core.mpgnn import MPGNNModel
from repro.core.tgar import combine_messages, tree_take


def _stage_masks(block, k):
    em = block.edge_mask
    if block.edge_active is not None:
        em = em * block.edge_active[k]
    na = None
    if block.node_active is not None:
        na = block.node_active[k]
    return em, na


def explicit_loss_and_grad(model: MPGNNModel, params, block):
    """Forward (storing stage residuals) + explicit reverse schedule.

    Returns (loss, grads) with grads matching ``jax.grad(loss_block)``.
    """
    n_pad = block.num_nodes_padded

    # ---------------- forward: K passes of NN-TGA, keep stage closures ------
    h = block.x
    residuals: List[dict] = []
    for k, layer in enumerate(model.layers):
        lp = params["layers"][k]
        em, na = _stage_masks(block, k)

        t_fn = lambda p_, h_, layer_=layer: layer_.transform(p_, h_)
        n, t_vjp = jax.vjp(t_fn, lp, h)

        def g_fn(p_, n_, layer_=layer, em_=em):
            n_src = tree_take(n_, block.src)
            n_dst = tree_take(n_, block.dst)
            return layer_.gather(p_, n_src, n_dst, block.edge_attr,
                                 block.edge_weight, em_)
        msg, g_vjp = jax.vjp(g_fn, lp, n)

        def s_fn(msg_, layer_=layer, em_=em):
            return combine_messages(layer_, msg_, block.dst, n_pad, em_,
                                    backend=model.aggregate_backend,
                                    plan=getattr(block, "csc_plan", None))
        M, s_vjp = jax.vjp(s_fn, msg)

        def a_fn(p_, h_, M_, layer_=layer, na_=na):
            out = layer_.apply(p_, h_, M_)
            if na_ is not None:
                out = out * na_[:, None]
            return out * block.node_mask[:, None]
        h_next, a_vjp = jax.vjp(a_fn, lp, h, M)

        residuals.append({"t_vjp": t_vjp, "g_vjp": g_vjp, "s_vjp": s_vjp,
                          "a_vjp": a_vjp})
        h = h_next

    # ---------------- decoder + loss: two NN-T stages ------------------------
    def dec_fn(p_, h_):
        return model.decode({"decoder": p_["decoder"],
                             **({"dec_fc": p_["dec_fc"]}
                                if "dec_fc" in p_ else {})}, h_)
    dec_params = {k_: v for k_, v in params.items() if k_ != "layers"}
    logits, dec_vjp = jax.vjp(dec_fn, dec_params, h)

    def loss_fn(logits_):
        lg = logits_.astype(jnp.float32)
        logz = jax.nn.logsumexp(lg, axis=-1)
        ll = jnp.take_along_axis(lg, block.y[:, None], axis=-1)[:, 0]
        lm = block.loss_mask
        return jnp.sum((logz - ll) * lm) / jnp.maximum(jnp.sum(lm), 1.0)
    loss, l_vjp = jax.vjp(loss_fn, logits)

    # ---------------- backward: reverse schedule ------------------------------
    # loss NN-T backward
    (d_logits,) = l_vjp(jnp.ones((), jnp.float32))
    # decoder NN-T backward (+ its parameter grads -> NN-Reduce)
    d_dec_params, d_h = dec_vjp(d_logits)

    layer_grads: List[Any] = [None] * model.K
    for k in range(model.K - 1, -1, -1):
        r = residuals[k]
        # NN-T stage of the backward pass = derivative of Apy_k (Fig. 3b)
        d_lp_a, d_h_in_a, d_M = r["a_vjp"](d_h)
        # NN-G stage = derivative of Acc_k & Prop_k: gradient flows along
        # reversed edges to source/destination nodes
        (d_msg,) = r["s_vjp"](d_M)
        d_lp_g, d_n = r["g_vjp"](d_msg)
        # NN-A stage = derivative of Proj_k, back to node embeddings
        d_lp_t, d_h_prev = r["t_vjp"](d_n)
        # NN-Reduce: parameter gradients aggregated across stages
        layer_grads[k] = jax.tree_util.tree_map(
            lambda a, b, c: a + b + c, d_lp_a, d_lp_g, d_lp_t)
        d_h = jax.tree_util.tree_map(jnp.add, d_h_in_a, d_h_prev)

    grads = dict(d_dec_params)
    grads["layers"] = layer_grads
    return loss, grads
