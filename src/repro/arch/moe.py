"""Mixture-of-Experts FFN: router + two dispatch implementations.

``moe_impl="dense"`` (baseline) — every expert runs on every token, outputs
weighted by the (renormalized) top-k router gates. Mathematically identical
to sparse dispatch but burns num_experts/top_k× the FLOPs: this is the
"no clever routing" floor whose waste the roofline's MODEL_FLOPS/HLO ratio
exposes, and the starting point of the MoE hillclimb.

``moe_impl="ep"`` (optimized) — GraphTheta-style expert parallelism: token→
expert routing is a bipartite message-pass; like the paper's master/mirror
sync we move **only routed tokens** via ``all_to_all`` inside ``shard_map``
over the 'model' axis (DESIGN.md §4). Experts are sharded over that axis
(padded with dead experts when num_experts < axis size); capacity-bounded
buffers keep shapes static. Equivalent to dense dispatch whenever no
expert overflows its capacity (tested).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.nn.layers import _fan_in_init


def moe_init(key, d_model, d_ff, num_experts, dtype):
    ks = jax.random.split(key, 4)
    return {
        "router": _fan_in_init(ks[0], (d_model, num_experts), jnp.float32),
        "wi_gate": _fan_in_init(ks[1], (num_experts, d_model, d_ff), dtype),
        "wi_up": _fan_in_init(ks[2], (num_experts, d_model, d_ff), dtype),
        "wo": _fan_in_init(ks[3], (num_experts, d_ff, d_model), dtype),
    }


def router_gates(p, x, moe_cfg):
    """Renormalized top-k gates (B,S,E) + Switch-style load-balance aux."""
    logits = x.astype(jnp.float32) @ p["router"]          # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, moe_cfg.top_k)
    onehot = jax.nn.one_hot(topi, probs.shape[-1], dtype=probs.dtype)
    mask = jnp.sum(onehot, axis=-2)                        # (B,S,E) 0/1
    gated = probs * mask
    gated = gated / jnp.maximum(gated.sum(-1, keepdims=True), 1e-9)
    frac = jnp.mean(mask, axis=(0, 1))                     # routed fraction
    prob = jnp.mean(probs, axis=(0, 1))
    aux = probs.shape[-1] * jnp.sum(frac * prob)
    return gated, aux


def moe_ffn_dense(p, x, moe_cfg):
    """Baseline: all experts on all tokens, gate-weighted combine."""
    gates, aux = router_gates(p, x, moe_cfg)               # (B,S,E)
    h_g = jnp.einsum("bsd,edf->ebsf", x, p["wi_gate"])
    h_u = jnp.einsum("bsd,edf->ebsf", x, p["wi_up"])
    h = jax.nn.silu(h_g) * h_u
    y = jnp.einsum("ebsf,efd->ebsd", h, p["wo"])
    out = jnp.einsum("ebsd,bse->bsd", y, gates.astype(y.dtype))
    return out.astype(x.dtype), aux


def moe_ffn_ep(p, x, moe_cfg, mesh, axis: str = "model", dp_axis=None):
    """Expert-parallel dispatch via shard_map over ``axis``.

    x: (B, S, D) — B sharded over ``dp_axis`` (if given), S over ``axis``.
    Routed tokens move twice over the expert axis (dispatch + return), the
    only communication — the master/mirror rule applied to the bipartite
    token→expert graph.
    """
    from jax.sharding import PartitionSpec as P

    n_dev = mesh.shape[axis]
    E = moe_cfg.num_experts
    E_pad = max(E, n_dev)
    if E_pad % n_dev != 0:
        raise ValueError(f"expert count {E} must pad to a multiple of "
                         f"the device count {n_dev}")
    per_dev = E_pad // n_dev

    gates, aux = router_gates(p, x, moe_cfg)               # global (B,S,E)

    def local(x_l, gates_l, wi_g, wi_u, wo):
        b, s, d = x_l.shape
        T = b * s
        xt = x_l.reshape(T, d)
        g = gates_l.reshape(T, E)
        cap = max(1, int(np.ceil(T * moe_cfg.top_k / E
                                 * moe_cfg.capacity_factor)))
        sel = g > 0                                        # (T, E)
        pos = jnp.cumsum(sel.astype(jnp.int32), axis=0) - 1
        keep = sel & (pos < cap)
        flat_keep = keep.reshape(-1)
        tok_idx = jnp.broadcast_to(jnp.arange(T)[:, None], (T, E)).reshape(-1)
        be = jnp.where(flat_keep,
                       jnp.broadcast_to(jnp.arange(E)[None, :],
                                        (T, E)).reshape(-1), E_pad - 1)
        bp = jnp.where(flat_keep, pos.reshape(-1), cap - 1)
        contrib = jnp.where(flat_keep[:, None], xt[tok_idx], 0)
        buf = jnp.zeros((E_pad, cap, d), x_l.dtype)
        buf = buf.at[be, bp].add(contrib, mode="drop")

        # ---- dispatch: send expert-slices to their owners -------------------
        buf = buf.reshape(n_dev, per_dev, cap, d)
        buf = jax.lax.all_to_all(buf, axis, 0, 0)          # rows by sender
        buf = jnp.moveaxis(buf, 0, 1)                      # (per_dev, n_dev, cap, d)
        buf = buf.reshape(per_dev, n_dev * cap, d)

        # ---- local experts ---------------------------------------------------
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wi_g)) \
            * jnp.einsum("ecd,edf->ecf", buf, wi_u)
        y = jnp.einsum("ecf,efd->ecd", h, wo)

        # ---- return: back to the senders ------------------------------------
        y = y.reshape(per_dev, n_dev, cap, d)
        y = jnp.moveaxis(y, 1, 0)                          # (n_dev, per_dev, cap, d)
        y = jax.lax.all_to_all(y, axis, 0, 0)
        y = y.reshape(E_pad, cap, d)

        picked = jnp.where(flat_keep[:, None], y[be, bp], 0)
        w = (g.reshape(-1) * flat_keep).astype(picked.dtype)
        out = jnp.zeros((T, d), picked.dtype)
        out = out.at[tok_idx].add(picked * w[:, None], mode="drop")
        return out.reshape(b, s, d)

    wi_g, wi_u, wo = p["wi_gate"], p["wi_up"], p["wo"]
    if E_pad != E:
        padn = E_pad - E
        zp = lambda a: jnp.concatenate(
            [a, jnp.zeros((padn,) + a.shape[1:], a.dtype)], axis=0)
        wi_g, wi_u, wo = zp(wi_g), zp(wi_u), zp(wo)

    x_spec = P(dp_axis, axis, None)
    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(x_spec, x_spec, P(axis), P(axis), P(axis)),
        out_specs=x_spec, check_vma=False,
    )(x, gates.astype(x.dtype), wi_g, wi_u, wo)
    return out.astype(x.dtype), aux
