"""Plain float32 jax.numpy GAT-E (the paper's edge-attributed attention,
section 5.2.2), written from its equations. Per layer, with H heads of
width D:

    n_v = h_v W                       (H, D) per node
    logit_uv = LeakyReLU_0.2(a_src . n_u + a_dst . n_v + x_uv W_ea)
    value_uv = n_u + x_uv W_ev
    h'_v = softmax_u(logit_uv) weighted sum of value_uv, + b
           then ELU on all but the last layer

and a dense decoder. ``layers`` lists, per layer, the edges it
aggregates over and how many nodes (a prefix of the node axis) it
computes; rows past that prefix are zero, as the sampled view defines.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.common import einsum, mm, segment_softmax


def init(key, model: dict, feature_dim: int):
    """Seeded weights in the program's parameter layout (fan-in normal,
    zero biases)."""
    H, hid, Fe = model["num_heads"], model["hidden_dim"], model["edge_feature_dim"]
    D = hid // H
    dims = [feature_dim] + [hid] * model["num_layers"]
    keys = jax.random.split(key, model["num_layers"] + 1)

    def normal(k, shape):
        return jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(
            float(shape[0]))

    layers = []
    for i in range(model["num_layers"]):
        ks = jax.random.split(keys[i], 5)
        layers.append({"w": normal(ks[0], (dims[i], H * D)),
                       "a_src": normal(ks[1], (H, D)),
                       "a_dst": normal(ks[2], (H, D)),
                       "w_e_att": normal(ks[3], (Fe, H)),
                       "w_e_val": normal(ks[4], (Fe, H * D)),
                       "b": jnp.zeros((H * D,), jnp.float32)})
    dec = {"w": normal(keys[-1], (hid, model["num_classes"])),
           "b": jnp.zeros((model["num_classes"],), jnp.float32)}
    return {"layers": layers, "decoder": dec}


def layer(p, h, edge_x, src, dst, n_out, last, precision):
    n = h.shape[0]
    H, D = p["a_src"].shape
    nv = mm(h, p["w"], precision).reshape(n, H, D)
    a_s = einsum("nhd,hd->nh", nv, p["a_src"], precision)
    a_d = einsum("nhd,hd->nh", nv, p["a_dst"], precision)
    e_att = mm(edge_x, p["w_e_att"], precision)
    e_val = mm(edge_x, p["w_e_val"], precision).reshape(-1, H, D)
    logit = jax.nn.leaky_relu(a_s[src] + a_d[dst] + e_att, 0.2)
    agg = segment_softmax(logit, nv[src] + e_val, dst, n)
    out = agg.reshape(n, H * D) + p["b"]
    if not last:
        out = jax.nn.elu(out)
    return jnp.where(jnp.arange(n)[:, None] < n_out, out, 0.0)


def logits(params, x, edge_x, layers, n_targets, precision):
    """Decoder outputs of the first ``n_targets`` nodes. ``layers`` holds
    per layer a dict ``src``, ``dst`` (node ids), ``eid`` (rows of
    ``edge_x``) and ``n_out``."""
    h = x
    K = len(layers)
    for k, ly in enumerate(layers):
        h = layer(params["layers"][k], h, edge_x[ly["eid"]], ly["src"],
                  ly["dst"], ly["n_out"], k == K - 1, precision)
    dec = params["decoder"]
    return mm(h[:n_targets], dec["w"], precision) + dec["b"]
