"""MPGNN (paper Algorithm 1): K passes of Proj/Prop/Agg + decoder + loss.

``MPGNNModel`` composes TGAR layers with a decoder (an NN-T stage) and the
loss (another NN-T stage) — matching the paper's "forward = K+2 passes of
NN-TGA" description (§3.2). The same model object runs on a single
GraphBlock (this module) or distributed via the hybrid-parallel engine
(:mod:`repro.core.engine`) — the paper's "training and inference via a
unified implementation".
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.tgar import TGARLayer, layer_forward_block
from repro.nn.layers import dense_init, dense_apply, softmax_cross_entropy


@dataclass(frozen=True)
class MPGNNModel:
    layers: Sequence[TGARLayer]
    num_classes: int
    decoder_hidden: int = 0          # optional extra FC before the decoder
    # Sum-stage aggregation backend ("reference" | "csc", see
    # repro.core.aggregate; None = the platform's default). The "csc"
    # kernel path needs a CSCPlan on the block (build_block(csc_plan=
    # True)) or engine shard
    aggregate_backend: Optional[str] = None

    @property
    def K(self):
        return len(self.layers)

    def init(self, key, feature_dim: int):
        keys = jax.random.split(key, self.K + 2)
        params = {"layers": [ly.init(k) for ly, k in zip(self.layers, keys)]}
        last = self.layers[-1].out_dim
        if self.decoder_hidden:
            params["dec_fc"] = dense_init(keys[-2], last, self.decoder_hidden)
            last = self.decoder_hidden
        params["decoder"] = dense_init(keys[-1], last, self.num_classes)
        return params

    def encode(self, params, block):
        """K passes of NN-TGA over the block; returns final embeddings."""
        h = block.x
        n = block.num_nodes_padded
        for k, layer in enumerate(self.layers):
            h = layer_forward_block(layer, params["layers"][k], h, block, k,
                                    n, backend=self.aggregate_backend)
        return h

    def decode(self, params, h):
        """Decoder = a single NN-T (node-local) stage (§3.2)."""
        if self.decoder_hidden:
            h = jax.nn.relu(dense_apply(params["dec_fc"], h))
        return dense_apply(params["decoder"], h)


def forward_block(model: MPGNNModel, params, block):
    h = model.encode(params, block)
    return model.decode(params, h)


def loss_block(model: MPGNNModel, params, block):
    """Loss = a single NN-T stage over labeled (loss-masked) nodes."""
    logits = forward_block(model, params, block)
    return softmax_cross_entropy(logits, block.y, block.loss_mask)


def accuracy_block(model: MPGNNModel, params, block, mask=None):
    logits = forward_block(model, params, block)
    pred = jnp.argmax(logits, axis=-1)
    m = (mask if mask is not None else block.loss_mask).astype(jnp.float32)
    correct = (pred == block.y).astype(jnp.float32) * m
    return jnp.sum(correct) / jnp.maximum(jnp.sum(m), 1.0)
