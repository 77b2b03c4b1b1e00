"""Pallas TPU kernel: fused edge-softmax aggregation (GAT/GAT-E Sum stage).

Computes, per destination node i:  out_i = Σ_j softmax_j(logit_{j→i}) v_{j→i}
— the attention-weighted neighbor aggregation that dominates GAT layers.
Unfused, this is 3 segment passes (max, exp-sum, weighted sum) with HBM
round-trips between them; the kernel fuses them in one launch, reading
the destination's edges twice: (max m, denom l, accumulator acc) per
destination row live in VMEM scratch across the block's edge chunks.

Same CSC-blocked layout as segment_sum.py: destinations tiled into BN-row
blocks, each owning a contiguous padded edge slice (built once per graph by
ops.build_csc_plan — the paper's reused CSC indexing). Like the sum/max
kernels, the per-edge gather is **fused**: the raw ``(E, H)`` logits and
``(E, H·D)`` values stay in HBM and each chunk's rows are copied into VMEM
scratch by per-row DMAs driven by the plan indices in SMEM — no
pre-gathered ``(nb, L_pad, ·)`` tensors. The logits are repeated over
their head's ``D`` value lanes, so all heads share one ``(1, H·D)`` row
per edge and multi-head attention is **one** kernel launch with no
per-head code. The grid is ``(nb, 2, n_chunks)``: a destination block
first folds its exact per-destination max over all its edge chunks
(phase 0), then its denominator and weighted sum against that max
(phase 1). Like the sum kernel, each destination folds its edges in plan
order, so its result does not depend on how edges fall into chunks or
blocks — a node scored in a small serving view gets the same bits as in
the full graph. Reached from the forward paths through the ``"csc"``
backend of :mod:`repro.core.aggregate` (GAT/GAT-E ``softmax`` combine on
a single shard).

The launch also emits the per-destination softmax stats (running max
``m`` and denominator ``l``) as two node-proportional outputs: the
recompute-in-kernel backward (backward.py) rebuilds the edge
probabilities from them instead of re-running reference segment passes,
so no ``(E, H)`` probability tensor ever exists in HBM in either
direction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.segment_sum import (NEG, _check_plan, chunk_view,
                                       fold_rows, gather_rows, row_view)


def _edge_softmax_kernel(idx_hbm, ids_hbm, logit_hbm, val_hbm, out_ref,
                         mstat_ref, lstat_ref, idx_smem, ids_smem, lbuf,
                         vbuf, sem, m_ref, l_ref, acc_ref, *, block_n: int,
                         n_chunks: int, num_edges: int):
    """One (node_block, phase, edge_chunk) grid step. Phase 0 folds each
    destination's running max; phase 1 its denominator and weighted sum
    against that max. Logits arrive repeated over each head's value
    lanes, so every fold is elementwise over one (1, Wp) row."""
    b, ph, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    block_e = vbuf.shape[0]

    @pl.when((ph == 0) & (c == 0))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    chunk = b * n_chunks + c
    pltpu.sync_copy(idx_hbm.at[chunk], idx_smem)
    pltpu.sync_copy(ids_hbm.at[chunk], ids_smem)

    def live(i):
        return ids_smem[0, i] < block_n

    width = lbuf.shape[1]
    gather_rows(idx_smem, logit_hbm, lbuf, sem, num_edges, 0, width, live)

    @pl.when(ph == 0)
    def _max():
        def fold(i, r):
            m_ref[pl.ds(r, 1), :] = jnp.maximum(m_ref[pl.ds(r, 1), :],
                                                lbuf[pl.ds(i, 1), :])
        fold_rows(ids_smem, block_e, block_n, fold)

    @pl.when(ph == 1)
    def _sum():
        gather_rows(idx_smem, val_hbm, vbuf, sem, num_edges, 0, width, live)

        def fold(i, r):
            logit = lbuf[pl.ds(i, 1), :]
            # masked edges (logit == NEG) weigh exactly 0, as in the
            # reference segment softmax
            ex = jnp.where(logit > NEG / 2,
                           jnp.exp(logit - m_ref[pl.ds(r, 1), :]), 0.0)
            l_ref[pl.ds(r, 1), :] += ex
            acc_ref[pl.ds(r, 1), :] += ex * vbuf[pl.ds(i, 1), :]
        fold_rows(ids_smem, block_e, block_n, fold)

    @pl.when((ph == 1) & (c == n_chunks - 1))
    def _finish():
        out_ref[...] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)
        # the per-destination softmax stats (max, denominator) ride out
        # of the launch: the recompute-in-kernel backward (backward.py)
        # rebuilds p_e from them instead of re-running reference segment
        # passes — two node-proportional extra outputs
        mstat_ref[...] = m_ref[...]
        lstat_ref[...] = l_ref[...]


def edge_softmax_csc(logits, values, gather_idx, local_ids,
                     num_blocks: int, block_n: int, block_e: int = 256,
                     interpret: bool = False):
    """Fused-gather multi-head edge softmax.

    logits (E, H), values (E, H, D), gather_idx/local_ids (nb, L_pad)
    -> (out (nb*block_n, H, D), m (nb*block_n, H), l (nb*block_n, H)):
    the aggregation plus the per-destination softmax stats (max and
    denominator) the fused backward rebuilds p_e from; one launch, all
    heads per grid step. Outputs are float32.
    """
    e, h = logits.shape
    d = values.shape[-1]
    nc = _check_plan(gather_idx, num_blocks, block_e)
    if values.shape != (e, h, d):
        raise ValueError(f"values {values.shape} do not match logits "
                         f"{logits.shape}: expected ({e}, {h}, {d})")
    n_rows = num_blocks * block_n
    if e == 0:
        return (jnp.zeros((n_rows, h, d), jnp.float32),
                jnp.full((n_rows, h), NEG, jnp.float32),
                jnp.zeros((n_rows, h), jnp.float32))
    # each head's logit repeated over its D value lanes: (E, 1, Wp)
    lg = row_view(jnp.repeat(logits.astype(jnp.float32), d, axis=1))
    vals = row_view(values.reshape(e, h * d))
    wp = vals.shape[-1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(num_blocks, 2, nc),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 4,
        out_specs=[pl.BlockSpec((block_n, wp), lambda b, ph, c: (b, 0))] * 3,
        scratch_shapes=[
            pltpu.SMEM((1, block_e), jnp.int32),
            pltpu.SMEM((1, block_e), jnp.int32),
            pltpu.VMEM((block_e, wp), jnp.float32),
            pltpu.VMEM((block_e, wp), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
            pltpu.VMEM((block_n, wp), jnp.float32),
            pltpu.VMEM((block_n, wp), jnp.float32),
            pltpu.VMEM((block_n, wp), jnp.float32),
        ],
    )
    out, m, den = pl.pallas_call(
        functools.partial(_edge_softmax_kernel, block_n=block_n,
                          n_chunks=nc, num_edges=e),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_rows, wp), jnp.float32)] * 3,
        interpret=interpret,
    )(chunk_view(gather_idx, block_e), chunk_view(local_ids, block_e), lg,
      vals)

    def per_head(x):          # one lane per head: its value lanes agree
        return x[:, :h * d].reshape(n_rows, h, d)

    return per_head(out), per_head(m)[:, :, 0], per_head(den)[:, :, 0]
