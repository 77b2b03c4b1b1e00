"""Pallas TPU kernels: fused Sum-stage backward passes.

The forward CSC kernels (segment_sum.py / edge_softmax.py) aggregate raw
``(E, ...)`` edge messages into per-destination rows with the per-edge
gather fused on-chip. Their cotangents flow the other way — every edge
needs a value read from its destination row — and until this module the
``custom_vjp`` backwards were reference math: ``g[segment_ids]`` jnp
gathers plus a full ``jax.ops.segment_*`` softmax recompute, i.e. under
``jax.grad`` roughly two thirds of a train step's memory traffic bypassed
the planned layout entirely (the "message bombing" the forward
eliminated). These kernels close that gap: the whole train step stays
pre-gather-free (see ``ops.assert_sum_stage_fused``).

Layout
------
Backward is a *scatter-free* pass when organized over the **edge axis**:
``d_data[e] = f(g[dst[e]])`` touches each output row exactly once. The
grid therefore tiles the (padded) edge axis in ``block_e`` chunks; the
node-indexed arrays (cotangent ``g``, saved forward output, softmax
stats) stay in HBM and each edge's row is copied into VMEM by a row DMA
(:func:`~repro.kernels.segment_sum.gather_rows`), addressed by the plan's
**inverse map** ``edge_dst`` — built host-side in ``build_csc_plan`` by
inverting ``gather_idx``/``local_ids`` (live lane ``(c, l)`` holds edge
``gather_idx[c, l]`` destined for row ``local_ids[c, l]``).
Each chunk of ``edge_dst`` is copied to SMEM for the DMA addresses and
also read as a ``(BE, 1)`` column: lanes whose destination is outside
the plan (``num_segments``: pad lanes, and edges no block aggregates)
get a zero cotangent, as the reference segment ops' transpose gives
them. The outputs are allocated at the true edge count, so the final
partial block is an ordinary masked boundary block.

Three kernels:

- :func:`segment_sum_bwd_csc` — the linear backward, a pure plan-driven
  gather: ``d_data[e] = g[dst[e]]``; d-tiled.
- :func:`segment_max_bwd_csc` — the same gather plus an in-kernel
  argmax-hit mask against the saved forward output (ties share the
  cotangent, matching ``jax.ops.segment_max``).
- :func:`edge_softmax_bwd_csc` — recompute-in-kernel: rebuilds the edge
  probability ``p_e = exp(logit_e - m_i) / den_i`` inside each edge block
  from the saved logits and the forward kernel's per-destination softmax
  stats (``m``/``den`` ride out of the fused forward launch as two tiny
  node-proportional outputs). No ``(E, H)`` probability tensor is ever
  materialized in HBM and no reference ``segment_max``/``segment_sum``
  recompute runs; ``d_logits`` and ``d_values`` come out of **one**
  launch, all heads per grid step, mirroring the forward.

VMEM geometry is tabulated with the forward budget in segment_sum.py:
per grid step a ``(BE, BD)`` gather scratch per gathered operand plus the
``(BE, BD)`` edge tiles — nothing that scales with N or E.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.segment_sum import (NEG, _pick_block_d, chunk_view,
                                       gather_rows, lane_pad, row_view)


def lane(x, k: int):
    """Column ``k`` of a 2-D value as a (rows, 1) column (exact select)."""
    hit = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) == k
    return jnp.sum(jnp.where(hit, x, 0.0), axis=1, keepdims=True)


def _check_edge_dst(edge_dst, block_e: int, num_edges: int) -> int:
    e_pad = edge_dst.shape[0]
    if e_pad % block_e != 0 or e_pad < num_edges:
        raise ValueError(
            f"edge_dst pad {e_pad} must be a block_e={block_e} multiple "
            f"covering num_edges={num_edges}")
    return e_pad


def _edge_specs(block_e: int, col_index):
    """The (SMEM-bound) edge_dst chunks in HBM and its (BE, 1) column."""
    return [pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((block_e, 1), col_index)]


def _edge_args(edge_dst, block_e: int):
    return chunk_view(edge_dst, block_e), edge_dst.reshape(-1, 1)


def _live(dst_smem, num_segments: int):
    """Lanes with a destination row (pad lanes hold num_segments)."""
    return lambda i: dst_smem[0, i] < num_segments


# ---------------------------------------------------------------------------
# segment-sum backward: plan-driven per-edge gather
# ---------------------------------------------------------------------------


def _gather_bwd_kernel(dst_hbm, dst_col, g_hbm, out_ref, dst_smem, gbuf, sem,
                       *, num_segments: int, block_d: int):
    """One (d_tile, edge_chunk) grid step of ``d_data[e] = g[dst[e]]``.

    dst_hbm: (E_pad/BE, 1, BE) int32 chunks of the plan's inverse map.
    dst_col: (BE, 1) int32 — the same chunk as a column (validity).
    g_hbm:   (N, 1, Dp) f32 cotangent rows in HBM.
    out_ref: (BE, BD) f32 edge tile of the message cotangent.
    """
    dt, c = pl.program_id(0), pl.program_id(1)
    pltpu.sync_copy(dst_hbm.at[c], dst_smem)
    gather_rows(dst_smem, g_hbm, gbuf, sem, num_segments, dt * block_d,
                block_d, _live(dst_smem, num_segments))
    out_ref[...] = jnp.where(dst_col[...] < num_segments, gbuf[...], 0.0)


def segment_sum_bwd_csc(g: jax.Array, edge_dst: jax.Array, num_edges: int,
                        block_e: int = 256, block_d: int = 0,
                        interpret: bool = False):
    """Backward of the fused segment-sum: gather the output cotangent onto
    the edge axis through the plan's inverse map.

    g:        (N, D) cotangent of the (sliced) kernel output.
    edge_dst: (E_pad,) int32, E_pad % block_e == 0; lane e holds dst[e],
              pad lanes hold N.
    returns   (num_edges, D) float32.
    """
    n, d = g.shape
    e_pad = _check_edge_dst(edge_dst, block_e, num_edges)
    if num_edges == 0:
        return jnp.zeros((0, d), jnp.float32)
    gv = row_view(g)
    dp = gv.shape[-1]
    bd = block_d or _pick_block_d(dp)
    if dp % bd != 0:
        raise ValueError(f"feature dim {dp} not divisible by block_d={bd}")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(dp // bd, e_pad // block_e),
        in_specs=_edge_specs(block_e, lambda dt, c: (c, 0))
        + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((block_e, bd), lambda dt, c: (c, dt)),
        scratch_shapes=[pltpu.SMEM((1, block_e), jnp.int32),
                        pltpu.VMEM((block_e, bd), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
    )
    out = pl.pallas_call(
        functools.partial(_gather_bwd_kernel, num_segments=n, block_d=bd),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_edges, dp), jnp.float32),
        interpret=interpret,
    )(*_edge_args(edge_dst, block_e), gv)
    return out[:, :d]


# ---------------------------------------------------------------------------
# segment-max backward: the gather + an in-kernel argmax-hit mask
# ---------------------------------------------------------------------------


def _gather_max_bwd_kernel(dst_hbm, dst_col, g_hbm, fwd_hbm, data_ref,
                           out_ref, dst_smem, gbuf, fbuf, sem, *,
                           num_segments: int, block_d: int):
    """Gather backward masked by ``data == forward_max`` (subgradient:
    ties share the cotangent, matching ``jax.ops.segment_max``)."""
    dt, c = pl.program_id(0), pl.program_id(1)
    pltpu.sync_copy(dst_hbm.at[c], dst_smem)
    live = _live(dst_smem, num_segments)
    gather_rows(dst_smem, g_hbm, gbuf, sem, num_segments, dt * block_d,
                block_d, live)
    gather_rows(dst_smem, fwd_hbm, fbuf, sem, num_segments, dt * block_d,
                block_d, live)
    hit = (dst_col[...] < num_segments) & (data_ref[...] == fbuf[...])
    out_ref[...] = jnp.where(hit, gbuf[...], 0.0)


def segment_max_bwd_csc(g: jax.Array, fwd_out: jax.Array, data: jax.Array,
                        edge_dst: jax.Array, num_edges: int,
                        block_e: int = 256, block_d: int = 0,
                        interpret: bool = False):
    """Backward of the fused segment-max.

    g / fwd_out: (N, D) cotangent and saved forward output.
    data:        (E, D) the forward's edge operand (for the hit mask).
    returns      (num_edges, D) float32.
    """
    n, d = g.shape
    if fwd_out.shape != (n, d) or data.shape != (num_edges, d):
        raise ValueError(
            f"fwd_out {fwd_out.shape} / data {data.shape} do not match "
            f"the expected ({n}, {d}) / ({num_edges}, {d})")
    e_pad = _check_edge_dst(edge_dst, block_e, num_edges)
    if num_edges == 0:
        return jnp.zeros((0, d), jnp.float32)
    gv, fv, dv = row_view(g), row_view(fwd_out), lane_pad(data)
    dp = gv.shape[-1]
    bd = block_d or _pick_block_d(dp)
    if dp % bd != 0:
        raise ValueError(f"feature dim {dp} not divisible by block_d={bd}")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(dp // bd, e_pad // block_e),
        in_specs=_edge_specs(block_e, lambda dt, c: (c, 0)) + [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((block_e, bd), lambda dt, c: (c, dt)),
        ],
        out_specs=pl.BlockSpec((block_e, bd), lambda dt, c: (c, dt)),
        scratch_shapes=[pltpu.SMEM((1, block_e), jnp.int32),
                        pltpu.VMEM((block_e, bd), jnp.float32),
                        pltpu.VMEM((block_e, bd), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
    )
    out = pl.pallas_call(
        functools.partial(_gather_max_bwd_kernel, num_segments=n,
                          block_d=bd),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_edges, dp), jnp.float32),
        interpret=interpret,
    )(*_edge_args(edge_dst, block_e), gv, fv, dv)
    return out[:, :d]


# ---------------------------------------------------------------------------
# edge-softmax backward: recompute p_e in-kernel, one launch, all heads
# ---------------------------------------------------------------------------


def _edge_softmax_bwd_kernel(dst_hbm, dst_col, g_hbm, stat_hbm, logit_ref,
                             val_ref, dlogit_ref, dval_ref, dst_smem, gbuf,
                             sbuf, sem, *, num_segments: int, heads: int,
                             head_dim: int):
    """One edge-chunk grid step, all heads.

    With p_e = softmax_e(logit) over destination i's in-edges:
        d_value_e = p_e * g_i
        d_logit_e = p_e * (v_e . g_i  -  out_i . g_i)
    p_e is rebuilt here from the saved logits and the forward's softmax
    stats (running max m_i, denominator den_i) — never materialized as an
    (E, H) tensor. Each edge gathers its destination's cotangent row and
    one packed stats row ``[m (H) | den (H) | out.g (H)]``.
    """
    c = pl.program_id(0)
    pltpu.sync_copy(dst_hbm.at[c], dst_smem)
    live = _live(dst_smem, num_segments)
    gather_rows(dst_smem, g_hbm, gbuf, sem, num_segments, 0, gbuf.shape[1],
                live)
    gather_rows(dst_smem, stat_hbm, sbuf, sem, num_segments, 0,
                sbuf.shape[1], live)
    valid = dst_col[...] < num_segments                        # (BE, 1)
    # rows of lanes without a destination were never gathered
    logits = logit_ref[...]
    stats = jnp.where(valid, sbuf[...], 0.0)
    g = jnp.where(valid, gbuf[...], 0.0)
    vg_all = val_ref[...] * g
    head_of_lane = jax.lax.broadcasted_iota(jnp.int32, (1, g.shape[1]),
                                            1) // head_dim
    logit_lane = jax.lax.broadcasted_iota(jnp.int32, (1, logits.shape[1]),
                                          1)
    dval = jnp.zeros_like(g)
    dlogit = jnp.zeros_like(logits)
    for h in range(heads):
        logit = lane(logits, h)                                # (BE, 1)
        # recompute-in-kernel; masked edges (logit == NEG) and pad lanes
        # get p = 0 exactly, matching the reference math's masked
        # exponentials
        p = (jnp.exp(logit - lane(stats, h))
             / jnp.maximum(lane(stats, heads + h), 1e-20))
        p = jnp.where(valid & (logit > NEG / 2), p, 0.0)
        mine = head_of_lane == h
        dval = jnp.where(mine, p * g, dval)
        vg = jnp.sum(jnp.where(mine, vg_all, 0.0), axis=1, keepdims=True)
        dlogit = jnp.where(logit_lane == h,
                           p * (vg - lane(stats, 2 * heads + h)), dlogit)
    dval_ref[...] = dval
    dlogit_ref[...] = dlogit


def edge_softmax_bwd_csc(g: jax.Array, logits: jax.Array, values: jax.Array,
                         m: jax.Array, den: jax.Array, og: jax.Array,
                         edge_dst: jax.Array, num_edges: int,
                         block_e: int = 256, interpret: bool = False):
    """Backward of the fused edge-softmax aggregation — one launch, all
    heads per grid step (mirroring the forward).

    g (N, H, D) output cotangent; logits (E, H) / values (E, H, D) saved
    forward operands; m / den (N, H) the forward kernel's softmax stats;
    og (N, H) = sum(out * g, -1). Returns float32 (d_logits (E, H),
    d_values (E, H, D)).
    """
    n, h, d = g.shape
    if logits.shape != (num_edges, h):
        raise ValueError(f"logits {logits.shape} do not match the "
                         f"expected ({num_edges}, {h})")
    if values.shape != (num_edges, h, d):
        raise ValueError(f"values {values.shape} do not match the "
                         f"expected ({num_edges}, {h}, {d})")
    if m.shape != (n, h) or den.shape != (n, h) or og.shape != (n, h):
        raise ValueError(
            f"softmax stats m {m.shape} / den {den.shape} / og {og.shape}"
            f" do not match the expected ({n}, {h})")
    e_pad = _check_edge_dst(edge_dst, block_e, num_edges)
    if num_edges == 0:
        return (jnp.zeros((0, h), jnp.float32),
                jnp.zeros((0, h, d), jnp.float32))
    gv = row_view(g.reshape(n, h * d))                          # (N, 1, Wp)
    stats = row_view(jnp.concatenate([m, den, og], axis=1).astype(
        jnp.float32))                                           # (N, 1, Sp)
    lg = lane_pad(logits)                                       # (E, Hp)
    vals = lane_pad(values.reshape(num_edges, h * d))           # (E, Wp)
    hp, wp = lg.shape[1], vals.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(e_pad // block_e,),
        in_specs=_edge_specs(block_e, lambda c: (c, 0)) + [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((block_e, hp), lambda c: (c, 0)),
            pl.BlockSpec((block_e, wp), lambda c: (c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_e, hp), lambda c: (c, 0)),
            pl.BlockSpec((block_e, wp), lambda c: (c, 0)),
        ],
        scratch_shapes=[pltpu.SMEM((1, block_e), jnp.int32),
                        pltpu.VMEM((block_e, wp), jnp.float32),
                        pltpu.VMEM((block_e, stats.shape[-1]), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
    )
    d_logits, d_values = pl.pallas_call(
        functools.partial(_edge_softmax_bwd_kernel, num_segments=n,
                          heads=h, head_dim=d),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((num_edges, hp), jnp.float32),
            jax.ShapeDtypeStruct((num_edges, wp), jnp.float32),
        ],
        interpret=interpret,
    )(*_edge_args(edge_dst, block_e), gv, stats, lg, vals)
    return d_logits[:, :h], d_values[:, :h * d].reshape(num_edges, h, d)
