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
grid therefore tiles the (padded) edge axis in ``block_e`` chunks and
fills a ``(BE, BD)`` buffer with each edge's destination row of the
node-indexed table (cotangent ``g``, saved forward output, softmax
stats), addressed by the plan's **inverse map** ``edge_dst`` — built
host-side in ``build_csc_plan`` by inverting ``gather_idx``/``local_ids``
(live lane ``(c, l)`` holds edge ``gather_idx[c, l]`` destined for row
``local_ids[c, l]``). Each chunk of ``edge_dst`` is copied to SMEM for
the row addresses and also read as a ``(BE, 1)`` column: lanes whose
destination is outside the plan (``num_segments``: pad lanes, and edges
no block aggregates) get a zero cotangent, as the reference segment ops'
transpose gives them. The outputs are allocated at the true edge count,
so the final partial block is an ordinary masked boundary block.

Resident table
--------------
The table is indexed by node, not by edge, so it is small beside the
edge axis. Where it fits, it is **resident**: one whole-array block per
launch (per d-tile, where there is a d-tile axis), single-buffered, and
each lane's row is a VMEM row copy
(:func:`~repro.kernels.segment_sum.copy_rows`). The table is read from
HBM once per launch, whatever the number of chunks, and no DMA is issued
in the lane loop. Where it does not fit, each live lane's row is one HBM
row DMA (:func:`~repro.kernels.segment_sum.gather_rows`), as the forward
kernels gather. The choice is the shape's alone: a launch is resident
when its table's lane-padded bytes, plus the ``edge_dst`` column and the
``(BE, ·)`` edge tiles, scratch and largest temporary, fit
``VMEM_BUDGET`` (the static analyzer's ``vmem.budget`` default, 16
MiB); resident launches ask Mosaic for ``RESIDENT_VMEM_LIMIT`` of scoped
VMEM, room for the pipeline's second buffers of the edge tiles. Both
paths give the same bits, since a copy is a copy.

The trace-time tally (:func:`launch_tally`, fed by the ``ops`` wrappers)
counts the launches a traced computation holds by path; the trainers
attach it to ``train.fit`` as ``bwd_vmem`` and ``bwd_dma``.

Three kernels:

- :func:`segment_sum_bwd_csc` — the linear backward, a pure plan-driven
  gather: ``d_data[e] = g[dst[e]]``; d-tiled.
- :func:`segment_max_bwd_csc` — the same gather plus an in-kernel
  argmax-hit mask against the saved forward output (ties share the
  cotangent, matching ``jax.ops.segment_max``); two tables, ``g`` and
  the forward output, resident or gathered together.
- :func:`edge_softmax_bwd_csc` — recompute-in-kernel: rebuilds the edge
  probability ``p_e = exp(logit_e - m_i) / den_i`` inside each edge block
  from the saved logits and the forward kernel's per-destination softmax
  stats (``m``/``den`` ride out of the fused forward launch as two tiny
  node-proportional outputs). No ``(E, H)`` probability tensor is ever
  materialized in HBM and no reference ``segment_max``/``segment_sum``
  recompute runs; ``d_logits`` and ``d_values`` come out of **one**
  launch, all heads per grid step, mirroring the forward. Its table is
  one packed row per node, ``[g (H·D) | m (H) | den (H) | out·g (H)]``
  lane-padded, so each lane takes one row (GAT-E's 4 x 8 heads: 44
  lanes, one 128-lane row).

VMEM geometry is tabulated with the forward budget in segment_sum.py.
"""
from __future__ import annotations

import contextlib
import functools
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.vmem import DEFAULT_VMEM_BUDGET
from repro.kernels.segment_sum import (LANES, NEG, _pick_block_d,
                                       chunk_view, copy_rows, gather_rows,
                                       lane_pad, row_view)

VMEM_BUDGET = DEFAULT_VMEM_BUDGET       # a resident launch's VMEM bound
RESIDENT_VMEM_LIMIT = 2 * DEFAULT_VMEM_BUDGET   # Mosaic's scoped VMEM


def lane(x, k: int):
    """Column ``k`` of a 2-D value as a (rows, 1) column (exact select)."""
    hit = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) == k
    return jnp.sum(jnp.where(hit, x, 0.0), axis=1, keepdims=True)


# ---------------------------------------------------------------------------
# the path: resident table or per-lane DMA, and its trace-time tally
# ---------------------------------------------------------------------------


def _path(n: int, table_lanes: int, block_e: int, tile_lanes: int) -> str:
    """``"vmem"`` when an ``(n, table_lanes)`` f32 table plus the
    ``edge_dst`` column and ``(BE, tile_lanes)`` of edge tiles, scratch
    and temporary fit ``VMEM_BUDGET``, else ``"dma"``."""
    nbytes = 4 * (n * table_lanes + block_e * (LANES + tile_lanes))
    return "vmem" if nbytes <= VMEM_BUDGET else "dma"


def _lanes(width: int) -> int:
    return -(-width // LANES) * LANES


def segment_sum_bwd_path(n: int, d: int, block_e: int = 256) -> str:
    """Path of :func:`segment_sum_bwd_csc` for an (n, d) cotangent: the
    table is one d-tile; out, buffer and temporary are three tiles."""
    bd = _pick_block_d(_lanes(d))
    return _path(n, bd, block_e, 3 * bd)


def segment_max_bwd_path(n: int, d: int, block_e: int = 256) -> str:
    """Path of :func:`segment_max_bwd_csc`: two tables (``g`` and the
    forward output); data, out, two buffers and a temporary."""
    bd = _pick_block_d(_lanes(d))
    return _path(n, 2 * bd, block_e, 5 * bd)


def edge_softmax_bwd_path(n: int, h: int, d: int,
                          block_e: int = 256) -> str:
    """Path of :func:`edge_softmax_bwd_csc`: the packed (n, H·D + 3H)
    table; logit and value tiles in and out, the buffer and a temporary
    as wide as it."""
    packed = _lanes(h * d + 3 * h)
    return _path(n, packed, block_e,
                 2 * _lanes(h) + 2 * _lanes(h * d) + 2 * packed)


class _Tallies(threading.local):
    def __init__(self):
        self.open: list = []


_TALLIES = _Tallies()


@contextlib.contextmanager
def launch_tally():
    """Count the backward launches traced inside the block, by path:
    yields ``{"bwd_vmem": n, "bwd_dma": m}``, filled as the thread traces
    them (:func:`count_launch`)."""
    counts = {"bwd_vmem": 0, "bwd_dma": 0}
    _TALLIES.open.append(counts)
    try:
        yield counts
    finally:
        _TALLIES.open.remove(counts)


def count_launch(path: str) -> None:
    """Add one traced launch on ``path`` to every open tally of the
    thread. The ``ops`` wrappers call it outside their jitted bodies,
    which jit traces once per signature, not once per call."""
    for counts in _TALLIES.open:
        counts["bwd_" + path] += 1


# ---------------------------------------------------------------------------
# shared launch pieces
# ---------------------------------------------------------------------------


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


def _table(x, resident: bool, block_d: int, index_map):
    """A node table's operand and spec: lane-padded (N, Dp) whole-column
    blocks held for the launch (one per d-tile), or the (N, 1, Dp) HBM
    view that per-lane row DMAs address."""
    if not resident:
        return row_view(x), pl.BlockSpec(memory_space=pl.ANY)
    x = lane_pad(x)
    return x, pl.BlockSpec((x.shape[0], block_d), index_map,
                           pipeline_mode=pl.Buffered(1))


def _params(resident: bool):
    return (pltpu.CompilerParams(vmem_limit_bytes=RESIDENT_VMEM_LIMIT)
            if resident else None)


def _rows(resident: bool, dst_smem, table, buf, sem, num_segments: int,
          d0=0):
    """Fill ``buf`` with each lane's destination row of ``table``: VMEM
    row copies from a resident table, else one HBM row DMA per live lane
    (columns ``d0:d0+BD`` of the (N, 1, Dp) view). Lanes without a
    destination hold rows the consumers mask."""
    if resident:
        copy_rows(dst_smem, table, buf, num_segments)
    else:
        gather_rows(dst_smem, table, buf, sem, num_segments, d0,
                    buf.shape[1], _live(dst_smem, num_segments))


# ---------------------------------------------------------------------------
# segment-sum backward: plan-driven per-edge gather
# ---------------------------------------------------------------------------


def _gather_bwd_kernel(dst_hbm, dst_col, g_ref, out_ref, dst_smem, gbuf, sem,
                       *, num_segments: int, block_d: int, resident: bool):
    """One (d_tile, edge_chunk) grid step of ``d_data[e] = g[dst[e]]``.

    dst_hbm: (E_pad/BE, 1, BE) int32 chunks of the plan's inverse map.
    dst_col: (BE, 1) int32 — the same chunk as a column (validity).
    g_ref:   (N, BD) f32 resident cotangent tile, or (N, 1, Dp) in HBM.
    out_ref: (BE, BD) f32 edge tile of the message cotangent.
    """
    dt, c = pl.program_id(0), pl.program_id(1)
    pltpu.sync_copy(dst_hbm.at[c], dst_smem)
    _rows(resident, dst_smem, g_ref, gbuf, sem, num_segments, dt * block_d)
    out_ref[...] = jnp.where(dst_col[...] < num_segments, gbuf[...], 0.0)


def segment_sum_bwd_csc(g: jax.Array, edge_dst: jax.Array, num_edges: int,
                        block_e: int = 256, interpret: bool = False):
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
    resident = segment_sum_bwd_path(n, d, block_e) == "vmem"
    dp = _lanes(d)
    bd = _pick_block_d(dp)
    gv, g_spec = _table(g, resident, bd, lambda dt, c: (0, dt))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(dp // bd, e_pad // block_e),
        in_specs=_edge_specs(block_e, lambda dt, c: (c, 0)) + [g_spec],
        out_specs=pl.BlockSpec((block_e, bd), lambda dt, c: (c, dt)),
        scratch_shapes=[pltpu.SMEM((1, block_e), jnp.int32),
                        pltpu.VMEM((block_e, bd), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
    )
    out = pl.pallas_call(
        functools.partial(_gather_bwd_kernel, num_segments=n, block_d=bd,
                          resident=resident),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_edges, dp), jnp.float32),
        compiler_params=_params(resident),
        interpret=interpret,
    )(*_edge_args(edge_dst, block_e), gv)
    return out[:, :d]


# ---------------------------------------------------------------------------
# segment-max backward: the gather + an in-kernel argmax-hit mask
# ---------------------------------------------------------------------------


def _gather_max_bwd_kernel(dst_hbm, dst_col, g_ref, fwd_ref, data_ref,
                           out_ref, dst_smem, gbuf, fbuf, sem, *,
                           num_segments: int, block_d: int, resident: bool):
    """Gather backward masked by ``data == forward_max`` (subgradient:
    ties share the cotangent, matching ``jax.ops.segment_max``)."""
    dt, c = pl.program_id(0), pl.program_id(1)
    pltpu.sync_copy(dst_hbm.at[c], dst_smem)
    _rows(resident, dst_smem, g_ref, gbuf, sem, num_segments, dt * block_d)
    _rows(resident, dst_smem, fwd_ref, fbuf, sem, num_segments,
          dt * block_d)
    hit = (dst_col[...] < num_segments) & (data_ref[...] == fbuf[...])
    out_ref[...] = jnp.where(hit, gbuf[...], 0.0)


def segment_max_bwd_csc(g: jax.Array, fwd_out: jax.Array, data: jax.Array,
                        edge_dst: jax.Array, num_edges: int,
                        block_e: int = 256, interpret: bool = False):
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
    resident = segment_max_bwd_path(n, d, block_e) == "vmem"
    dp = _lanes(d)
    bd = _pick_block_d(dp)
    table_index = lambda dt, c: (0, dt)  # noqa: E731
    gv, g_spec = _table(g, resident, bd, table_index)
    fv, f_spec = _table(fwd_out, resident, bd, table_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(dp // bd, e_pad // block_e),
        in_specs=_edge_specs(block_e, lambda dt, c: (c, 0)) + [
            g_spec, f_spec,
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
                          block_d=bd, resident=resident),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_edges, dp), jnp.float32),
        compiler_params=_params(resident),
        interpret=interpret,
    )(*_edge_args(edge_dst, block_e), gv, fv, lane_pad(data))
    return out[:, :d]


# ---------------------------------------------------------------------------
# edge-softmax backward: recompute p_e in-kernel, one launch, all heads
# ---------------------------------------------------------------------------


def _edge_softmax_bwd_kernel(dst_hbm, dst_col, row_ref, logit_ref, val_ref,
                             dlogit_ref, dval_ref, dst_smem, *scratch,
                             num_segments: int, heads: int, head_dim: int,
                             resident: bool):
    """One edge-chunk grid step, all heads.

    With p_e = softmax_e(logit) over destination i's in-edges:
        d_value_e = p_e * g_i
        d_logit_e = p_e * (v_e . g_i  -  out_i . g_i)
    p_e is rebuilt here from the saved logits and the forward's softmax
    stats (running max m_i, denominator den_i) — never materialized as an
    (E, H) tensor. Each edge takes its destination's one packed row
    ``[g (H·D) | m (H) | den (H) | out.g (H)]``: one buffer from the
    resident table, else one per 128 lanes of it, each gathered by its
    own row DMAs (a one-row DMA may not cut a wider buffer's tiles).
    """
    *bufs, sem = scratch
    c = pl.program_id(0)
    pltpu.sync_copy(dst_hbm.at[c], dst_smem)
    for k, buf in enumerate(bufs):
        _rows(resident, dst_smem, row_ref, buf, sem, num_segments,
              k * LANES)
    valid = dst_col[...] < num_segments                        # (BE, 1)
    # rows of lanes without a destination are masked out
    logits = logit_ref[...]
    row = bufs[0][...] if resident else jnp.concatenate(
        [buf[...] for buf in bufs], axis=1)
    row = jnp.where(valid, row, 0.0)
    wp = val_ref.shape[1]
    # lanes past H·D of g never reach an output: no head owns them
    g = row if row.shape[1] == wp else row[:, :wp]
    stat0 = heads * head_dim                    # lane of m's first head
    vg_all = val_ref[...] * g
    head_of_lane = jax.lax.broadcasted_iota(jnp.int32, (1, wp), 1) // head_dim
    logit_lane = jax.lax.broadcasted_iota(jnp.int32, (1, logits.shape[1]),
                                          1)
    dval = jnp.zeros_like(g)
    dlogit = jnp.zeros_like(logits)
    for h in range(heads):
        logit = lane(logits, h)                                # (BE, 1)
        # recompute-in-kernel; masked edges (logit == NEG) and pad lanes
        # get p = 0 exactly, matching the reference math's masked
        # exponentials
        p = (jnp.exp(logit - lane(row, stat0 + h))
             / jnp.maximum(lane(row, stat0 + heads + h), 1e-20))
        p = jnp.where(valid & (logit > NEG / 2), p, 0.0)
        mine = head_of_lane == h
        dval = jnp.where(mine, p * g, dval)
        vg = jnp.sum(jnp.where(mine, vg_all, 0.0), axis=1, keepdims=True)
        dlogit = jnp.where(logit_lane == h,
                           p * (vg - lane(row, stat0 + 2 * heads + h)),
                           dlogit)
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
    resident = edge_softmax_bwd_path(n, h, d, block_e) == "vmem"
    packed = jnp.concatenate([g.reshape(n, h * d), m, den, og],
                             axis=1).astype(jnp.float32)   # (N, H·D + 3H)
    pp = _lanes(h * d + 3 * h)
    rows, row_spec = _table(packed, resident, pp, lambda c: (0, 0))
    lg = lane_pad(logits)                                       # (E, Hp)
    vals = lane_pad(values.reshape(num_edges, h * d))           # (E, Wp)
    hp, wp = lg.shape[1], vals.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(e_pad // block_e,),
        in_specs=_edge_specs(block_e, lambda c: (c, 0)) + [
            row_spec,
            pl.BlockSpec((block_e, hp), lambda c: (c, 0)),
            pl.BlockSpec((block_e, wp), lambda c: (c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_e, hp), lambda c: (c, 0)),
            pl.BlockSpec((block_e, wp), lambda c: (c, 0)),
        ],
        scratch_shapes=[pltpu.SMEM((1, block_e), jnp.int32)]
        + ([pltpu.VMEM((block_e, pp), jnp.float32)] if resident else
           [pltpu.VMEM((block_e, LANES), jnp.float32)] * (pp // LANES))
        + [pltpu.SemaphoreType.DMA(())],
    )
    d_logits, d_values = pl.pallas_call(
        functools.partial(_edge_softmax_bwd_kernel, num_segments=n,
                          heads=h, head_dim=d, resident=resident),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((num_edges, hp), jnp.float32),
            jax.ShapeDtypeStruct((num_edges, wp), jnp.float32),
        ],
        compiler_params=_params(resident),
        interpret=interpret,
    )(*_edge_args(edge_dst, block_e), rows, lg, vals)
    return d_logits[:, :h], d_values[:, :h * d].reshape(num_edges, h, d)
