"""jit'd public wrappers around the Pallas kernels (+ host-side planning).

Each op takes ``interpret=``; the default (``None``) resolves from the
platform (:func:`default_interpret`): Mosaic on a TPU, the Pallas
interpreter elsewhere, so the same calls validate on CPU. The pure jnp
oracles live in ref.py. The Sum-stage kernels compute in float32; the
wrappers cast their results back to the input dtype.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.jaxpr import (ContractError, JaxprContext,  # noqa: F401
                                  check_or_raise,
                                  count_segment_scatters,  # noqa: F401
                                  jaxpr_avals, jaxpr_eqns,  # noqa: F401
                                  run_rules)
from repro.kernels.backward import (count_launch, edge_softmax_bwd_csc,
                                    edge_softmax_bwd_path,
                                    segment_max_bwd_csc,
                                    segment_max_bwd_path,
                                    segment_sum_bwd_csc,
                                    segment_sum_bwd_path)
from repro.kernels.segment_sum import segment_sum_csc, segment_max_csc
from repro.kernels.wkv6 import wkv6 as _wkv6_kernel
from repro.kernels.flash_attention import flash_attention as _flash_kernel


def default_interpret() -> bool:
    """Pallas interpret mode everywhere but on a TPU."""
    return jax.default_backend() != "tpu"


def _interp(interpret) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


# ---------------------------------------------------------------------------
# segment sum / max: host plan + device ops
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CSCPlan:
    """Per-graph packed edge layout for the blocked aggregation kernels.

    Destinations are tiled into ``num_blocks`` blocks of ``block_n`` rows.
    Each block's in-edges, in CSC order, fill whole ``block_e``-lane
    chunks, ``max(1, ceil(len_b / block_e))`` of them (the one chunk of an
    empty block is what writes its output rows), packed back to back;
    padded plans end in dead chunks. The forward kernels read each chunk's
    block from the plan's step table (``segment_sum.step_table``), so they
    walk only chunks that hold edges.

    Built once per graph (the paper's reused CSC indexing); all views and
    batches reuse it — only the per-edge messages change between steps.
    Registered as a jax pytree (index arrays are leaves, the block geometry
    is static aux data) so plans ride along GraphBlocks and engine shards
    through ``jit`` / ``shard_map`` / ``grad``.
    """
    gather_idx: np.ndarray    # (n_chunks, BE) int32 into the edge axis
    #                           (dead lanes hold num_edges; the kernels
    #                           never gather them)
    local_ids: np.ndarray     # (n_chunks, BE) int32: a live lane's
    #                           destination row (block b holds rows
    #                           [b*BN, (b+1)*BN)); a dead lane of block b
    #                           holds -1 - b, of a trailing chunk
    #                           -1 - num_blocks
    edge_dst: np.ndarray      # (E_pad,) int32: the plan's inverse map,
    #                           lane e = destination row of edge e (pad
    #                           lanes hold num_segments) — drives the
    #                           backward kernels' per-edge gather
    num_blocks: int
    block_n: int
    block_e: int
    num_segments: int
    num_edges: int


def _plan_flatten(p: CSCPlan):
    return ((p.gather_idx, p.local_ids, p.edge_dst),
            (p.num_blocks, p.block_n, p.block_e, p.num_segments,
             p.num_edges))


def _plan_unflatten(aux, children):
    return CSCPlan(children[0], children[1], children[2], *aux)


jax.tree_util.register_pytree_node(CSCPlan, _plan_flatten, _plan_unflatten)


def num_plan_blocks(num_segments: int, block_n: int) -> int:
    """Destination blocks of a plan over ``num_segments`` rows."""
    return -(-num_segments // block_n)


def build_csc_plan(segment_ids: np.ndarray, num_segments: int,
                   block_n: int = 128, block_e: int = 256,
                   n_chunks: int = 0) -> CSCPlan:
    """The packed plan over ``segment_ids`` (ids outside ``[0,
    num_segments)`` join no block). ``n_chunks`` > 0 pads it with dead
    chunks to that many (so plans built for one bucket, or for the shards
    of one graph, share a shape)."""
    ids = np.asarray(segment_ids)
    E = len(ids)
    order = np.argsort(ids, kind="stable").astype(np.int64)
    sorted_ids = ids[order]
    nb = num_plan_blocks(num_segments, block_n)
    bounds = np.searchsorted(
        sorted_ids, np.minimum(np.arange(nb + 1) * block_n, num_segments))
    lens = np.diff(bounds)
    per_block = np.maximum(1, -(-lens // block_e))
    first = np.concatenate([[0], np.cumsum(per_block)])
    live = int(first[-1])
    if n_chunks and n_chunks < live:
        raise ValueError(
            f"forced n_chunks={n_chunks} is below the {live} chunks the "
            f"blocks need")
    n_chunks = n_chunks or live
    gather = np.full((n_chunks, block_e), E, np.int32)
    local = np.full((n_chunks, block_e), -1 - nb, np.int32)
    local[:live] = np.repeat(-1 - np.arange(nb, dtype=np.int32),
                             per_block)[:, None]
    # sorted edge k of block b lands on lane first[b]*BE + (k - bounds[b])
    blk = np.repeat(np.arange(nb), lens)
    lane = first[blk] * block_e + np.arange(len(blk)) - bounds[blk]
    gather.flat[lane] = order[bounds[0]:bounds[-1]]
    local.flat[lane] = sorted_ids[bounds[0]:bounds[-1]]
    # the inverse map the backward kernels scalar-prefetch: each live
    # lane names its edge and that edge's destination row. Padded to a
    # block_e multiple (pad lanes = num_segments, clip-gathered).
    e_pad = max(block_e, -(-E // block_e) * block_e)
    edge_dst = np.full(e_pad, num_segments, np.int32)
    edge_dst[gather.flat[lane]] = local.flat[lane]
    return CSCPlan(gather, local, edge_dst, nb, block_n, block_e,
                   num_segments, E)


def bucket_plan_chunks(n_pad: int, e_pad: int, block_n: int = 128,
                       block_e: int = 256) -> int:
    """The chunk count of every plan of an ``(n_pad, e_pad)`` bucket:
    ``ceil(e_pad / BE) + nb`` covers any view, since a block of ``len_b``
    edges takes ``max(1, ceil(len_b / BE)) <= len_b // BE + 1`` chunks."""
    return -(-e_pad // block_e) + num_plan_blocks(n_pad, block_n)


def build_bucket_csc_plan(dst_local: np.ndarray, n_pad: int, e_pad: int,
                          block_n: int = 128,
                          block_e: int = 256) -> CSCPlan:
    """Bucket-shape-stable plan over a compact view's local destination
    ids: every plan built for one ``(n_pad, e_pad)`` bucket has identical
    leaf shapes AND identical static geometry (``num_blocks`` and the
    chunk count, :func:`bucket_plan_chunks`, derive from the bucket, not
    the view), so a jitted step taking the plan as a pytree caches exactly
    one executable per bucket. The chunks the view does not need are dead
    and cost the forward kernels one table read each.

    =================  ======  ===============  =======================
    bucket             blocks  chunks (lanes)   step table (SMEM words)
    =================  ======  ===============  =======================
    (4,096, 16,384)    32      96 (24,576)      129
    (16,384, 65,536)   128     384 (98,304)     513
    (32,768, 131,072)  256     768 (196,608)    1,025
    =================  ======  ===============  =======================

    Pad edges carry segment id ``n_pad`` — outside every block's range, so
    they join no block; their values are additionally nulled by the
    block's ``edge_mask`` like any padded edge."""
    e = len(dst_local)
    if e > e_pad:
        raise ValueError(
            f"{e} edges do not fit the bucket's e_pad={e_pad}")
    if e and int(dst_local.max()) >= n_pad:
        raise ValueError(
            f"destination id {int(dst_local.max())} outside the "
            f"bucket's n_pad={n_pad}")
    ids = np.full(e_pad, n_pad, np.int32)
    ids[:e] = dst_local
    return build_csc_plan(
        ids, n_pad, block_n, block_e,
        n_chunks=bucket_plan_chunks(n_pad, e_pad, block_n, block_e))


def build_csc_plans_stacked(segment_ids_rows, num_segments: int,
                            block_n: int = 128, block_e: int = 256):
    """One plan per row of ``segment_ids_rows`` (P, E), all padded to the
    largest chunk count — the per-shard reused plans of the distributed
    engine, stacked to (P, n_chunks, BE)."""
    rows = [np.asarray(r) for r in segment_ids_rows]
    plans = [build_csc_plan(r, num_segments, block_n, block_e) for r in rows]
    n_chunks = max(p.gather_idx.shape[0] for p in plans)
    return [p if p.gather_idx.shape[0] == n_chunks else
            build_csc_plan(r, num_segments, block_n, block_e, n_chunks)
            for r, p in zip(rows, plans)]


@functools.partial(jax.jit, static_argnames=(
    "num_segments", "block_n", "block_e", "interpret", "op"))
def _segment_reduce_planned(data, gather_idx, local_ids, num_segments: int,
                            block_n: int, block_e: int, interpret: bool,
                            op: str = "sum"):
    # the gather is fused into the kernels (plan chunks copied to SMEM)
    # — no (n_chunks, BE, D) pre-gathered tensor is materialized here
    kern = segment_sum_csc if op == "sum" else segment_max_csc
    out = kern(data, gather_idx, local_ids,
               num_plan_blocks(num_segments, block_n), block_n, block_e,
               interpret=interpret)
    return out[:num_segments]


def _reshape_to_2d(data):
    """(E,) / (E, D) / (E, H, D) -> ((E, prod(rest)), trailing_shape)."""
    trailing = data.shape[1:]
    return data.reshape(data.shape[0], -1), trailing


def segment_sum_op(data: jax.Array, plan: CSCPlan,
                   interpret: bool | None = None) -> jax.Array:
    """data (E,)/(E, D)/(E, H, D) float -> (num_segments, ...trailing), via
    the Pallas CSC kernel (multi-head messages fold into the lane axis)."""
    if data.shape[0] != plan.num_edges:
        raise ValueError(f"data edge axis {data.shape[0]} != plan "
                         f"num_edges {plan.num_edges}")
    flat, trailing = _reshape_to_2d(data)
    out = _segment_reduce_planned(
        flat, jnp.asarray(plan.gather_idx), jnp.asarray(plan.local_ids),
        plan.num_segments, plan.block_n, plan.block_e, _interp(interpret),
        "sum")
    return out.reshape((plan.num_segments,) + trailing).astype(data.dtype)


def segment_max_op(data: jax.Array, plan: CSCPlan,
                   interpret: bool | None = None) -> jax.Array:
    """Masked segment max; empty segments come back as NEG (callers clamp,
    matching the -inf identity of ``jax.ops.segment_max``)."""
    if data.shape[0] != plan.num_edges:
        raise ValueError(f"data edge axis {data.shape[0]} != plan "
                         f"num_edges {plan.num_edges}")
    flat, trailing = _reshape_to_2d(data)
    out = _segment_reduce_planned(
        flat, jnp.asarray(plan.gather_idx), jnp.asarray(plan.local_ids),
        plan.num_segments, plan.block_n, plan.block_e, _interp(interpret),
        "max")
    return out.reshape((plan.num_segments,) + trailing).astype(data.dtype)


# -- fused backward wrappers (the custom_vjp bodies in core/aggregate) ------


@functools.partial(jax.jit, static_argnames=("num_edges", "block_e",
                                             "interpret"))
def _segment_sum_bwd_planned(g, edge_dst, num_edges: int, block_e: int,
                             interpret: bool):
    return segment_sum_bwd_csc(g, edge_dst, num_edges, block_e,
                               interpret=interpret)


def segment_sum_bwd_op(g: jax.Array, plan: CSCPlan,
                       interpret: bool | None = None) -> jax.Array:
    """Backward of :func:`segment_sum_op`: g (num_segments, ...trailing)
    -> (E, ...trailing) via the plan-driven gather kernel (segment-sum is
    linear, so d_data[e] = g[dst[e]])."""
    if g.shape[0] != plan.num_segments:
        raise ValueError(f"cotangent segment axis {g.shape[0]} != plan "
                         f"num_segments {plan.num_segments}")
    flat, trailing = _reshape_to_2d(g)
    if plan.num_edges:
        count_launch(segment_sum_bwd_path(*flat.shape, plan.block_e))
    out = _segment_sum_bwd_planned(flat, jnp.asarray(plan.edge_dst),
                                   plan.num_edges, plan.block_e,
                                   _interp(interpret))
    return out.reshape((plan.num_edges,) + trailing).astype(g.dtype)


@functools.partial(jax.jit, static_argnames=("num_edges", "block_e",
                                             "interpret"))
def _segment_max_bwd_planned(g, fwd_out, data, edge_dst, num_edges: int,
                             block_e: int, interpret: bool):
    return segment_max_bwd_csc(g, fwd_out, data, edge_dst, num_edges,
                               block_e, interpret=interpret)


def segment_max_bwd_op(g: jax.Array, fwd_out: jax.Array, data: jax.Array,
                       plan: CSCPlan,
                       interpret: bool | None = None) -> jax.Array:
    """Backward of :func:`segment_max_op`: the gather kernel plus the
    in-kernel argmax-hit mask against the saved forward output."""
    if g.shape[0] != plan.num_segments:
        raise ValueError(f"cotangent segment axis {g.shape[0]} != plan "
                         f"num_segments {plan.num_segments}")
    if data.shape[0] != plan.num_edges:
        raise ValueError(f"data edge axis {data.shape[0]} != plan "
                         f"num_edges {plan.num_edges}")
    gf, trailing = _reshape_to_2d(g)
    ff, _ = _reshape_to_2d(fwd_out)
    df, _ = _reshape_to_2d(data)
    if plan.num_edges:
        count_launch(segment_max_bwd_path(*gf.shape, plan.block_e))
    out = _segment_max_bwd_planned(gf, ff, df, jnp.asarray(plan.edge_dst),
                                   plan.num_edges, plan.block_e,
                                   _interp(interpret))
    return out.reshape((plan.num_edges,) + trailing).astype(data.dtype)


# ---------------------------------------------------------------------------
# contract shims — the jaxpr walkers and Sum-stage asserts moved to the
# repro.analysis rule registry (version-robust jaxpr_eqns, Finding
# records, the ``python -m repro.analysis`` CI gate). These delegating
# shims keep the historical ops-level API; the assert_* helpers raise
# ContractError (an AssertionError subclass), so existing
# ``pytest.raises(AssertionError)`` callers keep passing.
# ---------------------------------------------------------------------------


def assert_pregather_free(closed_jaxpr, plan: CSCPlan):
    """Shim over the ``jaxpr.pregather`` registry rule: the traced
    computation never allocates a tensor shaped like the pre-gathered
    (n_chunks, BE, ...) message layout the fused kernels eliminated —
    including the 2-D *float* (n_chunks, BE) layout the old edge-softmax
    path used for gathered logits. The integer 2-D plan index arrays
    (gather_idx/local_ids) are expected and allowed."""
    check_or_raise(run_rules(JaxprContext(closed_jaxpr, plan=plan),
                             ids=["jaxpr.pregather"]))


def assert_sum_stage_fused(closed_jaxpr, plan: CSCPlan):
    """Shim over the full Sum-stage ruleset on the csc path, forward AND
    backward:

    1. ``jaxpr.pregather`` — no ``(n_chunks, BE, ...)`` float tensor;
    2. ``jaxpr.segment-scatter`` — no scatter primitive whose updates
       carry the edge axis (the forward fallback's ``.at[ids].add/max``
       and the softmax recompute's segment passes);
    3. ``jaxpr.backward-gather`` — no gather primitive mapping the
       segment axis onto the edge axis outside the kernels (the old
       ``g[segment_ids]`` backward); the fused backward reads cotangents
       through the kernels' on-chip gather from the scalar-prefetched
       ``edge_dst`` plan instead.

    Apply to ``jax.value_and_grad`` jaxprs of combine-level losses: there
    the only segment-shaped traffic *is* the Sum stage, so the assertion
    is exact. (Model-level jaxprs legitimately gather/scatter the edge
    axis in NN-Gather — use :func:`count_segment_scatters` across
    backends there, plus the pre-gather walk which stays exact.)
    """
    check_or_raise(run_rules(
        JaxprContext(closed_jaxpr, plan=plan),
        ids=["jaxpr.pregather", "jaxpr.segment-scatter",
             "jaxpr.backward-gather"]))


# ---------------------------------------------------------------------------
# wkv6
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_op(r, k, v, w, u, chunk: int = 64, interpret: bool | None = None):
    """Chunked WKV6; pads T up to a chunk multiple and slices back."""
    B, T, H, K = r.shape
    pad = (-T) % chunk
    if pad:
        zk = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
        r, k, v = zk(r), zk(k), zk(v)
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0), (0, 0)),
                    constant_values=1.0)
    out = _wkv6_kernel(r, k, v, w, u, chunk=chunk,
                       interpret=_interp(interpret))
    return out[:, :T]


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=(
    "causal", "sliding_window", "block_q", "block_k", "interpret"))
def flash_attention_op(q, k, v, causal: bool = True, sliding_window: int = 0,
                       block_q: int = 128, block_k: int = 128,
                       interpret: bool | None = None):
    """GQA-aware wrapper: repeats kv heads to q heads, pads T to blocks."""
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    if Hkv != Hq:
        rep = Hq // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    bq = min(block_q, T)
    bk = min(block_k, T)
    # after clamping, round the larger block down to a multiple of the
    # smaller: then max(bq, bk) is a common multiple of both (the
    # kernel's divisibility contract) and padding stays under one block
    # (an lcm of coprime-ish clamped blocks could inflate T several-fold)
    if bq >= bk:
        bq = max(bk, bq // bk * bk)
    else:
        bk = max(bq, bk // bq * bq)
    pad = (-T) % max(bq, bk)
    if pad:
        zp = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q, k, v = zp(q), zp(k), zp(v)
    # seq_len=T (the *unpadded* length) so the kernel masks the padded
    # keys — without it, non-causal attention leaks zero-logit pad keys
    # into the softmax denominator
    out = _flash_kernel(q, k, v, causal=causal,
                        sliding_window=sliding_window,
                        block_q=bq, block_k=bk, seq_len=T,
                        interpret=_interp(interpret))
    return out[:, :T]


# ---------------------------------------------------------------------------
# edge softmax (GAT aggregation)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=(
    "num_segments", "block_n", "block_e", "interpret"))
def _edge_softmax_planned(logits, values, gather_idx, local_ids,
                          num_segments: int, block_n: int, block_e: int,
                          interpret: bool):
    from repro.kernels.edge_softmax import edge_softmax_csc
    # raw (E, H) / (E, H, D) operands go straight to the fused-gather
    # kernel; all heads run in a single launch. The
    # launch also yields the per-destination softmax stats (m, den) the
    # recompute-in-kernel backward rebuilds p_e from.
    out, m, den = edge_softmax_csc(logits, values, gather_idx, local_ids,
                                   num_plan_blocks(num_segments, block_n),
                                   block_n, block_e, interpret=interpret)
    return out[:num_segments], m[:num_segments], den[:num_segments]


def _lift_single_head(logits, values):
    if logits.ndim == 1:
        return logits[:, None], values[:, None, :], True
    if logits.ndim != 2 or values.ndim != 3:
        raise ValueError(
            f"expected (E, H) logits with (E, H, D) values, got "
            f"{logits.shape} / {values.shape}")
    return logits, values, False


def edge_softmax_op(logits: jax.Array, values: jax.Array, plan: CSCPlan,
                    interpret: bool | None = None) -> jax.Array:
    """Fused GAT aggregation: softmax-weighted neighbor sums.

    Single-head: logits (E,), values (E, D) -> (num_segments, D).
    Multi-head:  logits (E, H), values (E, H, D) -> (num_segments, H, D);
    heads share the CSC plan (the gather layout depends only on the
    destination ids, not the head) and run as one kernel launch with the
    head axis on the grid.
    """
    out, _, _ = edge_softmax_fwd_op(logits, values, plan, interpret)
    return out


def edge_softmax_fwd_op(logits: jax.Array, values: jax.Array,
                        plan: CSCPlan, interpret: bool | None = None):
    """:func:`edge_softmax_op` plus the kernel's per-destination softmax
    stats: returns (out, m (num_segments, H), den (num_segments, H)) —
    the residuals the fused backward needs to rebuild p_e in-kernel."""
    if logits.shape[0] != plan.num_edges:
        raise ValueError(f"logits edge axis {logits.shape[0]} != plan "
                         f"num_edges {plan.num_edges}")
    g_idx = jnp.asarray(plan.gather_idx)
    l_ids = jnp.asarray(plan.local_ids)
    lg, vals, single = _lift_single_head(logits, values)
    out, m, den = _edge_softmax_planned(
        lg, vals, g_idx, l_ids, plan.num_segments, plan.block_n,
        plan.block_e, _interp(interpret))
    out = out.astype(values.dtype)
    if single:
        return out[:, 0, :], m, den
    return out, m, den


@functools.partial(jax.jit, static_argnames=("num_edges", "block_e",
                                             "interpret"))
def _edge_softmax_bwd_planned(g, logits, values, m, den, og, edge_dst,
                              num_edges: int, block_e: int,
                              interpret: bool):
    return edge_softmax_bwd_csc(g, logits, values, m, den, og, edge_dst,
                                num_edges, block_e, interpret=interpret)


def edge_softmax_bwd_op(g: jax.Array, logits: jax.Array, values: jax.Array,
                        out: jax.Array, m: jax.Array, den: jax.Array,
                        plan: CSCPlan, interpret: bool | None = None):
    """Backward of :func:`edge_softmax_op` — the recompute-in-kernel pass.

    g / out (num_segments, H, D) cotangent and saved forward output;
    logits / values the saved forward operands; m / den the forward
    launch's softmax stats. Returns (d_logits, d_values) from one launch
    covering all heads; the edge probabilities are rebuilt inside the
    kernel (never an (E, H) tensor in HBM) and no reference segment pass
    runs.
    """
    if logits.shape[0] != plan.num_edges:
        raise ValueError(f"logits edge axis {logits.shape[0]} != plan "
                         f"num_edges {plan.num_edges}")
    lg, vals, single = _lift_single_head(logits, values)
    if single:
        g, out = g[:, None, :], out[:, None, :]
    # og_i = out_i . g_i: the node-proportional contraction of d_logit
    # (elementwise jnp, no segment op, no edge-axis materialization)
    og = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32), axis=-1)
    if plan.num_edges:
        count_launch(edge_softmax_bwd_path(*g.shape, plan.block_e))
    d_logits, d_values = _edge_softmax_bwd_planned(
        g, lg, vals, m, den, og, jnp.asarray(plan.edge_dst),
        plan.num_edges, plan.block_e, _interp(interpret))
    d_logits = d_logits.astype(logits.dtype)
    d_values = d_values.astype(values.dtype)
    if single:
        return d_logits[:, 0], d_values[:, 0, :]
    return d_logits, d_values
