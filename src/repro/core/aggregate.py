"""Unified Sum-stage aggregation backend (paper §3.1 / §4.2, Fig. A3).

The Sum stage — per-edge gather + per-destination aggregation — is 76% of
GNN runtime in the paper's stage breakdown, and both forward paths used to
reimplement it: ``combine_messages`` (single block) and the combine branch
of ``_layer_forward_sharded`` (distributed) each hand-rolled sum/mean/
softmax over ``jax.ops.segment_*``. This module is the single combine
engine both consume:

- :data:`COMBINE_SPECS` — the registry of combine modes (``sum`` / ``mean``
  / ``max`` / ``softmax``) with their algebraic properties.
- :class:`AggregationBackend` — pluggable segment primitives. Two
  implementations ship: ``"reference"`` (portable jnp segment ops, the
  float32 oracle) and ``"csc"`` (the Pallas CSC-blocked kernels of
  :mod:`repro.kernels`, interpret-mode on CPU, Mosaic on TPU). A name
  given in config is honoured; without one the platform picks
  (:func:`default_backend_name`: ``"csc"`` on TPU, ``"reference"``
  elsewhere).
- :func:`combine` — the one Sum-stage implementation. Locally it is the
  full aggregation; under the hybrid-parallel engine the same code runs on
  shard-local partials and finalizes through a :class:`ShardContext`
  (mirror→master reduce + master→mirror broadcast hooks), which is exactly
  the paper's reduce/broadcast halo phases.

The ``"csc"`` backend needs a precomputed :class:`~repro.kernels.ops.
CSCPlan` (built once per graph/shard — the paper's reused CSC indexing);
a call without one raises :class:`MissingPlanError` rather than quietly
running the reference ops. The kernels read the plan's index chunks into
SMEM and gather message rows from HBM by DMA — the kernel path consumes
the raw ``(E, H, D)`` messages directly, with no pre-gathered
``(n_chunks, BE, D)`` intermediate (and multi-head softmax is one launch).
Kernel forwards are paired with fused Pallas
``custom_vjp`` backwards (:mod:`repro.kernels.backward`): a plan-driven
gather kernel for sum, the same gather plus an in-kernel argmax-hit mask
for max, and a recompute-in-kernel softmax jacobian — so ``jax.grad`` of
both the block and distributed paths never leaves the planned layout
(certified by ``ops.assert_sum_stage_fused``).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import (CSCPlan, default_interpret, num_plan_blocks,
                               edge_softmax_bwd_op,
                               edge_softmax_fwd_op, edge_softmax_op,
                               segment_max_bwd_op, segment_max_op,
                               segment_sum_bwd_op, segment_sum_op)
from repro.kernels.segment_sum import NEG   # the one masking sentinel


# ---------------------------------------------------------------------------
# combine-mode registry
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CombineSpec:
    """Static description of a Sum-stage combine mode.

    ``needs_logits``  — gather must emit a per-edge ``"logit"`` field.
    ``reduce_ops``    — halo reduce phases the distributed finalize needs
                        (paper §4.1: sum-reduce; softmax adds a max pass).
    """
    name: str
    needs_logits: bool
    reduce_ops: tuple


COMBINE_SPECS: Dict[str, CombineSpec] = {
    "sum": CombineSpec("sum", False, ("sum",)),
    "mean": CombineSpec("mean", False, ("sum",)),
    "max": CombineSpec("max", False, ("max",)),
    "softmax": CombineSpec("softmax", True, ("max", "sum")),
}


def combine_spec(mode: str) -> CombineSpec:
    try:
        return COMBINE_SPECS[mode]
    except KeyError:
        raise ValueError(
            f"unknown combine mode {mode!r}; "
            f"registered: {sorted(COMBINE_SPECS)}") from None


# ---------------------------------------------------------------------------
# backends
# ---------------------------------------------------------------------------


class AggregationBackend:
    """Segment primitives the combine algorithms are written against.

    ``data`` may be (E,), (E, H) or (E, H, D); outputs keep the trailing
    shape with the edge axis replaced by ``num_segments``. ``plan`` is an
    optional precomputed CSCPlan; backends that don't use one ignore it.
    """

    name = "abstract"

    def segment_sum(self, data, segment_ids, num_segments: int,
                    plan: Optional[CSCPlan] = None):
        raise NotImplementedError

    def segment_max(self, data, segment_ids, num_segments: int,
                    plan: Optional[CSCPlan] = None):
        raise NotImplementedError

    def edge_softmax(self, logits, values, segment_ids, num_segments: int,
                     plan: Optional[CSCPlan] = None):
        """Fused local softmax-weighted sum. ``logits`` are already masked
        to NEG and ``values`` zeroed on inactive edges."""
        seg_max = self.segment_max(logits, segment_ids, num_segments, plan)
        seg_max = jnp.maximum(seg_max, NEG)            # empty segments
        ex = jnp.exp(logits - seg_max[segment_ids])
        ex = jnp.where(logits > NEG / 2, ex, 0.0)
        den = self.segment_sum(ex, segment_ids, num_segments, plan)
        num = self.segment_sum(ex[..., None] * values, segment_ids,
                               num_segments, plan)
        return num / jnp.maximum(den, 1e-9)[..., None]


class ReferenceBackend(AggregationBackend):
    """The portable jnp segment ops (CPU / dry-run / oracle)."""

    name = "reference"

    def segment_sum(self, data, segment_ids, num_segments, plan=None):
        return jax.ops.segment_sum(data, segment_ids, num_segments)

    def segment_max(self, data, segment_ids, num_segments, plan=None):
        return jax.ops.segment_max(data, segment_ids, num_segments)


# -- csc backend: Pallas kernels + reference-math custom VJPs ---------------


def _int_zeros(x):
    """float0 cotangent for integer primals (plan indices, segment ids)."""
    return np.zeros(np.shape(x), dtype=jax.dtypes.float0)


def _plan_from_children(plan_children, meta, num_segments, num_edges):
    """Rebuild the CSCPlan from its traced index arrays (the pytree
    children ride through the custom_vjp as regular operands so the
    backward kernels can scalar-prefetch them)."""
    bn, be, _ = meta
    return CSCPlan(plan_children[0], plan_children[1], plan_children[2],
                   num_plan_blocks(num_segments, bn), bn, be, num_segments,
                   num_edges)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _csc_segment_sum(num_segments, meta, data, plan_children, segment_ids):
    plan = _plan_from_children(plan_children, meta, num_segments,
                               data.shape[0])
    return segment_sum_op(data, plan, interpret=meta[2])


def _csc_segment_sum_fwd(num_segments, meta, data, plan_children,
                         segment_ids):
    out = _csc_segment_sum(num_segments, meta, data, plan_children,
                           segment_ids)
    return out, (segment_ids, plan_children)


def _csc_segment_sum_bwd(num_segments, meta, res, g):
    segment_ids, plan_children = res
    # segment-sum is linear: d(data) = gather of the output cotangent —
    # the plan-driven Pallas gather kernel (d_data[e] = g[dst[e]], dst
    # scalar-prefetched from the plan's inverse map), not a g[ids] jnp
    # gather: the backward stays in the planned layout
    plan = _plan_from_children(plan_children, meta, num_segments,
                               segment_ids.shape[0])
    return (segment_sum_bwd_op(g, plan, interpret=meta[2]),
            tuple(_int_zeros(c) for c in plan_children),
            _int_zeros(segment_ids))


_csc_segment_sum.defvjp(_csc_segment_sum_fwd, _csc_segment_sum_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _csc_segment_max(num_segments, meta, data, plan_children, segment_ids):
    plan = _plan_from_children(plan_children, meta, num_segments,
                               data.shape[0])
    return segment_max_op(data, plan, interpret=meta[2])


def _csc_segment_max_fwd(num_segments, meta, data, plan_children,
                         segment_ids):
    out = _csc_segment_max(num_segments, meta, data, plan_children,
                           segment_ids)
    return out, (data, out, segment_ids, plan_children)


def _csc_segment_max_bwd(num_segments, meta, res, g):
    data, out, segment_ids, plan_children = res
    # subgradient: cotangent flows to entries attaining the segment max
    # (ties share it, matching jax.ops.segment_max); the argmax-hit mask
    # against the saved forward output is fused into the gather kernel
    plan = _plan_from_children(plan_children, meta, num_segments,
                               data.shape[0])
    return (segment_max_bwd_op(g, out, data, plan, interpret=meta[2]),
            tuple(_int_zeros(c) for c in plan_children),
            _int_zeros(segment_ids))


_csc_segment_max.defvjp(_csc_segment_max_fwd, _csc_segment_max_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _csc_edge_softmax(num_segments, meta, logits, values, plan_children,
                      segment_ids):
    plan = _plan_from_children(plan_children, meta, num_segments,
                               logits.shape[0])
    return edge_softmax_op(logits, values, plan, interpret=meta[2])


def _csc_edge_softmax_fwd(num_segments, meta, logits, values, plan_children,
                          segment_ids):
    plan = _plan_from_children(plan_children, meta, num_segments,
                               logits.shape[0])
    # the fused forward launch also emits the per-destination softmax
    # stats (running max m, denominator den) — node-proportional
    # residuals the backward rebuilds p_e from in-kernel, replacing the
    # old full reference segment_max/segment_sum recompute
    out, m, den = edge_softmax_fwd_op(logits, values, plan,
                                      interpret=meta[2])
    return out, (logits, values, out, m, den, segment_ids, plan_children)


def _csc_edge_softmax_bwd(num_segments, meta, res, g):
    logits, values, out, m, den, segment_ids, plan_children = res
    # recompute-in-kernel softmax jacobian. With p_e = softmax(logit_e)
    # over each destination's in-edges:
    #   d v_e     = p_e * g_i
    #   d logit_e = p_e * (v_e . g_i  -  out_i . g_i)
    # p_e is rebuilt inside the kernel from the saved logits + stats; no
    # (E, H) probability tensor, no reference segment passes, one launch
    # with heads on the grid (see kernels/backward.py).
    plan = _plan_from_children(plan_children, meta, num_segments,
                               logits.shape[0])
    d_logits, d_values = edge_softmax_bwd_op(g, logits, values, out, m,
                                             den, plan, interpret=meta[2])
    return (d_logits, d_values,
            tuple(_int_zeros(c) for c in plan_children),
            _int_zeros(segment_ids))


_csc_edge_softmax.defvjp(_csc_edge_softmax_fwd, _csc_edge_softmax_bwd)


def reference_edge_softmax_bwd(g, logits, values, out, segment_ids,
                               num_segments):
    """The pre-fusion reference-math softmax backward, kept verbatim as
    (a) the documented oracle for the kernel backward and (b) the
    reconstruction the benchmark times the fused backward against:
    a full segment_max/segment_sum recompute plus three ``x[segment_ids]``
    edge gathers, all through HBM."""
    seg_max = jax.ops.segment_max(logits, segment_ids, num_segments)
    seg_max = jnp.maximum(seg_max, NEG)
    ex = jnp.exp(logits - seg_max[segment_ids])
    ex = jnp.where(logits > NEG / 2, ex, 0.0)
    den = jax.ops.segment_sum(ex, segment_ids, num_segments)
    p = ex / jnp.maximum(den, 1e-9)[segment_ids]
    g_e = g[segment_ids]                                   # (E, H, D)
    d_values = p[..., None] * g_e
    vg = jnp.sum(values * g_e, axis=-1)                    # (E, H)
    og = jnp.sum(out[segment_ids] * g_e, axis=-1)          # (E, H)
    d_logits = p * (vg - og)
    return d_logits, d_values


class MissingPlanError(ValueError):
    """The ``"csc"`` backend was called without a CSCPlan."""


class CSCBackend(AggregationBackend):
    """The Pallas CSC-blocked kernels behind the backend interface.

    Requires a precomputed CSCPlan (build once per graph/shard via
    ``GraphBlock``/``PartitionPlan`` caches); a call without one raises
    :class:`MissingPlanError`. ``interpret=None`` resolves per call:
    interpret-mode off TPU, Mosaic compilation on TPU.
    """

    name = "csc"

    def __init__(self, interpret: Optional[bool] = None):
        self.interpret = interpret

    def _meta(self, plan: Optional[CSCPlan]):
        if plan is None:
            raise MissingPlanError(
                "the csc backend needs a CSCPlan: build the block with "
                "csc_plan=True (or stage it through the engine), or pick "
                "the reference backend")
        interp = (default_interpret() if self.interpret is None
                  else self.interpret)
        return (plan.block_n, plan.block_e, interp)

    @staticmethod
    def _children(plan: CSCPlan):
        return (jnp.asarray(plan.gather_idx), jnp.asarray(plan.local_ids),
                jnp.asarray(plan.edge_dst))

    def segment_sum(self, data, segment_ids, num_segments, plan=None):
        return _csc_segment_sum(num_segments, self._meta(plan), data,
                                self._children(plan), segment_ids)

    def segment_max(self, data, segment_ids, num_segments, plan=None):
        return _csc_segment_max(num_segments, self._meta(plan), data,
                                self._children(plan), segment_ids)

    def edge_softmax(self, logits, values, segment_ids, num_segments,
                     plan=None):
        return _csc_edge_softmax(num_segments, self._meta(plan), logits,
                                 values, self._children(plan), segment_ids)


_BACKENDS: Dict[str, Callable[[], AggregationBackend]] = {}
_INSTANCES: Dict[str, AggregationBackend] = {}


def register_backend(name: str, factory: Callable[[], AggregationBackend]):
    _BACKENDS[name] = factory
    _INSTANCES.pop(name, None)


register_backend("reference", ReferenceBackend)
register_backend("csc", CSCBackend)


def default_backend_name() -> str:
    """The platform's Sum-stage backend: the kernels on a TPU, the jnp
    reference ops elsewhere (where the kernels would only interpret)."""
    return "csc" if jax.default_backend() == "tpu" else "reference"


def get_backend(backend: Union[None, str, AggregationBackend]
                ) -> AggregationBackend:
    """Resolve a backend name (or pass an instance through); ``None`` is
    the platform's default (:func:`default_backend_name`)."""
    if backend is None:
        backend = default_backend_name()
    if isinstance(backend, AggregationBackend):
        return backend
    if backend not in _BACKENDS:
        raise ValueError(f"unknown aggregation backend {backend!r}; "
                         f"registered: {sorted(_BACKENDS)}")
    if backend not in _INSTANCES:
        _INSTANCES[backend] = _BACKENDS[backend]()
    return _INSTANCES[backend]


def backend_name(model) -> str:
    """The resolved backend name of a model (its ``aggregate_backend``,
    or the platform default when it names none)."""
    return get_backend(getattr(model, "aggregate_backend", None)).name


# ---------------------------------------------------------------------------
# the one combine implementation
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShardContext:
    """Halo hooks for finalizing shard-local partial aggregates.

    ``reduce(arr, op)`` maps mirror-slot partials (n_mirror, ...) to
    master-aligned values (n_master, ...); ``bcast(arr)`` maps master
    values back onto mirror slots. Together they are the paper's
    mirror→master reduce and master→mirror broadcast phases.
    """
    n_master: int
    reduce: Callable[[Any, str], Any]
    bcast: Callable[[Any], Any]


def _finalize(partial, shard: Optional[ShardContext], op: str):
    """Local partials over [masters ; mirrors] -> per-master totals."""
    if shard is None:
        return partial
    local, mirrored = partial[:shard.n_master], partial[shard.n_master:]
    if op == "sum":
        return local + shard.reduce(mirrored, "sum")
    return jnp.maximum(local, shard.reduce(mirrored, "max"))


def combine(mode: str, msg, dst, num_segments: int, edge_mask,
            backend: Union[None, str, AggregationBackend] = None,
            plan: Optional[CSCPlan] = None,
            shard: Optional[ShardContext] = None):
    """The Sum stage: per-destination aggregation of edge messages.

    msg["value"]: (E, H, D); msg["logit"]: (E, H) when the mode needs it;
    dst (E,) int; edge_mask (E,) float. Returns (num_segments, H, D) —
    or per-master totals (n_master, H, D) when ``shard`` is given and the
    arrays are shard-local (num_segments = n_master_pad + n_mirror_pad).
    """
    spec = combine_spec(mode)
    be = get_backend(backend)
    value = msg["value"]

    if spec.name == "softmax":
        logit = jnp.where(edge_mask[:, None] > 0, msg["logit"], NEG)
        masked_value = value * edge_mask[:, None, None]
        if shard is None:
            return be.edge_softmax(logit, masked_value, dst, num_segments,
                                   plan)
        # distributed segment-softmax: global max pass, then sum passes on
        # the shifted exponentials (both finalized through the halo)
        lmax = be.segment_max(logit, dst, num_segments, plan)
        lmax = jnp.maximum(lmax, NEG)                 # clamp empty (-inf)
        gmax_m = _finalize(lmax, shard, "max")
        gmax_all = jnp.concatenate([gmax_m, shard.bcast(gmax_m)], axis=0)
        ex = jnp.exp(logit - gmax_all[dst]) * edge_mask[:, None]
        den = _finalize(be.segment_sum(ex, dst, num_segments, plan),
                        shard, "sum")
        num = _finalize(be.segment_sum(ex[..., None] * masked_value, dst,
                                       num_segments, plan), shard, "sum")
        return num / jnp.maximum(den, 1e-9)[..., None]

    if spec.name == "max":
        masked = jnp.where(edge_mask[:, None, None] > 0, value, NEG)
        agg = _finalize(be.segment_max(masked, dst, num_segments, plan),
                        shard, "max")
        # empty destinations aggregate to the identity (0), not -inf/NEG
        return jnp.where(agg > NEG / 2, agg, 0.0)

    total = _finalize(
        be.segment_sum(value * edge_mask[:, None, None], dst, num_segments,
                       plan), shard, "sum")
    if spec.name == "mean":
        deg = _finalize(be.segment_sum(edge_mask, dst, num_segments, plan),
                        shard, "sum")
        total = total / jnp.maximum(deg, 1e-9)[:, None, None]
    return total
