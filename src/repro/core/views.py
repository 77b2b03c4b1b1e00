"""Vectorized view-construction engine (paper §2.3/§4.2 host path).

PR 4 made the device step compiled-once, which moved the per-step cost to
*host-side view construction* — the same batch-preparation bottleneck
DistDGL attacks with dedicated samplers. This module owns that path:

- :class:`GraphView` — "a light-weighted logic view of the global graph"
  (per-layer node/edge active masks + a loss mask), the unification all
  three training strategies reduce to.
- :class:`ViewBuilder` — builds views into a ring of *reusable*
  preallocated ``(K, N)``/``(K, E)`` mask buffers: repeated construction
  does zero fresh mask allocations. Single consumer; a view's arrays are
  valid until ``slots`` more views are built from the same builder.
- :class:`ClusterViewCache` — per-cluster member and halo node sets are
  precomputed **once** from the static clustering; each step's active set
  is composed by OR-ing the chosen clusters' cached sets, so the per-step
  ``np.isin`` membership scan and halo edge walks disappear. (Halo
  distributes over unions: grow(A∪B) = grow(A) ∪ grow(B), because an edge
  contributes exactly when its dst is inside — so the union of cached
  per-cluster halos IS the halo of the union, bit-exactly.)
- :class:`ViewStream` — an *indexable* strategy stream: view i is built
  from an RNG stream derived from (seed, i), so any worker can build any
  index and the result is order-stable regardless of scheduling. This is
  what the Trainer's multi-stream prefetch pool fans out over, and what
  makes the view cursor checkpointable (the RNG state IS the index).
- :class:`CompactView` — the relabeled sampled-subgraph form (DistDGL's
  compact blocks): local-id edge list over only the sampled nodes plus a
  local→global map and per-hop offsets, so per-view host work, bytes and
  device memory scale with the *view*, not the graph. Dense masks remain
  the bit-parity oracle (``CompactView.to_dense``).
- :class:`BucketSpec` / :class:`CompactBlockBuilder` — size-bucketed
  padding: compact blocks are padded to a small fixed menu of
  ``(n_pad, e_pad)`` shapes (per-bucket buffer rings), so a jitted step
  compiles at most once per bucket instead of once per view shape.

``cluster_view_recompute`` keeps the pre-cache per-step recompute as the
parity oracle and benchmark baseline.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, replace
from typing import Iterator, Optional, Sequence

import numpy as np

from repro.graph.csr import Graph, GraphBlock, base_block
from repro.utils.timing import annotate, recording, span
from repro.core.subgraph import (bfs_layers, bfs_layers_fresh,
                                 fill_khop_masks, stamped_in_edges)


# ---------------------------------------------------------------------------
# the view abstraction
# ---------------------------------------------------------------------------


@dataclass
class GraphView:
    graph: Graph
    K: int
    strategy: str
    node_active: Optional[np.ndarray]    # (K, N) f32 or None (=all)
    edge_active: Optional[np.ndarray]    # (K, M) f32 or None
    loss_mask: np.ndarray                # (N,) f32
    meta: dict

    def as_block(self, gcn_norm: bool = True,
                 csc_plan: bool = False) -> GraphBlock:
        """Stamp this view's loss/activity masks onto a shallow copy of
        the graph's cached strategy-invariant base block — features, edge
        layout, degree norms and (with ``csc_plan=True``) the CSCPlan are
        shared read-only across every view of one graph instead of being
        rebuilt (degree recompute included) per view."""
        base = base_block(self.graph, gcn_norm=gcn_norm, csc_plan=csc_plan)
        return replace(base,
                       loss_mask=(self.loss_mask > 0).astype(np.float32),
                       node_active=self.node_active,
                       edge_active=self.edge_active)

    _COUNT_KEYS = ("active_nodes", "active_edges", "targets")

    def active_counts(self) -> dict:
        """Builder-recorded counts from ``meta`` (O(1) — the logging path
        must not rescan (K, N)/(K, E) masks every call); hand-built views
        without the meta keys fall back to the mask scan."""
        m = self.meta
        if all(k in m for k in self._COUNT_KEYS):
            return {k: int(m[k]) for k in self._COUNT_KEYS}
        n_nodes = (self.graph.num_nodes if self.node_active is None
                   else int((self.node_active.max(axis=0) > 0).sum()))
        n_edges = (self.graph.num_edges if self.edge_active is None
                   else int((self.edge_active.max(axis=0) > 0).sum()))
        return {"active_nodes": n_nodes, "active_edges": n_edges,
                "targets": int((self.loss_mask > 0).sum())}

    def copy_masks(self) -> "GraphView":
        """Detach from any builder buffers (fresh mask arrays)."""
        return GraphView(
            self.graph, self.K, self.strategy,
            None if self.node_active is None else self.node_active.copy(),
            None if self.edge_active is None else self.edge_active.copy(),
            self.loss_mask.copy(), dict(self.meta))


# ---------------------------------------------------------------------------
# compact sampled-subgraph views (relabeled local-id blocks)
# ---------------------------------------------------------------------------


@dataclass
class CompactView:
    """A relabeled sampled subgraph — DistDGL-style compact block.

    ``nodes`` holds the sampled global ids in **hop order**: the hop-0
    targets first, then the nodes first reached at hop 1, etc.
    (``hop_offsets[d]`` = number of nodes within d hops; ``hop_offsets[K]``
    = all sampled nodes). Because BFS hop sets are nested, per-layer
    activity reduces to rank comparisons in local-id space::

        node active in layer k  <=>  local_id < hop_offsets[K-1-k]
        edge active in layer k  <=>  dst_local < hop_offsets[K-1-k]
                                  and src_local < hop_offsets[K-k]

    so no (K, N) or (K, E) array ever exists — host bytes and build time
    are O(view), not O(graph). Cluster views use a flat ordering with all
    offsets equal to n (every sampled node active in every layer).

    ``edge_ids`` maps local edges back to the global edge arrays (edge
    weights / GCN norms / attributes are *gathered*, never recomputed);
    ``src_local``/``dst_local`` are the relabeled CSC-sorted edge list
    (nondecreasing dst) the per-bucket CSCPlan is built from.
    """
    graph: Graph
    K: int
    strategy: str
    nodes: np.ndarray         # (n,) int64 global ids, hop-ordered
    hop_offsets: np.ndarray   # (K+1,) int64; hop_offsets[-1] == n
    src_local: np.ndarray     # (e,) int32
    dst_local: np.ndarray     # (e,) int32, nondecreasing
    edge_ids: np.ndarray      # (e,) int64 global edge ids
    loss_local: np.ndarray    # (n,) f32 loss mask in local id space
    meta: dict

    @property
    def num_nodes(self) -> int:
        return int(len(self.nodes))

    @property
    def num_edges(self) -> int:
        return int(len(self.edge_ids))

    def nbytes(self) -> int:
        """Host bytes this view owns — the compact-vs-dense memory model."""
        return int(self.nodes.nbytes + self.hop_offsets.nbytes
                   + self.src_local.nbytes + self.dst_local.nbytes
                   + self.edge_ids.nbytes + self.loss_local.nbytes)

    def layer_bounds(self, k: int) -> tuple:
        """(dst-side, src-side) local-id bounds of layer k."""
        off = self.hop_offsets
        return int(off[self.K - 1 - k]), int(off[self.K - k])

    def edge_layer_mask(self, k: int) -> np.ndarray:
        d_bound, s_bound = self.layer_bounds(k)
        return (self.dst_local < d_bound) & (self.src_local < s_bound)

    def active_counts(self) -> dict:
        return {"active_nodes": int(self.hop_offsets[self.K - 1]),
                "active_edges": self.num_edges,
                "targets": int((self.loss_local > 0).sum())}

    def copy_masks(self) -> "CompactView":
        """Detach (fresh arrays) — the ViewStream iterator contract."""
        return CompactView(self.graph, self.K, self.strategy,
                           self.nodes.copy(), self.hop_offsets.copy(),
                           self.src_local.copy(), self.dst_local.copy(),
                           self.edge_ids.copy(), self.loss_local.copy(),
                           dict(self.meta))

    def to_dense(self) -> GraphView:
        """Materialize the dense (K, N)/(K, E) mask view — the bit-parity
        bridge to the retained dense oracle path (tests assert this equals
        the dense builder's masks for the same stream index)."""
        g, K = self.graph, self.K
        na = np.zeros((K, g.num_nodes), np.float32)
        ea = np.zeros((K, g.num_edges), np.float32)
        for k in range(K):
            d_bound, _ = self.layer_bounds(k)
            na[k, self.nodes[:d_bound]] = 1.0
            ea[k, self.edge_ids[self.edge_layer_mask(k)]] = 1.0
        loss = np.zeros(g.num_nodes, np.float32)
        loss[self.nodes] = self.loss_local
        return GraphView(g, K, self.strategy, na, ea, loss,
                         dict(self.meta))

    def as_block(self, gcn_norm: bool = True, csc_plan: bool = False,
                 bucket: Optional[tuple] = None, block_n: int = 128,
                 block_e: int = 256) -> GraphBlock:
        """One-off padded block with fresh arrays; ``bucket`` is an
        ``(n_pad, e_pad)`` pair (None pads tight). Streamed training goes
        through :class:`CompactBlockBuilder` — per-bucket buffer rings and
        a shape-stable plan per bucket."""
        n_pad, e_pad = bucket or (max(1, self.num_nodes),
                                  max(1, self.num_edges))
        slot = _CompactSlot(self.graph, self.K, int(n_pad), int(e_pad))
        return _fill_compact_block(self, slot, gcn_norm, csc_plan,
                                   block_n, block_e)


def _ceil_pow2(x: int) -> int:
    return 1 << (max(1, int(x)) - 1).bit_length()


@dataclass(frozen=True)
class BucketSpec:
    """A small fixed menu of ``(n_pad, e_pad)`` padded shapes for compact
    blocks. A jitted step over bucketed blocks compiles at most once per
    bucket (shapes + CSCPlan geometry are pure functions of the bucket);
    :meth:`pick` returns the smallest bucket fitting a view and raises
    past the largest — the default ladder tops out at graph capacity, so
    only config-supplied specs can overflow."""
    shapes: tuple    # ((n_pad, e_pad), ...), kept sorted ascending

    def __post_init__(self):
        shapes = tuple(sorted({(int(n), int(e)) for n, e in self.shapes}))
        if not shapes:
            raise ValueError("BucketSpec needs at least one (n_pad, e_pad)")
        object.__setattr__(self, "shapes", shapes)

    @classmethod
    def for_graph(cls, g: Graph, levels: int = 4, n_min: int = 64,
                  e_min: int = 256) -> "BucketSpec":
        """Powers-of-two ladder from ``(n_min, e_min)`` up to graph
        capacity (halving per level): small batches trace small shapes,
        and the largest bucket always fits the worst-case view."""
        n_top = _ceil_pow2(max(n_min, g.num_nodes))
        e_top = _ceil_pow2(max(e_min, g.num_edges))
        return cls(tuple((max(n_min, n_top >> i), max(e_min, e_top >> i))
                         for i in range(max(1, int(levels)))))

    def __len__(self) -> int:
        return len(self.shapes)

    def pick(self, n: int, e: int) -> tuple:
        for shape in self.shapes:
            if shape[0] >= n and shape[1] >= e:
                return shape
        raise ValueError(
            f"view ({n} nodes, {e} edges) overflows every bucket "
            f"{list(self.shapes)} — supply a BucketSpec with a larger "
            f"(n_pad, e_pad)")


class _CompactSlot:
    """One bucket-shaped set of reusable block buffers. ``feature_dim``
    overrides the feature width when the staged ``x`` rows come from a
    source other than ``g.node_features`` (the serving embedding cache
    stages cached hidden-layer rows, whose width is the model's hidden
    dim, not the raw feature dim)."""

    def __init__(self, g: Graph, K: int, n_pad: int, e_pad: int,
                 feature_dim: Optional[int] = None):
        F = (g.node_features.shape[1] if feature_dim is None
             else int(feature_dim))
        self.src = np.zeros(e_pad, np.int32)
        self.dst = np.zeros(e_pad, np.int32)
        self.edge_mask = np.zeros(e_pad, np.float32)
        self.node_mask = np.zeros(n_pad, np.float32)
        self.x = np.zeros((n_pad, F), np.float32)
        self.y = np.zeros(n_pad, np.int32)
        self.loss = np.zeros(n_pad, np.float32)
        self.edge_weight = np.zeros(e_pad, np.float32)
        self.edge_attr = (np.zeros((e_pad, g.edge_features.shape[1]),
                                   np.float32)
                          if g.edge_features is not None else None)
        self.node_active = np.zeros((K, n_pad), np.float32)
        self.edge_active = np.zeros((K, e_pad), np.float32)


def _fill_compact_block(view: CompactView, slot: _CompactSlot,
                        gcn_norm: bool, csc_plan: bool, block_n: int,
                        block_e: int,
                        features: Optional[np.ndarray] = None
                        ) -> GraphBlock:
    """Gather the view's node/edge data into (zeroed) bucket-shaped
    buffers. Pad edges keep src = dst = 0 with edge_mask 0 — inert under
    every combine mode, exactly like the dense path's padding.
    ``features`` substitutes an alternate (N, D) per-node row source for
    ``g.node_features`` (the serving cache's embedding table)."""
    g, K = view.graph, view.K
    n, e = view.num_nodes, view.num_edges
    x_src = g.node_features if features is None else features
    slot.src.fill(0)
    slot.src[:e] = view.src_local
    slot.dst.fill(0)
    slot.dst[:e] = view.dst_local
    slot.edge_mask.fill(0.0)
    slot.edge_mask[:e] = 1.0
    slot.node_mask.fill(0.0)
    slot.node_mask[:n] = 1.0
    slot.x.fill(0.0)
    slot.x[:n] = x_src[view.nodes]
    slot.y.fill(0)
    slot.y[:n] = g.labels[view.nodes]
    slot.loss.fill(0.0)
    slot.loss[:n] = view.loss_local
    slot.edge_weight.fill(0.0)
    if gcn_norm:
        slot.edge_weight[:e] = g.gcn_norm()[view.edge_ids]
    elif g.edge_weights is not None:
        slot.edge_weight[:e] = g.edge_weights[view.edge_ids]
    else:
        slot.edge_weight[:e] = 1.0
    if slot.edge_attr is not None:
        slot.edge_attr.fill(0.0)
        slot.edge_attr[:e] = g.edge_features[view.edge_ids]
    slot.node_active.fill(0.0)
    slot.edge_active.fill(0.0)
    for k in range(K):
        d_bound, _ = view.layer_bounds(k)
        slot.node_active[k, :d_bound] = 1.0   # hop-ordered: a prefix
        slot.edge_active[k, :e][view.edge_layer_mask(k)] = 1.0
    plan = None
    if csc_plan:
        from repro.kernels.ops import build_bucket_csc_plan
        plan = build_bucket_csc_plan(view.dst_local, len(slot.node_mask),
                                     len(slot.edge_mask), block_n, block_e)
    return GraphBlock(slot.src, slot.dst, slot.edge_mask, slot.node_mask,
                      slot.x, slot.y, slot.loss, slot.edge_weight,
                      slot.edge_attr, node_active=slot.node_active,
                      edge_active=slot.edge_active, csc_plan=plan)


class CompactBlockBuilder:
    """Stages CompactViews into per-bucket rings of reusable padded block
    buffers — the compact analog of ViewBuilder's mask-buffer ring. Each
    touched bucket shape owns ``slots`` preallocated buffer sets, so
    steady-state staging does zero fresh O(bucket) allocations, and with
    ``csc_plan=True`` a bucket-shape-stable CSCPlan is built per view from
    the compact dst ids (host cost O(view)).

    A staged block's arrays alias ring memory and stay valid until
    ``slots`` more views land in the *same* bucket; consumers that hold
    blocks longer (e.g. across a prefetch queue) ``device_put`` them
    first **and block until the transfer completes** (under async
    dispatch the host->device copy may be deferred, and a later ring
    fill would race it). Dense GraphViews pass through :meth:`GraphView.as_block`
    unchanged (the full-graph shape acts as its own single bucket), so
    one trainer loop drives both paths for parity benches.
    """

    def __init__(self, g: Graph, K: int,
                 buckets: Optional[BucketSpec] = None, slots: int = 2,
                 gcn_norm: bool = True, csc_plan: bool = False,
                 block_n: int = 128, block_e: int = 256,
                 features: Optional[np.ndarray] = None):
        self.g = g
        self.K = int(K)
        # alternate per-node row source for block.x (the serving embedding
        # cache passes its table; updated in place, so the ref stays live)
        self.features = features
        self.buckets = buckets or BucketSpec.for_graph(g)
        self.slots = max(1, int(slots))
        self.gcn_norm = bool(gcn_norm)
        self.csc_plan = bool(csc_plan)
        self.block_n = int(block_n)
        self.block_e = int(block_e)
        self._rings: dict = {}     # (n_pad, e_pad) -> [_CompactSlot, ...]
        self._turns: dict = {}
        self.stages = 0
        # views too large for every configured bucket (degraded to an
        # escalation shape rather than crashing mid-training)
        self.overflows = 0
        self._warned_overflow = False

    def _pick(self, view) -> tuple:
        """The view's bucket — degrading gracefully on overflow: a view
        too large for every configured bucket escalates to a
        power-of-two shape covering it (capped at graph capacity). The
        escalated shape behaves as one extra bucket (compiles once,
        counted in ``overflows``, warned about once) instead of killing
        a long training run over one oversized cluster."""
        try:
            return self.buckets.pick(view.num_nodes, view.num_edges)
        except ValueError:
            self.overflows += 1
            if not self._warned_overflow:
                self._warned_overflow = True
                warnings.warn(
                    f"CompactView ({view.num_nodes} nodes, "
                    f"{view.num_edges} edges) overflows every bucket "
                    f"{list(self.buckets.shapes)}; escalating to a "
                    "power-of-two shape at most graph capacity. Supply "
                    "a BucketSpec with a larger top bucket to avoid the "
                    "extra compile.", RuntimeWarning, stacklevel=3)
            n = min(_ceil_pow2(view.num_nodes), self.g.num_nodes)
            e = min(_ceil_pow2(view.num_edges), self.g.num_edges)
            return (max(n, view.num_nodes), max(e, view.num_edges))

    def bucket_for(self, view) -> tuple:
        if isinstance(view, GraphView):   # dense: its own full-graph shape
            return (view.graph.num_nodes, view.graph.num_edges)
        return self._pick(view)

    def stage(self, view) -> GraphBlock:
        """The view's padded block (with its CSCPlan when ``csc_plan``),
        under a ``view.stage`` span whose counters, while a profiler
        session records, give the plan's lanes (what one Sum-stage pass
        walks) and the view's live edges."""
        with span("view.stage"):
            block = self._stage(view)
            if recording():
                plan = block.csc_plan
                annotate(plan_lanes=(0 if plan is None
                                     else int(plan.gather_idx.size)),
                         live_edges=(view.graph.num_edges
                                     if isinstance(view, GraphView)
                                     else view.num_edges))
            return block

    def _stage(self, view) -> GraphBlock:
        self.stages += 1
        if isinstance(view, GraphView):
            return view.as_block(gcn_norm=self.gcn_norm,
                                 csc_plan=self.csc_plan)
        shape = self._pick(view)
        ring = self._rings.setdefault(shape, [])
        if len(ring) < self.slots:
            fdim = (None if self.features is None
                    else self.features.shape[1])
            ring.append(_CompactSlot(self.g, self.K, *shape,
                                     feature_dim=fdim))
        turn = self._turns.get(shape, 0)
        self._turns[shape] = turn + 1
        return _fill_compact_block(view, ring[turn % len(ring)],
                                   self.gcn_norm, self.csc_plan,
                                   self.block_n, self.block_e,
                                   features=self.features)


# ---------------------------------------------------------------------------
# cluster-view cache
# ---------------------------------------------------------------------------


def cluster_view_recompute(g: Graph, clusters: np.ndarray,
                           chosen: np.ndarray, halo_hops: int,
                           train: np.ndarray):
    """The pre-cache per-step recompute: ``np.isin`` membership + halo
    edge walks. Kept as the parity oracle (tests assert the cached path
    is bit-exact against it) and as the ``view_build`` bench baseline.

    Returns (member bool(N), active bool(N), loss f32(N)).
    """
    member = np.isin(clusters, chosen)
    active = member.copy()
    for _ in range(halo_hops):
        # grow along incoming edges (neighbors feeding the members)
        grow = np.zeros(g.num_nodes, bool)
        inside = active[g.dst]
        grow[g.src[inside]] = True
        active |= grow
    loss = (member & train).astype(np.float32)
    if loss.sum() == 0:
        loss = member.astype(np.float32)
    return member, active, loss


class ClusterViewCache:
    """Static per-cluster node sets, computed once per clustering.

    ``members[c]`` — sorted member node ids of cluster c;
    ``halo[c]`` — sorted node ids of c's ``halo_hops``-grown active set.
    A step's active set over any chosen cluster subset is the union of the
    cached sets (halo distributes over unions — see module docstring), so
    composing a view costs O(Σ|halo(c)|), not O(N + E·halo_hops).
    """

    def __init__(self, g: Graph, clusters: np.ndarray, halo_hops: int = 0):
        from repro.core.clustering import cluster_members
        self.g = g
        self.clusters = np.asarray(clusters)
        self.halo_hops = int(halo_hops)
        self.num_clusters = int(self.clusters.max()) + 1
        self.members = cluster_members(self.clusters, self.num_clusters)
        self.halo = (self.members if self.halo_hops == 0
                     else self._grow_halos())

    def _grow_halos(self) -> list:
        """Per-cluster halo BFS over in-edges of the *frontier* only —
        the same CSR-segment expansion as ``bfs_layers`` — with a stamp
        array (last cluster to visit each node) standing in for a visited
        bitmap, so there is nothing to clear between clusters. Total work
        is O(Σ_c in-edges(halo_c)), NOT C full-edge scans per hop (the
        old recompute's cost, fatal at C ~ thousands)."""
        from repro.core.subgraph import _expand_frontier
        g, C = self.g, self.num_clusters
        indptr, order = g.csc()
        src = g.src
        stamp = np.full(g.num_nodes, -1, np.int64)
        halos = []
        for c in range(C):
            frontier = self.members[c]
            stamp[frontier] = c
            grown = [frontier]
            for _ in range(self.halo_hops):
                eidx = _expand_frontier(indptr, order, frontier, 0, None)
                if len(eidx) == 0:
                    break
                cand = src[eidx]
                fresh = np.unique(cand[stamp[cand] != c])
                if len(fresh) == 0:
                    break
                stamp[fresh] = c
                grown.append(fresh)
                frontier = fresh
            halos.append(np.unique(np.concatenate(grown))
                         if len(grown) > 1 else np.asarray(frontier))
        return halos

    def compose(self, chosen: Sequence[int], member_out: np.ndarray,
                active_out: np.ndarray) -> None:
        """OR the chosen clusters' cached sets into the caller's (N,) bool
        scratch buffers."""
        member_out.fill(False)
        member_out[np.concatenate([self.members[c] for c in chosen])] = True
        active_out.fill(False)
        active_out[np.concatenate([self.halo[c] for c in chosen])] = True


# ---------------------------------------------------------------------------
# the builder: reusable mask buffers
# ---------------------------------------------------------------------------


class _Slot:
    def __init__(self, K: int, N: int, E: int):
        self.node = np.zeros((K, N), np.float32)
        self.edge = np.zeros((K, E), np.float32)
        self.loss = np.zeros(N, np.float32)


class ViewBuilder:
    """Builds GraphViews into a ring of preallocated mask buffers.

    Repeated view construction does **zero** fresh ``(K, N)``/``(K, E)``
    allocations: each build rotates to the next slot and overwrites it.
    Consequently a built view's arrays alias builder memory and stay valid
    only until ``slots`` more views are built — the Trainer's pipeline
    consumes (shards + stages) each view before the ring wraps, and each
    prefetch worker owns a private builder. Callers that need detached
    views use :meth:`GraphView.copy_masks`.
    """

    def __init__(self, g: Graph, K: int, slots: int = 2,
                 compact: bool = False):
        self.g = g
        self.K = K
        self.compact = bool(compact)
        N, E = g.num_nodes, g.num_edges
        g.csc()     # no-op when cached; the prefetch pool materializes it
                    # before fan-out, direct users pay it here once
        if self.compact:
            # compact builds never touch dense (K, N)/(K, E) buffers —
            # don't allocate them (that O(K·N) footprint is the point)
            self._slots = []
        else:
            self._slots = [_Slot(K, N, E) for _ in range(max(1, slots))]
            # shared scratch (single consumer; never escapes into views)
            self._visited = np.zeros(N, bool)
            self._in_hop = np.zeros((K + 1, N), bool)
            self._member = np.zeros(N, bool)
            self._active = np.zeros(N, bool)
        self._turn = 0
        self.builds = 0
        # stamp / local-id scratch for the compact build paths, created on
        # first use (dense-only builders never pay for it)
        self._stamp: Optional[np.ndarray] = None
        self._g2l: Optional[np.ndarray] = None
        self._tick = 0
        # all-ones train fallback for graphs without a train_mask,
        # allocated once per builder instead of once per cluster build
        self._all_train: Optional[np.ndarray] = None

    def _train_mask(self, train: Optional[np.ndarray]) -> np.ndarray:
        if train is not None:
            return train
        if self.g.train_mask is not None:
            return self.g.train_mask
        if self._all_train is None:
            self._all_train = np.ones(self.g.num_nodes, bool)
        return self._all_train

    def _next_slot(self) -> _Slot:
        if not self._slots:
            raise RuntimeError(
                "this ViewBuilder was created compact=True and owns no "
                "dense mask buffers; use khop_compact/cluster_compact")
        slot = self._slots[self._turn % len(self._slots)]
        self._turn += 1
        self.builds += 1
        return slot

    def _compact_scratch(self):
        if self._stamp is None:
            self._stamp = np.full(self.g.num_nodes, -1, np.int64)
            self._g2l = np.zeros(self.g.num_nodes, np.int64)
        self._tick += 1
        return self._stamp, self._g2l, self._tick

    # -- mini-batch (k-hop BFS) views -----------------------------------------

    def khop_view(self, targets: np.ndarray, neighbor_cap: int = 0,
                  rng: Optional[np.random.Generator] = None) -> GraphView:
        """Vectorized :func:`repro.core.subgraph.khop_subgraph_view` into
        reused buffers; bit-exact with the allocating function."""
        slot = self._next_slot()
        hops, visited = bfs_layers(self.g, targets, self.K, neighbor_cap,
                                   rng, _visited_out=self._visited)
        fill_khop_masks(self.g, hops, self.K, slot.node, slot.edge,
                        in_hop=self._in_hop)
        slot.loss.fill(0.0)
        uniq = np.unique(targets)
        slot.loss[uniq] = 1.0
        # counts recorded at build time: active_counts() must never rescan
        # the (K, N)/(K, E) masks (layer 0 is the union across layers)
        return GraphView(self.g, self.K, "mini", slot.node, slot.edge,
                         slot.loss,
                         {"targets": int(len(uniq)),
                          "touched": int(visited.sum()),
                          "active_nodes": int(len(hops[self.K - 1])),
                          "active_edges": int(slot.edge[0].sum())})

    # -- cluster-batch views ---------------------------------------------------

    def cluster_view(self, chosen: np.ndarray, cache: ClusterViewCache,
                     train: Optional[np.ndarray] = None) -> GraphView:
        """Compose the chosen clusters' cached member/halo sets; bit-exact
        with :func:`cluster_view_recompute`."""
        g = self.g
        slot = self._next_slot()
        cache.compose(chosen, self._member, self._active)
        member, active = self._member, self._active
        slot.node[:] = active                    # (N,) bool -> (K, N) f32
        slot.edge[:] = active[g.src] & active[g.dst]
        train = self._train_mask(train)
        np.multiply(member, train, out=slot.loss, casting="unsafe")
        if not slot.loss.any():
            slot.loss[:] = member
        n_active = int(active.sum())
        return GraphView(g, self.K, "cluster", slot.node, slot.edge,
                         slot.loss,
                         {"clusters": [int(c) for c in chosen],
                          "members": int(member.sum()),
                          "active": n_active,
                          "active_nodes": n_active,
                          "active_edges": int(slot.edge[0].sum()),
                          "targets": int(slot.loss.sum())})

    # -- compact (relabeled sampled-subgraph) builds ---------------------------

    def khop_compact(self, targets: np.ndarray, neighbor_cap: int = 0,
                     rng: Optional[np.random.Generator] = None
                     ) -> CompactView:
        """The compact form of :meth:`khop_view`: hop-ordered relabeling
        straight from the fresh-per-hop frontier output — no (K, N) array
        exists at any point. Same-index parity with the dense builder is
        bit-exact (``CompactView.to_dense()``): both consume identical rng
        draws, so sampled node/edge sets match."""
        g, K = self.g, self.K
        stamp, g2l, tick = self._compact_scratch()
        fresh, _ = bfs_layers_fresh(g, targets, K, neighbor_cap, rng,
                                    stamp=stamp, stamp_val=tick)
        self.builds += 1
        offsets = np.cumsum([len(f) for f in fresh]).astype(np.int64)
        nodes = np.concatenate(fresh)
        n = int(offsets[-1])
        g2l[nodes] = np.arange(n)
        # edges: ALL in-edges of nodes within K-1 hops whose src was
        # visited (with a neighbor cap, unsampled in-neighbors stay out —
        # matching the dense masks' semantics), CSC-sorted by local dst
        eidx = stamped_in_edges(g, nodes[:int(offsets[K - 1])], stamp, tick)
        src_local = g2l[g.src[eidx]].astype(np.int32)
        dst_local = g2l[g.dst[eidx]].astype(np.int32)
        sorter = np.argsort(dst_local, kind="stable")
        loss_local = np.zeros(n, np.float32)
        loss_local[:int(offsets[0])] = 1.0    # hop 0 = the unique targets
        return CompactView(
            g, K, "mini", nodes, offsets, src_local[sorter],
            dst_local[sorter], eidx[sorter].astype(np.int64), loss_local,
            {"targets": int(offsets[0]), "touched": n,
             "active_nodes": int(offsets[K - 1]),
             "active_edges": int(len(eidx))})

    def cluster_compact(self, chosen: np.ndarray, cache: ClusterViewCache,
                        train: Optional[np.ndarray] = None) -> CompactView:
        """The compact form of :meth:`cluster_view`: the active set is the
        union of the chosen clusters' cached halo sets, edges are the
        in-edges of that set with both endpoints inside — O(view), never a
        full-edge scan. All hop offsets equal n (every active node is
        active in every layer, matching the dense broadcast)."""
        g, K = self.g, self.K
        stamp, g2l, tick = self._compact_scratch()
        members = np.unique(np.concatenate(
            [cache.members[c] for c in chosen])).astype(np.int64)
        nodes = (members if cache.halo_hops == 0 else np.unique(
            np.concatenate([cache.halo[c] for c in chosen])).astype(
                np.int64))
        self.builds += 1
        n = len(nodes)
        stamp[nodes] = tick
        g2l[nodes] = np.arange(n)
        eidx = stamped_in_edges(g, nodes, stamp, tick)
        src_local = g2l[g.src[eidx]].astype(np.int32)
        dst_local = g2l[g.dst[eidx]].astype(np.int32)
        sorter = np.argsort(dst_local, kind="stable")
        train = self._train_mask(train)
        labeled = members[train[members]]
        if len(labeled) == 0:
            labeled = members
        loss_local = np.zeros(n, np.float32)
        loss_local[g2l[labeled]] = 1.0
        return CompactView(
            g, K, "cluster", nodes, np.full(K + 1, n, np.int64),
            src_local[sorter], dst_local[sorter],
            eidx[sorter].astype(np.int64), loss_local,
            {"clusters": [int(c) for c in chosen],
             "members": int(len(members)), "active": n,
             "active_nodes": n, "active_edges": int(len(eidx)),
             "targets": int(len(labeled))})


# ---------------------------------------------------------------------------
# indexable strategy streams (per-index RNG -> order-stable parallel builds)
# ---------------------------------------------------------------------------


class ViewStream:
    """An indexable stream of GraphViews: ``build(i)`` is a pure function
    of the index (per-view RNG streams derived from ``(seed, i)``), so

    - the Trainer's multi-stream prefetch pool can build views on any
      worker in any order and emit them in index order, bit-identically to
      sequential construction, and
    - the stream position is a single checkpointable integer
      (``cursor``) — ``Trainer.restore`` fast-forwards with ``seek``.

    Also a plain iterator (``next`` builds at ``cursor`` and advances) —
    iterator consumers receive *detached* views (fresh mask arrays, the
    old generator contract), so buffering several is safe. Zero-copy
    buffer-ring access is the ``build(i, builder)`` path the Trainer's
    prefetch pool uses, where each view is consumed before its slot is
    rebuilt.
    """

    strategy = "?"
    compact = False   # mini/cluster streams flip this to yield CompactViews

    def __init__(self, g: Graph, K: int, seed: int = 0,
                 length: Optional[int] = None):
        self.g = g
        self.K = K
        self.seed = int(seed)
        self.length = length
        self.cursor = 0
        self._builder: Optional[ViewBuilder] = None

    # -- the indexable API -----------------------------------------------------

    def rng_for(self, i: int) -> np.random.Generator:
        """The order-stable per-view RNG stream."""
        return np.random.default_rng(
            np.random.SeedSequence(entropy=self.seed, spawn_key=(int(i),)))

    def build(self, i: int,
              builder: Optional[ViewBuilder] = None) -> GraphView:
        raise NotImplementedError

    def make_builder(self) -> Optional[ViewBuilder]:
        """A private ViewBuilder for one consumer thread (None when the
        stream needs no buffers — the static global view). Compact streams
        get builders without dense mask buffers."""
        return ViewBuilder(self.g, self.K, compact=self.compact)

    def seek(self, i: int) -> None:
        self.cursor = int(i)

    # -- iterator compatibility ------------------------------------------------

    def __iter__(self) -> Iterator[GraphView]:
        return self

    def __next__(self) -> GraphView:
        if self.length is not None and self.cursor >= self.length:
            raise StopIteration
        if self._builder is None:
            self._builder = self.make_builder()
        view = self.build(self.cursor, self._builder)
        self.cursor += 1
        if self._builder is not None:
            # detach from the builder's buffer ring (static streams have
            # no builder and must keep yielding the identical object)
            view = view.copy_masks()
        return view


class GlobalViewStream(ViewStream):
    """The static full-graph view — every index is the same object, so the
    Trainer's staging cache recognizes it and stages exactly once."""

    strategy = "global"

    def __init__(self, view: GraphView, length: Optional[int] = None):
        super().__init__(view.graph, view.K, seed=0, length=length)
        self._view = view

    def build(self, i: int, builder=None) -> GraphView:
        return self._view

    def make_builder(self) -> None:
        return None


class MiniBatchViewStream(ViewStream):
    """Random labeled targets + K-hop BFS active sets, one independent RNG
    stream per index."""

    strategy = "mini"

    def __init__(self, g: Graph, K: int, batch_nodes: int = 0,
                 neighbor_cap: int = 0, seed: int = 0,
                 length: Optional[int] = None, compact: bool = False):
        super().__init__(g, K, seed=seed, length=length)
        self.compact = bool(compact)
        self.labeled = np.where(g.train_mask if g.train_mask is not None
                                else np.ones(g.num_nodes, bool))[0]
        if len(self.labeled) == 0:
            raise ValueError(
                "mini-batch views: the graph has no labeled nodes "
                "(train_mask selects nothing) to sample batch targets from")
        self.batch_nodes = batch_nodes or max(1, len(self.labeled) // 100)
        self.neighbor_cap = neighbor_cap

    def build(self, i: int, builder: Optional[ViewBuilder] = None):
        rng = self.rng_for(i)
        targets = rng.choice(self.labeled,
                             size=min(self.batch_nodes, len(self.labeled)),
                             replace=False)
        builder = builder or self.make_builder()
        if self.compact:
            return builder.khop_compact(targets, self.neighbor_cap, rng)
        return builder.khop_view(targets, self.neighbor_cap, rng)


class ClusterViewStream(ViewStream):
    """Random cluster picks composed from one shared (read-only)
    ClusterViewCache, one independent RNG stream per index."""

    strategy = "cluster"

    def __init__(self, g: Graph, K: int, clusters: np.ndarray,
                 clusters_per_batch: int = 0, halo_hops: int = 0,
                 seed: int = 0, length: Optional[int] = None,
                 compact: bool = False):
        super().__init__(g, K, seed=seed, length=length)
        self.compact = bool(compact)
        self.cache = ClusterViewCache(g, clusters, halo_hops)
        C = self.cache.num_clusters
        self.clusters_per_batch = min(
            clusters_per_batch or max(1, C // 100), C)
        self.train = (g.train_mask if g.train_mask is not None
                      else np.ones(g.num_nodes, bool))

    def build(self, i: int, builder: Optional[ViewBuilder] = None):
        rng = self.rng_for(i)
        chosen = rng.choice(self.cache.num_clusters,
                            size=self.clusters_per_batch, replace=False)
        builder = builder or self.make_builder()
        if self.compact:
            return builder.cluster_compact(chosen, self.cache, self.train)
        return builder.cluster_view(chosen, self.cache, self.train)
