"""Graph storage: host-side global graph (CSR + CSC) and device GraphBlock.

The paper (§4.1) organizes outgoing edges in CSR and incoming edges in CSC
and stores node/edge values separately; we mirror that. ``Graph`` is the
host/numpy global graph (the distributed store); ``GraphBlock`` is the
fixed-shape jnp view a JIT-compiled step consumes (whole graph, a k-hop
subgraph, or one partition's shard).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import numpy as np


@dataclass
class Graph:
    """Global directed graph. For undirected inputs both directions exist."""
    src: np.ndarray                  # (M,) int32
    dst: np.ndarray                  # (M,) int32
    num_nodes: int
    node_features: np.ndarray        # (N, F) float32
    labels: np.ndarray               # (N,)  int32
    edge_features: Optional[np.ndarray] = None   # (M, Fe) float32
    edge_weights: Optional[np.ndarray] = None    # (M,)  float32
    train_mask: Optional[np.ndarray] = None      # (N,) bool
    val_mask: Optional[np.ndarray] = None
    test_mask: Optional[np.ndarray] = None
    name: str = "graph"
    # CSR/CSC built lazily
    _csr: Optional[tuple] = field(default=None, repr=False)
    _csc: Optional[tuple] = field(default=None, repr=False)
    # cached CSCPlans for the blocked aggregation kernels, keyed by
    # (n_pad, e_pad, block_n, block_e) — built once, shared by every view
    _csc_plans: dict = field(default_factory=dict, repr=False)
    # cached per-edge GCN norm + strategy-invariant base blocks (views
    # stamp their masks onto a shallow copy — see base_block below)
    _gcn_norm: Optional[np.ndarray] = field(default=None, repr=False)
    _base_blocks: dict = field(default_factory=dict, repr=False)

    @property
    def num_edges(self) -> int:
        return int(len(self.src))

    # --- CSR (outgoing) / CSC (incoming) ------------------------------------

    def csr(self):
        """(indptr, order) such that edges order[indptr[u]:indptr[u+1]]
        have src == u. ``order`` indexes into the edge arrays."""
        if self._csr is None:
            order = np.argsort(self.src, kind="stable").astype(np.int32)
            counts = np.bincount(self.src, minlength=self.num_nodes)
            indptr = np.zeros(self.num_nodes + 1, np.int64)
            np.cumsum(counts, out=indptr[1:])
            self._csr = (indptr, order)
        return self._csr

    def csc(self):
        if self._csc is None:
            order = np.argsort(self.dst, kind="stable").astype(np.int32)
            counts = np.bincount(self.dst, minlength=self.num_nodes)
            indptr = np.zeros(self.num_nodes + 1, np.int64)
            np.cumsum(counts, out=indptr[1:])
            self._csc = (indptr, order)
        return self._csc

    def out_degree(self) -> np.ndarray:
        return np.bincount(self.src, minlength=self.num_nodes)

    def in_degree(self) -> np.ndarray:
        return np.bincount(self.dst, minlength=self.num_nodes)

    def out_neighbors(self, u: int) -> np.ndarray:
        indptr, order = self.csr()
        return self.dst[order[indptr[u]:indptr[u + 1]]]

    def gcn_norm(self) -> np.ndarray:
        """Per-edge symmetric GCN normalization 1/sqrt(d_i d_j), the
        entries of D^-1/2 Â D^-1/2 (Kipf & Welling), with d the in-degree
        over this graph's own edges. GCN expects Â = A + I as edges: named
        datasets get their self-loops in ``api._resolve_graph``, and a
        caller-supplied graph is trusted as it stands, so nothing is added
        here for a loop that is not an edge. A node with no in-edges
        counts as degree 1, so its out-edges keep a finite weight. Cached
        — the edge set never changes, so every view/block of this graph
        shares one (M,) array (compact views gather slices of it per
        batch)."""
        if self._gcn_norm is None:
            deg = np.maximum(self.in_degree(), 1).astype(np.float64)
            self._gcn_norm = (
                1.0 / np.sqrt(deg[self.src] * deg[self.dst])).astype(
                np.float32)
        return self._gcn_norm

    def csc_plan(self, pad_nodes: int = 0, pad_edges: int = 0,
                 block_n: int = 128, block_e: int = 256):
        """Cached CSCPlan over the (padded) destination ids — the reused
        indexing of paper §4.2: every view/batch of this graph shares it
        (views change activity masks, never the edge layout)."""
        n_pad = max(pad_nodes, self.num_nodes)
        e_pad = max(pad_edges, self.num_edges)
        key = (n_pad, e_pad, block_n, block_e)
        if key not in self._csc_plans:
            from repro.kernels.ops import build_csc_plan
            ids = np.zeros(e_pad, np.int32)
            ids[: self.num_edges] = self.dst
            self._csc_plans[key] = build_csc_plan(ids, n_pad, block_n,
                                                  block_e)
        return self._csc_plans[key]

    def add_self_loops(self) -> "Graph":
        loops = np.arange(self.num_nodes, dtype=np.int32)
        src = np.concatenate([self.src, loops])
        dst = np.concatenate([self.dst, loops])
        ef = None
        if self.edge_features is not None:
            ef = np.concatenate(
                [self.edge_features,
                 np.zeros((self.num_nodes, self.edge_features.shape[1]),
                          self.edge_features.dtype)])
        ew = None
        if self.edge_weights is not None:
            ew = np.concatenate(
                [self.edge_weights, np.ones(self.num_nodes, np.float32)])
        return Graph(src.astype(np.int32), dst.astype(np.int32),
                     self.num_nodes, self.node_features, self.labels,
                     ef, ew, self.train_mask, self.val_mask, self.test_mask,
                     self.name + "+loops")


@dataclass
class GraphBlock:
    """Fixed-shape device view. All arrays are padded; masks mark validity.

    ``src``/``dst`` index into the node axis of ``x``. For a distributed
    shard the node axis is [masters ; mirrors] (see core/partition.py).
    Registered as a jax pytree (see bottom of file) so blocks pass through
    ``jit`` boundaries directly.
    """
    src: np.ndarray                 # (E_pad,) int32
    dst: np.ndarray                 # (E_pad,) int32
    edge_mask: np.ndarray           # (E_pad,) f32 1=valid
    node_mask: np.ndarray           # (N_pad,) f32 1=valid
    x: np.ndarray                   # (N_pad, F)
    y: np.ndarray                   # (N_pad,) int32
    loss_mask: np.ndarray           # (N_pad,) f32 — nodes contributing loss
    edge_weight: np.ndarray         # (E_pad,) f32 (e.g. GCN norm; 1s else)
    edge_attr: Optional[np.ndarray] = None     # (E_pad, Fe)
    # per-layer active sets (paper §4.2 "active status of nodes and edges");
    # shape (K, N_pad) / (K, E_pad); None = all valid entries active
    node_active: Optional[np.ndarray] = None
    edge_active: Optional[np.ndarray] = None
    # cached CSCPlan (repro.kernels.ops) for the "csc" aggregation backend;
    # None keeps the reference jnp segment ops
    csc_plan: Optional[object] = None

    @property
    def num_nodes_padded(self) -> int:
        return int(self.x.shape[0])

    @property
    def num_edges_padded(self) -> int:
        return int(self.src.shape[0])


def build_block(g: Graph, pad_nodes: int = 0, pad_edges: int = 0,
                loss_mask: Optional[np.ndarray] = None,
                gcn_norm: bool = True,
                csc_plan: bool = False) -> GraphBlock:
    """Whole-graph block (global-batch view). ``csc_plan=True`` attaches
    the graph's cached CSCPlan so the "csc" aggregation backend can run."""
    n, m = g.num_nodes, g.num_edges
    n_pad = max(pad_nodes, n)
    e_pad = max(pad_edges, m)
    src = np.zeros(e_pad, np.int32)
    dst = np.zeros(e_pad, np.int32)
    emask = np.zeros(e_pad, np.float32)
    src[:m], dst[:m], emask[:m] = g.src, g.dst, 1.0
    nmask = np.zeros(n_pad, np.float32)
    nmask[:n] = 1.0
    x = np.zeros((n_pad, g.node_features.shape[1]), np.float32)
    x[:n] = g.node_features
    y = np.zeros(n_pad, np.int32)
    y[:n] = g.labels
    lm = np.zeros(n_pad, np.float32)
    if loss_mask is None:
        loss_mask = (g.train_mask if g.train_mask is not None
                     else np.ones(n, bool))
    lm[:n] = loss_mask.astype(np.float32)
    ew = np.zeros(e_pad, np.float32)
    ew[:m] = g.gcn_norm() if gcn_norm else (
        g.edge_weights if g.edge_weights is not None else 1.0)
    ea = None
    if g.edge_features is not None:
        ea = np.zeros((e_pad, g.edge_features.shape[1]), np.float32)
        ea[:m] = g.edge_features
    plan = g.csc_plan(n_pad, e_pad) if csc_plan else None
    return GraphBlock(src, dst, emask, nmask, x, y, lm, ew, ea,
                      csc_plan=plan)


def base_block(g: Graph, gcn_norm: bool = True,
               csc_plan: bool = False) -> GraphBlock:
    """The strategy-invariant whole-graph block, cached per
    ``(gcn_norm, csc_plan)``: edge layout, features, labels and edge
    weights are identical across every view of one graph — only the loss
    mask and activity masks differ, and :meth:`GraphView.as_block` stamps
    those onto a shallow copy. Callers must treat the shared arrays as
    read-only."""
    key = (bool(gcn_norm), bool(csc_plan))
    if key not in g._base_blocks:
        g._base_blocks[key] = build_block(g, gcn_norm=gcn_norm,
                                          csc_plan=csc_plan)
    return g._base_blocks[key]


# ---------------------------------------------------------------------------
# pytree registration: GraphBlock flows through jit/grad as a container
# ---------------------------------------------------------------------------

_BLOCK_FIELDS = ("src", "dst", "edge_mask", "node_mask", "x", "y",
                 "loss_mask", "edge_weight", "edge_attr", "node_active",
                 "edge_active", "csc_plan")


def _block_flatten(b: GraphBlock):
    return tuple(getattr(b, f) for f in _BLOCK_FIELDS), None


def _block_unflatten(aux, children):
    return GraphBlock(*children)


try:
    import jax as _jax
    _jax.tree_util.register_pytree_node(GraphBlock, _block_flatten,
                                        _block_unflatten)
except ImportError:
    # numpy-only contexts: graph I/O works without jax, blocks just
    # aren't pytrees there
    pass  # lint: waive=src.silent-except
