"""Distributed graph representation (paper §4.1).

Nodes are distributed evenly; each edge is assigned to one partition; a
node owned elsewhere but referenced locally becomes a **mirror** — a
placeholder holding *no values* (the paper's replica-factor-1 claim): the
halo exchange materializes a compact ``(n_mirror, d)`` buffer per layer,
synchronizing only the masters a layer actually uses.

Partitioning methods (§5.4):
- ``1d_src`` (default) — edge goes to the owner of its source node (master
  node and all its out-edges colocated: edge attributes/attention local).
- ``1d_dst`` — by destination owner.
- ``vertex_cut`` — 2D grid hash over (src, dst) (PowerGraph-style), which
  balances edges on skewed graphs at the cost of replication.

The exchange plan is precomputed dense numpy (static shapes for JIT):
``send_idx[p, q, i]`` = local master slot on p of the i-th value p sends to
q; ``recv_slot[q, p, i]`` = the mirror slot on q where it lands. The engine
executes the plan with ``lax.all_to_all`` inside ``shard_map``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from repro.graph.csr import Graph


def _round_up(x: int, m: int = 8) -> int:
    return max(m, ((x + m - 1) // m) * m)


@dataclass
class PartitionPlan:
    P: int
    method: str
    owner: np.ndarray                 # (N,) int32 node -> partition
    masters: np.ndarray               # (P, n_m_pad) int32 global node ids
    master_mask: np.ndarray           # (P, n_m_pad) f32
    mirrors: np.ndarray               # (P, n_mir_pad) int32 global node ids
    mirror_mask: np.ndarray           # (P, n_mir_pad) f32
    src_local: np.ndarray             # (P, e_pad) int32 into [masters;mirrors]
    dst_local: np.ndarray             # (P, e_pad) int32
    edge_mask: np.ndarray             # (P, e_pad) f32
    edge_orig: np.ndarray             # (P, e_pad) int32 global edge ids
    send_idx: np.ndarray              # (P, P, s_pad) int32 master slots
    send_mask: np.ndarray             # (P, P, s_pad) f32
    recv_slot: np.ndarray             # (P, P, s_pad) int32 mirror slots
    recv_mask: np.ndarray             # (P, P, s_pad) f32
    # per-shard CSCPlans for the "csc" aggregation backend, cached by
    # (block_n, block_e) — built once per partitioning, reused by every
    # batch/view the engine stages (paper §4.2 reused indexing)
    _csc_plans: dict = field(default_factory=dict, repr=False)
    # cached inverse maps (global id -> local slot), built on first use by
    # the compact shard path (shard_view over CompactView scatters a few
    # thousand ids instead of gathering all N / all E per step)
    _locators: dict = field(default_factory=dict, repr=False)

    @property
    def n_m_pad(self) -> int:
        return int(self.masters.shape[1])

    @property
    def n_mir_pad(self) -> int:
        return int(self.mirrors.shape[1])

    @property
    def e_pad(self) -> int:
        return int(self.src_local.shape[1])

    @property
    def s_pad(self) -> int:
        return int(self.send_idx.shape[2])

    def csc_plans(self, block_n: int = 128, block_e: int = 256):
        """One CSCPlan per partition over its local destination ids
        (segments = the shard's [masters ; mirrors] axis), all with
        identical padded shapes so the engine can stack them (P,
        n_chunks, BE) and shard them over the worker group. The stacked index arrays
        are exactly what the fused-gather kernels scalar-prefetch — the
        shard's raw edge messages are never re-laid-out on device."""
        key = (block_n, block_e)
        if key not in self._csc_plans:
            from repro.kernels.ops import build_csc_plans_stacked
            n_tot = self.n_m_pad + self.n_mir_pad
            self._csc_plans[key] = build_csc_plans_stacked(
                self.dst_local, n_tot, block_n, block_e)
        return self._csc_plans[key]

    def node_locator(self) -> np.ndarray:
        """(N,) int64: master slot of each global node on its owner
        partition (``masters[owner[v], node_locator()[v]] == v``)."""
        if "node" not in self._locators:
            valid = self.master_mask > 0
            cols = np.broadcast_to(
                np.arange(self.n_m_pad, dtype=np.int64),
                self.masters.shape)
            slot = np.zeros(int(self.masters.max()) + 1, np.int64)
            slot[self.masters[valid].astype(np.int64)] = cols[valid]
            self._locators["node"] = slot
        return self._locators["node"]

    def edge_locator(self):
        """(part, slot): for each global edge id, its partition and edge
        slot there (``edge_orig[part[e], slot[e]] == e``)."""
        if "edge" not in self._locators:
            valid = self.edge_mask > 0
            M = int(self.edge_orig[valid].max()) + 1 if valid.any() else 1
            part = np.zeros(M, np.int64)
            slot = np.zeros(M, np.int64)
            rows = np.broadcast_to(
                np.arange(self.P, dtype=np.int64)[:, None],
                self.edge_orig.shape)
            cols = np.broadcast_to(
                np.arange(self.e_pad, dtype=np.int64),
                self.edge_orig.shape)
            ids = self.edge_orig[valid].astype(np.int64)
            part[ids] = rows[valid]
            slot[ids] = cols[valid]
            self._locators["edge"] = (part, slot)
        return self._locators["edge"]


@dataclass
class ShardedGraph:
    """Per-partition node/edge data, stacked over the partition axis."""
    plan: PartitionPlan
    x: np.ndarray                     # (P, n_m_pad, F)
    y: np.ndarray                     # (P, n_m_pad) int32
    edge_weight: np.ndarray           # (P, e_pad) f32
    edge_attr: Optional[np.ndarray]   # (P, e_pad, Fe) or None
    feature_dim: int


def build_partitions(g: Graph, P: int, method: str = "1d_src",
                     seed: int = 0, gcn_norm: bool = True
                     ) -> ShardedGraph:
    rng = np.random.default_rng(seed)
    N, M = g.num_nodes, g.num_edges

    # ---- master assignment: even split of a shuffled permutation ----------
    perm = rng.permutation(N)
    owner = np.empty(N, np.int32)
    owner[perm] = np.arange(N) % P

    # ---- edge assignment ----------------------------------------------------
    if method == "1d_src":
        e_part = owner[g.src]
    elif method == "1d_dst":
        e_part = owner[g.dst]
    elif method == "vertex_cut":
        r = int(np.floor(np.sqrt(P)))
        while P % r:
            r -= 1
        c = P // r
        hs = (g.src.astype(np.int64) * 2654435761 % (1 << 31)) % r
        hd = (g.dst.astype(np.int64) * 40503 % (1 << 31)) % c
        e_part = (hs * c + hd).astype(np.int32)
    else:
        raise ValueError(f"unknown partition method {method!r}")

    # ---- per-partition locals ----------------------------------------------
    masters_l, mirrors_l, edges_l = [], [], []
    for p in range(P):
        m_nodes = np.where(owner == p)[0].astype(np.int64)
        eids = np.where(e_part == p)[0].astype(np.int64)
        endpoints = np.unique(np.concatenate([g.src[eids], g.dst[eids]]))
        mir = endpoints[owner[endpoints] != p]
        masters_l.append(m_nodes)
        mirrors_l.append(np.sort(mir))
        edges_l.append(eids)

    n_m_pad = _round_up(max(len(m) for m in masters_l))
    n_mir_pad = _round_up(max((len(m) for m in mirrors_l), default=1))
    e_pad = _round_up(max(len(e) for e in edges_l))

    masters = np.zeros((P, n_m_pad), np.int32)
    master_mask = np.zeros((P, n_m_pad), np.float32)
    mirrors = np.zeros((P, n_mir_pad), np.int32)
    mirror_mask = np.zeros((P, n_mir_pad), np.float32)
    src_local = np.zeros((P, e_pad), np.int32)
    dst_local = np.zeros((P, e_pad), np.int32)
    edge_mask = np.zeros((P, e_pad), np.float32)
    edge_orig = np.zeros((P, e_pad), np.int32)

    master_slot = {}   # global id -> (p, slot)
    mirror_slot = {}
    for p in range(P):
        ml, rl = masters_l[p], mirrors_l[p]
        masters[p, :len(ml)] = ml
        master_mask[p, :len(ml)] = 1.0
        mirrors[p, :len(rl)] = rl
        mirror_mask[p, :len(rl)] = 1.0
        for i, nid in enumerate(ml):
            master_slot[(p, int(nid))] = i
        for i, nid in enumerate(rl):
            mirror_slot[(p, int(nid))] = i
        eids = edges_l[p]
        loc = np.empty(N, np.int64)   # scratch local index map for p
        loc[ml] = np.arange(len(ml))
        loc[rl] = n_m_pad + np.arange(len(rl))
        src_local[p, :len(eids)] = loc[g.src[eids]]
        dst_local[p, :len(eids)] = loc[g.dst[eids]]
        edge_mask[p, :len(eids)] = 1.0
        edge_orig[p, :len(eids)] = eids

    # ---- exchange plan: owner p -> mirror holder q ---------------------------
    pair_sends = {}
    for q in range(P):
        for nid in mirrors_l[q]:
            p = int(owner[nid])
            pair_sends.setdefault((p, q), []).append(int(nid))
    s_pad = _round_up(max((len(v) for v in pair_sends.values()), default=1))
    send_idx = np.zeros((P, P, s_pad), np.int32)
    send_mask = np.zeros((P, P, s_pad), np.float32)
    recv_slot = np.zeros((P, P, s_pad), np.int32)
    recv_mask = np.zeros((P, P, s_pad), np.float32)
    for (p, q), nids in pair_sends.items():
        for i, nid in enumerate(nids):
            send_idx[p, q, i] = master_slot[(p, nid)]
            send_mask[p, q, i] = 1.0
            recv_slot[q, p, i] = mirror_slot[(q, nid)]
            recv_mask[q, p, i] = 1.0

    plan = PartitionPlan(P, method, owner, masters, master_mask, mirrors,
                         mirror_mask, src_local, dst_local, edge_mask,
                         edge_orig, send_idx, send_mask, recv_slot, recv_mask)

    # ---- node/edge data sliced per partition ---------------------------------
    F = g.node_features.shape[1]
    x = np.zeros((P, n_m_pad, F), np.float32)
    y = np.zeros((P, n_m_pad), np.int32)
    for p in range(P):
        x[p] = g.node_features[masters[p]] * master_mask[p][:, None]
        y[p] = g.labels[masters[p]] * master_mask[p].astype(np.int32)
    ew = np.zeros((P, e_pad), np.float32)
    norm = g.gcn_norm() if gcn_norm else (
        g.edge_weights if g.edge_weights is not None
        else np.ones(M, np.float32))
    ea = None
    if g.edge_features is not None:
        ea = np.zeros((P, e_pad, g.edge_features.shape[1]), np.float32)
    for p in range(P):
        k = int(plan.edge_mask[p].sum())
        eids = edges_l[p]
        ew[p, :k] = norm[eids]
        if ea is not None:
            ea[p, :k] = g.edge_features[eids]
    return ShardedGraph(plan, x, y, ew, ea, F)


def partition_stats(sg: ShardedGraph) -> dict:
    """Metrics the paper reports for partitioning methods (Fig. 10, §4.1)."""
    plan = sg.plan
    n_masters = plan.master_mask.sum(axis=1)
    n_mirrors = plan.mirror_mask.sum(axis=1)
    n_edges = plan.edge_mask.sum(axis=1)
    comm = plan.send_mask.sum()          # values moved per broadcast phase
    total_nodes = float(n_masters.sum())
    return {
        "method": plan.method,
        "P": plan.P,
        "replica_factor": float((n_masters.sum() + n_mirrors.sum())
                                / max(total_nodes, 1)),
        "edge_balance": float(n_edges.max() / max(n_edges.mean(), 1e-9)),
        "master_balance": float(n_masters.max()
                                / max(n_masters.mean(), 1e-9)),
        "halo_values_per_sync": float(comm),
        "mirrors_total": float(n_mirrors.sum()),
        "edges_per_part_max": float(n_edges.max()),
        "memory_per_part_nodes": float(n_masters.max() + n_mirrors.max()),
    }
