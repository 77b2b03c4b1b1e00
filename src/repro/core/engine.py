"""Hybrid-parallel distributed training engine (paper §1/§4.3).

Conventional GNN data-parallelism gives each worker a whole subgraph; the
paper instead computes **each batch by a group of workers jointly**: node
and edge tensors are partition-sharded, parameters are replicated, and each
NN-TGAR stage runs as a local compute + a master/mirror halo exchange. We
realize the worker group as a mesh axis (default ``"graph"``) and the halo
exchange as `lax.all_to_all` over a precomputed static plan inside
``shard_map``. Gradients of the replicated parameters are combined with
``psum`` — the paper's NN-Reduce stage.

Communication matches §4.1: a value moves only master→mirror (broadcast
phase) and partial aggregates move mirror→master (reduce phase); traffic is
O(#mirrors) per layer, not O(edges) — the paper's "local message bombing"
fix. Attention models (softmax combine) add a max- and a sum-reduce pass —
the distributed segment-softmax.

The per-shard Sum stage is the shared combine engine of
:mod:`repro.core.aggregate`: shard-local partial aggregates run through the
selected :class:`AggregationBackend` (``"reference"`` jnp segment ops or
the ``"csc"`` Pallas kernels over per-shard cached CSCPlans) and are
finalized through a :class:`ShardContext` wrapping the halo exchange.
The stacked plan arrays staged here (``csc_gather``/``csc_local``,
(P, nb, L) with identical padded shapes across shards) feed the kernels
directly as scalar-prefetch operands — the per-edge gather is fused into
the kernel grid, so no shard ever materializes a pre-gathered message
tensor.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.aggregate import (ShardContext, backend_name, combine,
                                   get_backend)
from repro.core.mpgnn import MPGNNModel
from repro.core.partition import ShardedGraph
from repro.core.tgar import TGARLayer, tree_take, NEG
from repro.kernels.ops import CSCPlan

Axis = str


# ---------------------------------------------------------------------------
# halo exchange primitives (run inside shard_map; arrays are per-device)
# ---------------------------------------------------------------------------


def _exchange(buf, axis: Axis):
    """buf (P, s_pad, D) -> (P, s_pad, D) with row q = what device q sent."""
    return jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                              tiled=False)


def _bcast_array(arr, send_idx, send_mask, recv_slot, recv_mask, n_mir_pad,
                 axis: Axis):
    """Master values (n_m_pad, ...) -> mirror buffer (n_mir_pad, ...)."""
    shape = arr.shape
    flat = arr.reshape(shape[0], -1)
    buf = flat[send_idx] * send_mask[..., None]          # (P, s_pad, D)
    got = _exchange(buf, axis)
    got = got * recv_mask[..., None]
    mir = jnp.zeros((n_mir_pad, flat.shape[1]), flat.dtype)
    mir = mir.at[recv_slot.reshape(-1)].add(
        got.reshape(-1, flat.shape[1]), mode="drop")
    return mir.reshape((n_mir_pad,) + shape[1:])


def _reduce_array(mir, send_idx, send_mask, recv_slot, recv_mask, n_m_pad,
                  axis: Axis, op: str = "sum"):
    """Mirror partials (n_mir_pad, ...) -> master accumulation (n_m_pad, ...)."""
    shape = mir.shape
    flat = mir.reshape(shape[0], -1)
    picked = flat[recv_slot]                              # (P, s_pad, D)
    if op == "sum":
        buf = picked * recv_mask[..., None]
    else:  # max
        buf = jnp.where(recv_mask[..., None] > 0, picked, NEG)
    got = _exchange(buf, axis)                            # rows by mirror holder
    D = flat.shape[1]
    if op == "sum":
        got = got * send_mask[..., None]
        out = jnp.zeros((n_m_pad, D), flat.dtype)
        out = out.at[send_idx.reshape(-1)].add(got.reshape(-1, D),
                                               mode="drop")
    else:
        got = jnp.where(send_mask[..., None] > 0, got, NEG)
        out = jnp.full((n_m_pad, D), NEG, flat.dtype)
        out = out.at[send_idx.reshape(-1)].max(got.reshape(-1, D),
                                               mode="drop")
    return out.reshape((n_m_pad,) + shape[1:])


def _bcast_tree(tree, shard, axis):
    f = lambda a: _bcast_array(a, shard["send_idx"], shard["send_mask"],
                               shard["recv_slot"], shard["recv_mask"],
                               shard["n_mir_pad"], axis)
    return jax.tree_util.tree_map(f, tree)


# ---------------------------------------------------------------------------
# distributed TGAR layer forward
# ---------------------------------------------------------------------------


def _layer_forward_sharded(layer: TGARLayer, lp, h, shard, k: int,
                           axis: Axis, backend=None):
    n_m_pad = shard["n_m_pad"]
    n_mir_pad = shard["n_mir_pad"]
    n_tot = n_m_pad + n_mir_pad
    src, dst = shard["src_local"], shard["dst_local"]
    em = shard["edge_mask"] * shard["edge_active"][k]

    # NN-T on masters, then master -> mirror halo broadcast (the paper's
    # "synchronize only the masters used": one value per mirror per layer)
    n = layer.transform(lp, h)
    n_mir = _bcast_tree(n, shard, axis)
    n_all = jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([a, b.astype(a.dtype)], axis=0),
        n, n_mir)

    # NN-G on local edges
    n_src = tree_take(n_all, src)
    n_dst = tree_take(n_all, dst)
    msg = layer.gather(lp, n_src, n_dst, shard["edge_attr"],
                       shard["edge_weight"], em)

    # Sum: shard-local partial aggregation (shared combine engine) +
    # mirror->master halo finalize via the exchange plan
    red = functools.partial(_reduce_array, send_idx=shard["send_idx"],
                            send_mask=shard["send_mask"],
                            recv_slot=shard["recv_slot"],
                            recv_mask=shard["recv_mask"],
                            n_m_pad=n_m_pad, axis=axis)
    ctx = ShardContext(
        n_master=n_m_pad,
        reduce=lambda arr, op: red(arr, op=op),
        bcast=lambda arr: _bcast_tree(arr, shard, axis))
    M = combine(layer.combine, msg, dst, n_tot, em, backend=backend,
                plan=shard.get("csc_plan"), shard=ctx)

    h_next = layer.apply(lp, h, M)
    h_next = h_next * shard["node_active"][k][:, None]
    return h_next * shard["master_mask"][:, None]


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------


class HybridParallelEngine:
    """Runs an MPGNNModel over a partitioned graph with a device group.

    Requires a mesh whose ``axis`` has exactly ``plan.P`` devices. The same
    engine serves training (``train_step``) and inference (``infer``) — the
    paper's unified implementation. ``backend`` selects the Sum-stage
    aggregation backend (defaults to the model's ``aggregate_backend``);
    with ``"csc"`` the per-shard CSCPlans are built once at staging time
    and reused by every batch/view — the paper's reused CSC indexing.
    """

    def __init__(self, model: MPGNNModel, sharded: ShardedGraph,
                 mesh: Optional[Mesh] = None, axis: Axis = "graph",
                 backend=None):
        self.model = model
        self.sg = sharded
        self.plan = sharded.plan
        self.axis = axis
        self.backend = get_backend(backend if backend is not None
                                   else backend_name(model))
        self._csc_meta = None
        if mesh is None:
            devs = np.array(jax.devices()[: self.plan.P])
            if devs.size < self.plan.P:
                raise ValueError(
                    f"need {self.plan.P} devices, have {len(jax.devices())}")
            mesh = Mesh(devs, (axis,))
        self.mesh = mesh
        self._device_data = self._stage()

    # -- data staging ---------------------------------------------------------

    def _stage(self):
        plan, sg = self.plan, self.sg
        shd = lambda a: jax.device_put(
            a, NamedSharding(self.mesh, P(self.axis)))
        data = {
            "masters": shd(plan.masters),
            "master_mask": shd(plan.master_mask),
            "src_local": shd(plan.src_local),
            "dst_local": shd(plan.dst_local),
            "edge_mask": shd(plan.edge_mask),
            "send_idx": shd(plan.send_idx),
            "send_mask": shd(plan.send_mask),
            "recv_slot": shd(plan.recv_slot),
            "recv_mask": shd(plan.recv_mask),
            "x": shd(sg.x),
            "y": shd(sg.y),
            "edge_weight": shd(sg.edge_weight),
        }
        if sg.edge_attr is not None:
            data["edge_attr"] = shd(sg.edge_attr)
        if self.backend.name == "csc":
            plans = plan.csc_plans()
            self._csc_meta = plans[0]
            data["csc_gather"] = shd(np.stack(
                [p.gather_idx for p in plans]))
            data["csc_local"] = shd(np.stack(
                [p.local_ids for p in plans]))
            # the plans' inverse maps: per-shard (E_pad,) destination
            # rows, scalar-prefetched by the fused backward kernels so
            # the sharded grad path never falls back to g[ids] gathers
            data["csc_dst"] = shd(np.stack(
                [p.edge_dst for p in plans]))
        return data

    def stage_view(self, view_arrays: dict, retry=None):
        """Stage sharded view arrays onto the device mesh. With a
        :class:`repro.runtime.faults.Retrier`, the device_put batch is a
        retryable ``device_put`` stage — transfers are idempotent (host
        arrays are unchanged by a failed put), so a transient staging
        failure re-stages the same view."""
        shd = lambda a: jax.device_put(
            a, NamedSharding(self.mesh, P(self.axis)))

        def put():
            return {k: shd(v) for k, v in view_arrays.items()}

        if retry is None:
            return put()
        return retry("device_put", put)

    def default_view_arrays(self):
        plan = self.plan
        K = self.model.K
        return {
            "node_active": np.broadcast_to(
                plan.master_mask[:, None, :],
                (plan.P, K, plan.n_m_pad)).copy(),
            "edge_active": np.broadcast_to(
                plan.edge_mask[:, None, :],
                (plan.P, K, plan.e_pad)).copy(),
            "loss_mask": plan.master_mask.copy(),
        }

    # -- shard-local forward ----------------------------------------------------

    def _local_shard(self, data, view):
        """Squeeze the leading (1-sized) partition axis of shard blocks."""
        sq = lambda a: a[0]
        shard = {k: sq(v) for k, v in data.items()}
        shard.update({k: sq(v) for k, v in view.items()})
        shard["n_m_pad"] = self.plan.n_m_pad
        shard["n_mir_pad"] = self.plan.n_mir_pad
        if "edge_attr" not in shard:
            shard["edge_attr"] = None
        if "csc_gather" in shard:
            meta = self._csc_meta
            shard["csc_plan"] = CSCPlan(
                shard.pop("csc_gather"), shard.pop("csc_local"),
                shard.pop("csc_dst"),
                meta.num_blocks, meta.block_n, meta.block_e,
                meta.num_segments, meta.num_edges)
        return shard

    def _forward_local(self, params, shard):
        h = shard["x"]
        for k, layer in enumerate(self.model.layers):
            h = _layer_forward_sharded(layer, params["layers"][k], h,
                                       shard, k, self.axis,
                                       backend=self.backend)
        return self.model.decode(params, h)

    def _local_objective(self, params, shard):
        """Local loss contribution / global target count (see DESIGN.md:
        grads of the replicated params are psum'd by the caller — the
        paper's NN-Reduce)."""
        logits = self._forward_local(params, shard)
        lm = shard["loss_mask"] * shard["master_mask"]
        logits32 = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits32, axis=-1)
        ll = jnp.take_along_axis(logits32, shard["y"][:, None], axis=-1)[:, 0]
        nll = (logz - ll) * lm
        local_sum = jnp.sum(nll)
        count = jnp.sum(lm)
        total = jax.lax.psum(count, self.axis)
        return local_sum / jnp.maximum(total, 1.0)

    # -- public API ---------------------------------------------------------------

    def make_loss_and_grad(self):
        specs_data = {k: P(self.axis) for k in self._device_data}
        specs_view = {k: P(self.axis)
                      for k in ("node_active", "edge_active", "loss_mask")}

        @functools.partial(
            jax.jit,
            static_argnames=())
        def fn(params, data, view):
            def shard_fn(params, data, view):
                shard = self._local_shard(data, view)
                obj, grads = jax.value_and_grad(self._local_objective)(
                    params, shard)
                loss = jax.lax.psum(obj, self.axis)
                grads = jax.lax.psum(grads, self.axis)
                return loss, grads

            return jax.shard_map(
                shard_fn, mesh=self.mesh,
                in_specs=(P(), specs_data, specs_view),
                out_specs=(P(), P()), check_vma=False,
            )(params, data, view)

        return fn

    def make_train_step(self, opt):
        lg = self.make_loss_and_grad()

        @jax.jit
        def step(params, opt_state, data, view):
            loss, grads = lg(params, data, view)
            new_params, new_state = opt.update(grads, opt_state, params)
            return new_params, new_state, loss

        def run(params, opt_state, view_arrays):
            view = self.stage_view(view_arrays)
            return step(params, opt_state, self._device_data, view)

        return run

    def make_infer(self, on_trace: Optional[Callable[[], None]] = None):
        specs_data = {k: P(self.axis) for k in self._device_data}
        specs_view = {k: P(self.axis)
                      for k in ("node_active", "edge_active", "loss_mask")}

        # jit the shard_map closure ONCE (like make_loss_and_grad): every
        # call used to re-trace the whole distributed forward.
        # ``on_trace`` runs as a Python side effect of tracing only — the
        # Trainer uses it as a compile counter (retrace = contract breach).
        @jax.jit
        def infer_jit(params, data, view):
            if on_trace is not None:
                on_trace()

            def shard_fn(params, data, view):
                shard = self._local_shard(data, view)
                logits = self._forward_local(params, shard)
                return logits[None]

            return jax.shard_map(
                shard_fn, mesh=self.mesh,
                in_specs=(P(), specs_data, specs_view),
                out_specs=P(self.axis), check_vma=False,
            )(params, data, view)

        def fn(params, view_arrays):
            view = self.stage_view(view_arrays)
            # (P, n_m_pad, C) aligned with plan.masters
            return infer_jit(params, self._device_data, view)

        # the jitted core is exposed so repro.analysis can trace the
        # actual compiled computation (fn itself stages host arrays)
        fn.jitted = infer_jit
        return fn

    def gather_predictions(self, logits_sharded) -> np.ndarray:
        """(P, n_m_pad, C) -> (N, C) in global node order: one masked
        scatter over all partitions (valid master slots land on their
        global node row; padding slots drop out with the mask)."""
        plan = self.plan
        lg = np.asarray(logits_sharded)
        out = np.zeros((len(plan.owner), lg.shape[-1]), np.float32)
        valid = plan.master_mask > 0                      # (P, n_m_pad)
        out[plan.masters[valid]] = lg[valid]
        return out
