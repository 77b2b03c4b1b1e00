"""Kernel micro-benchmarks: Pallas (interpret) vs jnp oracle — plus the
end-to-end Sum-stage benchmark over the aggregation backends.

On this CPU container interpret-mode timings measure the Python emulation,
not TPU performance — the CSV documents call latency + the (shape, VMEM)
choices; TPU timing comes from running the same ops on hardware. The
``aggregate`` bench additionally writes BENCH_aggregate.json so successive
PRs can track the hot path (paper Fig. A3: 76% of runtime) end to end.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_call
from repro.analysis import (JaxprContext, check_or_raise,
                            count_segment_scatters, run_rules)
from repro.kernels.ops import (build_csc_plan, flash_attention_op,
                               segment_sum_op, wkv6_op)
from repro.kernels.ref import mha_ref, segment_sum_ref, wkv6_ref

# the bench certifies through the repro.analysis rule registry (the
# ops-level assert_* shims remain for legacy callers)
SUM_STAGE_RULES = ("jaxpr.pregather", "jaxpr.segment-scatter",
                   "jaxpr.backward-gather")


def _check(closed_jaxpr, plan, ids):
    check_or_raise(run_rules(JaxprContext(closed_jaxpr, plan=plan),
                             ids=ids))


def kernels():
    rng = np.random.default_rng(0)
    # segment sum: GNN aggregation hot spot (Fig. A3: 76% of runtime)
    E, N, D = 20000, 4000, 128
    ids = rng.integers(0, N, E).astype(np.int32)
    data = jnp.asarray(rng.normal(size=(E, D)), jnp.float32)
    plan = build_csc_plan(ids, N)
    us = time_call(lambda d: segment_sum_op(d, plan, interpret=True), data,
                   iters=2)
    us_ref = time_call(
        lambda d: segment_sum_ref(d, jnp.asarray(ids), N), data, iters=2)
    emit("kernels/segment_sum_pallas_interp", us,
         f"E={E};N={N};D={D};jnp_ref_us={us_ref:.0f}")

    # wkv6
    B, T, H, K = 1, 256, 4, 64
    r = jnp.asarray(rng.normal(size=(B, T, H, K)) * 0.5, jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, H, K)) * 0.5, jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, H, K)), jnp.float32)
    w = jnp.asarray(0.6 + 0.39 * rng.random((B, T, H, K)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(H, K)) * 0.2, jnp.float32)
    us = time_call(lambda *a: wkv6_op(*a, chunk=64, interpret=True),
                   r, k, v, w, u, iters=2)
    us_ref = time_call(lambda *a: wkv6_ref(*a)[0], r, k, v, w, u, iters=2)
    emit("kernels/wkv6_pallas_interp", us,
         f"T={T};H={H};K={K};scan_ref_us={us_ref:.0f}")

    # flash attention
    B, T, Hh, Dh = 1, 512, 4, 64
    q = jnp.asarray(rng.normal(size=(B, T, Hh, Dh)), jnp.float32)
    kk = jnp.asarray(rng.normal(size=(B, T, Hh, Dh)), jnp.float32)
    vv = jnp.asarray(rng.normal(size=(B, T, Hh, Dh)), jnp.float32)
    us = time_call(lambda *a: flash_attention_op(
        *a, block_q=128, block_k=128, interpret=True), q, kk, vv, iters=2)
    us_ref = time_call(lambda *a: mha_ref(*a), q, kk, vv, iters=2)
    emit("kernels/flash_attention_pallas_interp", us,
         f"T={T};H={Hh};D={Dh};dense_ref_us={us_ref:.0f}")


def _sum_stage_traffic():
    """Fused-gather kernel vs the PR-1 pre-gather path: wall-clock and
    message-bytes moved through the Sum stage.

    The pre-gather path is reconstructed over the same packed plan:
    materialize the ``(n_chunks, BE, D)`` layout in HBM, then run the same
    kernel over it with an identity gather (contiguous reads) — what PR 1
    shipped, on today's chunk layout. Also asserts (via the jaxpr) that
    the live fused path never allocates that layout.

    The graph is **skew-degree** (half the edges land on one destination
    block). The packed layout holds ``n_chunks·BE`` message rows, E plus
    under one chunk a block, so the pre-gather path moves about three
    times the fused path's message bytes whatever the skew.
    Interpret-mode wall-clock is per-grid-step bound, not bandwidth
    bound; the bytes columns carry the hardware-relevant ratio.
    """
    import functools

    from repro.kernels.segment_sum import segment_sum_csc

    rng = np.random.default_rng(1)
    E, N, D = 20000, 4000, 64
    hot = rng.integers(0, 128, E // 2)           # one hot destination block
    cold = rng.integers(0, N, E - E // 2)
    ids = np.concatenate([hot, cold]).astype(np.int32)
    data = jnp.asarray(rng.normal(size=(E, D)), jnp.float32)
    plan = build_csc_plan(ids, N)
    nb, n_chunks = plan.num_blocks, plan.gather_idx.shape[0]
    lanes = n_chunks * plan.block_e

    # jit the fused wrapper so both sides time compiled dispatch (the
    # pregather emulation below is @jax.jit)
    fused = jax.jit(functools.partial(segment_sum_op, plan=plan,
                                      interpret=True))
    _check(jax.make_jaxpr(fused)(data), plan, ["jaxpr.pregather"])
    us_fused = _best_of(fused, data)

    ident = np.arange(lanes, dtype=np.int32).reshape(plan.gather_idx.shape)

    @jax.jit
    def pregather(d):
        padded = jnp.concatenate([d, jnp.zeros((1, D), d.dtype)], axis=0)
        gathered = padded[jnp.asarray(plan.gather_idx)]   # (n_chunks, BE, D)
        return segment_sum_csc(gathered.reshape(lanes, D),
                               jnp.asarray(ident),
                               jnp.asarray(plan.local_ids), nb,
                               plan.block_n, plan.block_e,
                               interpret=True)[:N]

    us_pre = _best_of(pregather, data)
    np.testing.assert_allclose(np.asarray(fused(data)),
                               np.asarray(pregather(data)),
                               rtol=1e-5, atol=1e-5)
    emit("aggregate/sum_stage_fused_gather", us_fused,
         f"E={E};N={N};D={D};pregather_us={us_pre:.0f}")
    return {
        "edges": E, "num_segments": N, "feature_dim": D,
        "plan_blocks": nb, "plan_chunks": n_chunks,
        # bytes of message data crossing HBM for one Sum-stage call:
        # fused reads the raw (E, D) once; pre-gather reads it, writes the
        # (n_chunks, BE, D) layout, then the kernel reads that back
        "fused_message_bytes": 4 * E * D,
        "pregather_message_bytes": 4 * (E * D + 2 * lanes * D),
        "fused_us_per_call": round(us_fused, 1),
        "pregather_us_per_call": round(us_pre, 1),
        "fused_beats_pregather": bool(us_fused < us_pre),
    }


def _best_of(fn, *args, n=5):
    """Min over n samples — interpret-mode emulation is bimodal (GC /
    allocator pauses), so the mean buries real differences; the min is
    the standard microbenchmark estimator for that regime."""
    import time as _time
    jax.block_until_ready(fn(*args))                      # warmup
    samples = []
    for _ in range(n):
        t0 = _time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append(_time.perf_counter() - t0)
    return min(samples) * 1e6


def _backward_traffic():
    """Fused backward kernels vs the reconstructed PR-2 reference-math
    backward: wall-clock and message-bytes moved by one backward pass.

    Both sides run the SAME fused forward kernels; they differ only in
    the custom_vjp backward — the live path runs the plan-driven Pallas
    kernels (kernels/backward.py), the reconstruction re-attaches the old
    reference math (``g[segment_ids]`` jnp gathers; for softmax a full
    ``jax.ops.segment_max``/``segment_sum`` recompute plus three edge
    gathers), which is exactly what PR 2 shipped. Mirrors
    ``_sum_stage_traffic``: wall-clock carries the interpret-mode
    trajectory, the bytes columns carry the hardware-relevant ratio.

    Byte accounting (f32, message/edge tensors through HBM per call):

    - segment-sum bwd, fused: write d_data (E·D); the cotangent block
      (N·D) is a resident read. Reference: row-gather reads g (E·D) and
      writes d_data (E·D) — 2·E·D.
    - softmax bwd, fused: read logits (E·H) + values (E·H·D), write
      d_logits + d_values — 2·E·H·D + 2·E·H of edge traffic; p_e lives
      only in VMEM. Reference recompute: the two segment passes re-read
      the logits and materialize ex and p (4·E·H), the three edge
      gathers (g_e, out_e twice each: write+read = 4·E·H·D) plus values
      read and d_* writes — 7·E·H·D + 8·E·H in total.
    """
    from repro.core.aggregate import combine, reference_edge_softmax_bwd
    from repro.kernels.ops import edge_softmax_op

    rng = np.random.default_rng(2)
    E, N, D = 20000, 4000, 64
    H = 2
    ids = rng.integers(0, N, E).astype(np.int32)
    dst = jnp.asarray(ids)
    plan = build_csc_plan(ids, N)
    mask = jnp.ones(E, jnp.float32)
    value = jnp.asarray(rng.normal(size=(E, H, D)), jnp.float32)
    logit = jnp.asarray(rng.normal(size=(E, H)), jnp.float32)

    def loss(mode, v, lg, backend, pln):
        out = combine(mode, {"value": v, "logit": lg}, dst, N, mask,
                      backend=backend, plan=pln)
        return jnp.sum(jnp.sin(out) * out)

    # -- reconstructed PR-2 path: fused forward, reference-math backward
    @jax.custom_vjp
    def _sum_refbwd(v):
        return segment_sum_op(v, plan, interpret=True)

    def _sum_refbwd_fwd(v):
        return _sum_refbwd(v), ()

    def _sum_refbwd_bwd(res, g):
        return (g[dst],)                       # the old g[segment_ids]

    _sum_refbwd.defvjp(_sum_refbwd_fwd, _sum_refbwd_bwd)

    @jax.custom_vjp
    def _softmax_refbwd(lg, v):
        return edge_softmax_op(lg, v, plan, interpret=True)

    def _softmax_refbwd_fwd(lg, v):
        out = _softmax_refbwd(lg, v)
        return out, (lg, v, out)

    def _softmax_refbwd_bwd(res, g):
        lg, v, out = res
        return reference_edge_softmax_bwd(g, lg, v, out, dst, N)

    _softmax_refbwd.defvjp(_softmax_refbwd_fwd, _softmax_refbwd_bwd)

    # -- segment-sum backward ------------------------------------------------
    def _sin_loss(out):
        return jnp.sum(jnp.sin(out) * out)

    fused_sum = jax.jit(jax.grad(lambda v: loss("sum", v, logit, "csc",
                                                plan)))
    recon_sum = jax.jit(jax.grad(lambda v: _sin_loss(_sum_refbwd(v))))
    np.testing.assert_allclose(np.asarray(fused_sum(value)),
                               np.asarray(recon_sum(value)),
                               rtol=1e-4, atol=1e-5)
    _check(jax.make_jaxpr(fused_sum)(value), plan, SUM_STAGE_RULES)
    us_sum_fused = _best_of(fused_sum, value)
    us_sum_recon = _best_of(recon_sum, value)
    emit("aggregate/segment_sum_bwd_fused", us_sum_fused,
         f"E={E};N={N};H={H};D={D};reference_bwd_us={us_sum_recon:.0f}")

    # -- edge-softmax backward -----------------------------------------------
    fused_sm = jax.jit(jax.grad(lambda lg, v: loss(
        "softmax", v, lg, "csc", plan), argnums=(0, 1)))
    recon_sm = jax.jit(jax.grad(
        lambda lg, v: _sin_loss(_softmax_refbwd(lg, v)), argnums=(0, 1)))
    for a, b in zip(fused_sm(logit, value), recon_sm(logit, value)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    _check(jax.make_jaxpr(fused_sm)(logit, value), plan, SUM_STAGE_RULES)
    us_sm_fused = _best_of(fused_sm, logit, value)
    us_sm_recon = _best_of(recon_sm, logit, value)
    emit("aggregate/edge_softmax_bwd_fused", us_sm_fused,
         f"E={E};N={N};H={H};D={D};reference_bwd_us={us_sm_recon:.0f}")

    f32 = 4
    sum_fused_bytes = f32 * E * D * H
    sum_ref_bytes = f32 * 2 * E * D * H
    sm_fused_bytes = f32 * (2 * E * H * D + 2 * E * H)
    sm_ref_bytes = f32 * (7 * E * H * D + 8 * E * H)
    return {
        "edges": E, "num_segments": N, "heads": H, "feature_dim": D,
        "segment_sum": {
            "fused_message_bytes": sum_fused_bytes,
            "reference_message_bytes": sum_ref_bytes,
            "fused_us_per_call": round(us_sum_fused, 1),
            "reference_us_per_call": round(us_sum_recon, 1),
        },
        "edge_softmax": {
            "fused_message_bytes": sm_fused_bytes,
            "reference_message_bytes": sm_ref_bytes,
            "fused_us_per_call": round(us_sm_fused, 1),
            "reference_us_per_call": round(us_sm_recon, 1),
        },
        # the acceptance line: the fused backward moves fewer message
        # bytes than the reconstructed reference backward
        "fused_beats_reference_bytes": bool(
            sum_fused_bytes < sum_ref_bytes
            and sm_fused_bytes < sm_ref_bytes),
        "note": ("wall-clock is interpret-mode emulation (trajectory "
                 "only); both sides share the fused forward, so the "
                 "delta is the backward swap"),
    }


def aggregate(out_json: str = "BENCH_aggregate.json", smoke: bool = False):
    """End-to-end TGAR layer forward AND train step (value_and_grad)
    under each aggregation backend.

    Times ``forward_block`` and ``value_and_grad(loss_block)`` (NN-T ->
    NN-G -> Sum -> NN-A plus the reverse flow, jitted) for one model per
    combine mode, "reference" vs "csc", and dumps the rows to
    ``out_json`` for the perf trajectory of the Sum-stage hot path — plus
    the fused-vs-pregather traffic comparison of ``_sum_stage_traffic``
    and the fused-vs-reference backward comparison of
    ``_backward_traffic``.

    ``smoke=True`` is the CI lane: tiny shapes, one timing iteration,
    and the full set of jaxpr contracts (pre-gather-free forward+backward,
    scatter-free combine-level value_and_grad, fewer segment scatters
    than the reference end to end) asserted so a contract regression
    fails the lane, not just the nightly bench.
    """
    import dataclasses

    from repro.config import GNNConfig
    from repro.core.mpgnn import forward_block, loss_block
    from repro.core.strategies import global_batch_view
    from repro.graph import sbm_graph
    from repro.models import make_gnn

    if smoke and out_json == "BENCH_aggregate.json":
        out_json = "BENCH_aggregate_smoke.json"   # don't clobber nightly

    # traffic comparisons first: they are timing-sensitive and the model
    # loop below leaves the process with enough jit-cache/allocator
    # pressure to skew interpret-mode samples taken after it
    # (the bytes comparison in backward_traffic is analytic accounting —
    # the enforced guards are the jaxpr contracts asserted inside the
    # traffic functions and the model loop below)
    traffic = _sum_stage_traffic() if not smoke else None
    bwd_traffic = _backward_traffic() if not smoke else None

    if smoke:
        num_nodes, hidden, layers, iters = 200, 8, 1, 1
    else:
        num_nodes, hidden, layers, iters = 2000, 32, 2, 3
    g = sbm_graph(num_nodes=num_nodes, num_classes=4, feature_dim=hidden,
                  p_in=0.01, p_out=0.002, seed=0).add_self_loops()
    rows = []
    scatter_counts = {}
    for model_name, combine_mode, heads in (
            ("gcn", "sum", 1), ("sage", "mean", 1), ("sage_max", "max", 1),
            ("gat", "softmax", 4)):
        gcn_norm = model_name == "gcn"
        cfg = GNNConfig(model=model_name, num_layers=layers,
                        hidden_dim=hidden, num_classes=4,
                        feature_dim=hidden, num_heads=heads)
        model = make_gnn(cfg)
        params = model.init(jax.random.PRNGKey(0), hidden)
        view = global_batch_view(g, cfg.num_layers)
        for backend in ("reference", "csc"):
            m = dataclasses.replace(model, aggregate_backend=backend)
            block = view.as_block(gcn_norm=gcn_norm,
                                  csc_plan=backend == "csc")
            fwd = jax.jit(lambda p, b, m_=m: forward_block(m_, p, b))
            vag = jax.jit(jax.value_and_grad(
                lambda p, b, m_=m: loss_block(m_, p, b)))
            plan = block.csc_plan
            if backend == "csc":
                # the fused-gather contract, end to end through the model
                # — forward AND backward (the train-step jaxpr)
                _check(jax.make_jaxpr(fwd)(params, block), plan,
                       ["jaxpr.pregather"])
                _check(jax.make_jaxpr(lambda p: vag(p, block))(params),
                       plan, ["jaxpr.pregather"])
            scatter_counts[(model_name, backend)] = (
                count_segment_scatters(
                    jax.make_jaxpr(lambda p: vag(p, block))(params),
                    block.csc_plan or view.as_block(
                        gcn_norm=gcn_norm, csc_plan=True).csc_plan))
            for phase, fn in (("forward", fwd), ("value_and_grad", vag)):
                us = time_call(fn, params, block, iters=iters)
                emit(f"aggregate/{model_name}_{backend}_{phase}", us,
                     f"combine={combine_mode};N={g.num_nodes};"
                     f"E={g.num_edges};H={heads};D={hidden}")
                rows.append({"model": model_name, "combine": combine_mode,
                             "backend": backend, "phase": phase,
                             "us_per_call": round(us, 1),
                             "num_nodes": g.num_nodes,
                             "num_edges": g.num_edges,
                             "heads": heads, "hidden_dim": hidden,
                             "num_layers": cfg.num_layers,
                             "interpret_mode":
                                 jax.default_backend() != "tpu"})
        # the Sum-stage fallbacks are gone from the train step: only the
        # NN-Gather transposes (shared by both backends) may remain
        assert (scatter_counts[(model_name, "csc")]
                < scatter_counts[(model_name, "reference")]), (
            model_name, scatter_counts)

    if smoke:
        # combine-level certificate: the exact scatter/gather-free
        # contract of the fused backward, all four modes
        from repro.core.aggregate import combine
        rng = np.random.default_rng(0)
        E, N, H, D = 300, 64, 2, 8
        ids = rng.integers(0, N, E).astype(np.int32)
        dst = jnp.asarray(ids)
        cplan = build_csc_plan(ids, N, block_n=32, block_e=64)
        value = jnp.asarray(rng.normal(size=(E, H, D)), jnp.float32)
        logit = jnp.asarray(rng.normal(size=(E, H)), jnp.float32)
        mask = jnp.asarray(rng.random(E) > 0.2, jnp.float32)
        for mode in ("sum", "mean", "max", "softmax"):
            def closs(v, lg):
                out = combine(mode, {"value": v, "logit": lg}, dst, N,
                              mask, backend="csc", plan=cplan)
                return jnp.sum(out * out)

            _check(
                jax.make_jaxpr(jax.value_and_grad(closs, argnums=(0, 1)))(
                    value, logit), cplan, SUM_STAGE_RULES)
            emit(f"aggregate/contract_{mode}", 0.0, "sum_stage_fused=ok")

    with open(out_json, "w") as f:
        json.dump({"benchmark": "aggregate_layer_forward",
                   "device": jax.default_backend(),
                   "smoke": smoke,
                   "note": ("csc timings are Pallas interpret-mode off-TPU "
                            "(Python emulation, not kernel speed); the "
                            "trajectory is meaningful per backend/device. "
                            "csc rows are fused-gather, forward and "
                            "backward: verified free of the (n_chunks, "
                            "BE, D) pre-gather tensor via jaxpr walk, and the "
                            "train step carries no Sum-stage reference "
                            "segment fallbacks"),
                   "sum_stage_traffic": traffic,
                   "backward_traffic": bwd_traffic,
                   "segment_scatter_counts": {
                       f"{m}/{b}": c
                       for (m, b), c in scatter_counts.items()},
                   "rows": rows}, f, indent=2)
    print(f"wrote {out_json} ({len(rows)} rows)")
