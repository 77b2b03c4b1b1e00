"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import (build_csc_plan, segment_sum_op, wkv6_op,
                               flash_attention_op)
from repro.kernels.ref import segment_sum_ref, wkv6_ref, mha_ref


@pytest.mark.parametrize("E,N,D", [(64, 16, 8), (777, 300, 48),
                                   (1500, 97, 16), (33, 500, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_sum_sweep(E, N, D, dtype):
    rng = np.random.default_rng(E * N)
    ids = rng.integers(0, N, E).astype(np.int32)
    data = rng.normal(size=(E, D)).astype(np.float32)
    plan = build_csc_plan(ids, N, block_n=64, block_e=128)
    out = segment_sum_op(jnp.asarray(data, dtype), plan, interpret=True)
    ref = segment_sum_ref(jnp.asarray(data, dtype), jnp.asarray(ids), N)
    tol = 1e-5 if dtype == jnp.float32 else 6e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol * 8)


@pytest.mark.parametrize("blocks", [(32, 64), (64, 256), (128, 128)])
def test_segment_sum_block_shapes(blocks):
    bn, be = blocks
    rng = np.random.default_rng(bn)
    E, N, D = 513, 211, 24
    ids = rng.integers(0, N, E).astype(np.int32)
    data = rng.normal(size=(E, D)).astype(np.float32)
    plan = build_csc_plan(ids, N, block_n=bn, block_e=be)
    out = segment_sum_op(jnp.asarray(data), plan, interpret=True)
    ref = segment_sum_ref(jnp.asarray(data), jnp.asarray(ids), N)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_segment_sum_empty_segments():
    ids = np.array([5, 5, 5], np.int32)          # most segments empty
    data = np.ones((3, 4), np.float32)
    plan = build_csc_plan(ids, 64, block_n=16, block_e=16)
    out = np.asarray(segment_sum_op(jnp.asarray(data), plan,
                                    interpret=True))
    assert out[5].sum() == 12.0 and np.abs(out).sum() == 12.0


@pytest.mark.parametrize("T,chunk", [(64, 32), (96, 32), (100, 32),
                                     (128, 64)])
@pytest.mark.parametrize("KV", [(16, 16), (32, 48)])
def test_wkv6_sweep(T, chunk, KV):
    K, V = KV
    B, H = 2, 2
    rng = np.random.default_rng(T + K)
    r = rng.normal(size=(B, T, H, K)).astype(np.float32) * 0.5
    k = rng.normal(size=(B, T, H, K)).astype(np.float32) * 0.5
    v = rng.normal(size=(B, T, H, V)).astype(np.float32)
    w = (0.5 + 0.49 * rng.random((B, T, H, K))).astype(np.float32)
    u = (rng.normal(size=(H, K)) * 0.2).astype(np.float32)
    o = wkv6_op(*map(jnp.asarray, (r, k, v, w, u)), chunk=chunk,
                interpret=True)
    ref, _ = wkv6_ref(*map(jnp.asarray, (r, k, v, w, u)))
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_wkv6_bf16_inputs():
    B, T, H, K = 1, 64, 2, 16
    rng = np.random.default_rng(7)
    mk = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.bfloat16)
    r, k = mk(B, T, H, K), mk(B, T, H, K)
    v = mk(B, T, H, K)
    w = jnp.asarray(0.6 + 0.39 * rng.random((B, T, H, K)), jnp.bfloat16)
    u = jnp.asarray(rng.normal(size=(H, K)) * 0.2, jnp.float32)
    o = wkv6_op(r, k, v, w, u, chunk=32, interpret=True)
    ref, _ = wkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=0.15, atol=0.15)


@pytest.mark.parametrize("T,bq,bk", [(128, 32, 32), (128, 64, 32),
                                     (256, 64, 64)])
@pytest.mark.parametrize("window", [0, 48, 128])
def test_flash_attention_sweep(T, bq, bk, window):
    B, H, D = 2, 2, 32
    rng = np.random.default_rng(T + window)
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    o = flash_attention_op(q, k, v, causal=True, sliding_window=window,
                           block_q=bq, block_k=bk, interpret=True)
    ref = mha_ref(q, k, v, causal=True, sliding_window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_gqa_and_bf16():
    B, T, Hq, Hkv, D = 1, 128, 4, 2, 16
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(B, T, Hq, D)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.bfloat16)
    o = flash_attention_op(q, k, v, block_q=32, block_k=32, interpret=True)
    kr = jnp.repeat(k, 2, axis=2)
    vr = jnp.repeat(v, 2, axis=2)
    ref = mha_ref(q, kr, vr, causal=True)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=0.1, atol=0.1)


def test_wkv6_kernel_matches_model_chunked_path():
    """kernels/wkv6 (serving) == arch chunked train path (same math)."""
    from repro.arch.rwkv6_block import wkv_chunked
    B, T, H, K = 2, 64, 2, 16
    rng = np.random.default_rng(11)
    r = jnp.asarray(rng.normal(size=(B, T, H, K)) * 0.4, jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, H, K)) * 0.4, jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, H, K)), jnp.float32)
    w = jnp.asarray(0.6 + 0.39 * rng.random((B, T, H, K)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(H, K)) * 0.2, jnp.float32)
    o_kernel = wkv6_op(r, k, v, w, u, chunk=32, interpret=True)
    o_model, _ = wkv_chunked(r, k, v, w, u, chunk=16)
    np.testing.assert_allclose(np.asarray(o_kernel), np.asarray(o_model),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# edge softmax kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("E,N,D", [(100, 30, 8), (777, 300, 48),
                                   (1500, 97, 16)])
@pytest.mark.parametrize("blocks", [(32, 64), (64, 256)])
def test_edge_softmax_sweep(E, N, D, blocks):
    from repro.kernels.ops import edge_softmax_op
    from repro.kernels.ref import edge_softmax_ref
    bn, be = blocks
    rng = np.random.default_rng(E + bn)
    ids = rng.integers(0, N, E).astype(np.int32)
    logits = rng.normal(size=(E,)).astype(np.float32) * 4
    vals = rng.normal(size=(E, D)).astype(np.float32)
    plan = build_csc_plan(ids, N, block_n=bn, block_e=be)
    out = edge_softmax_op(jnp.asarray(logits), jnp.asarray(vals), plan,
                          interpret=True)
    ref = edge_softmax_ref(jnp.asarray(logits), jnp.asarray(vals),
                           jnp.asarray(ids), N)
    # empty segments produce 0 in the kernel (denominator clamp) and 0 in
    # the ref (num=0); compare everywhere
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_edge_softmax_matches_gat_sum_stage():
    """Kernel == the model's segment_softmax Sum stage (single head)."""
    from repro.core.tgar import segment_softmax
    from repro.kernels.ops import edge_softmax_op
    rng = np.random.default_rng(5)
    E, N, D = 400, 120, 16
    ids = rng.integers(0, N, E).astype(np.int32)
    logits = rng.normal(size=(E,)).astype(np.float32)
    vals = rng.normal(size=(E, D)).astype(np.float32)
    plan = build_csc_plan(ids, N, block_n=64, block_e=128)
    out = edge_softmax_op(jnp.asarray(logits), jnp.asarray(vals), plan,
                          interpret=True)
    ref = segment_softmax(jnp.asarray(logits)[:, None],
                          jnp.asarray(vals)[:, None, :],
                          jnp.asarray(ids), N,
                          jnp.ones(E, np.float32))[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_noncausal_odd_length():
    """Regression: T not a multiple of the block size, causal=False. The
    wrapper pads T up to the block; the padded keys carry zero logits, so
    without the true-length mask every real query's softmax denominator
    was inflated (causal masking used to hide this for pad keys > q_pos).
    """
    B, H, D = 2, 2, 16
    rng = np.random.default_rng(9)
    for T in (7, 33, 100):
        q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
        o = flash_attention_op(q, k, v, causal=False, block_q=32,
                               block_k=32, interpret=True)
        ref = mha_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5, err_msg=f"T={T}")


def test_flash_attention_unequal_blocks_odd_length():
    """Padding must target a common multiple of both block sizes: with
    unequal clamped blocks, padding to max(bq, bk) used to trip the
    kernel's divisibility assert."""
    B, T, H, D = 1, 100, 2, 16
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    o = flash_attention_op(q, k, v, causal=False, block_q=128, block_k=32,
                           interpret=True)
    ref = mha_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_causal_odd_length():
    """Padded tail must stay harmless in the causal path too."""
    B, T, H, D = 1, 45, 2, 16
    rng = np.random.default_rng(10)
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    o = flash_attention_op(q, k, v, causal=True, block_q=32, block_k=32,
                           interpret=True)
    ref = mha_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_edge_softmax_multi_head_single_launch():
    """(E, H, D) logits/values run as ONE fused kernel launch (heads on
    the grid) and match the per-head reference."""
    from repro.kernels.ops import edge_softmax_op
    from repro.kernels.ref import edge_softmax_ref
    rng = np.random.default_rng(12)
    E, N, H, D = 500, 120, 3, 16
    ids = rng.integers(0, N // 2, E).astype(np.int32)   # empty tail too
    logits = jnp.asarray(rng.normal(size=(E, H)) * 3, jnp.float32)
    vals = jnp.asarray(rng.normal(size=(E, H, D)), jnp.float32)
    plan = build_csc_plan(ids, N, block_n=32, block_e=64)
    out = edge_softmax_op(logits, vals, plan, interpret=True)
    assert out.shape == (N, H, D)
    for h in range(H):
        ref = edge_softmax_ref(logits[:, h], vals[:, h, :],
                               jnp.asarray(ids), N)
        np.testing.assert_allclose(np.asarray(out[:, h, :]),
                                   np.asarray(ref), rtol=2e-5, atol=2e-5,
                                   err_msg=f"head {h}")


def test_segment_max_d_tiled_wide_features():
    """D > the VMEM cap exercises the d-tile grid axis of the fused max
    kernel (the (BE, BD) gather scratch stays bounded). Tiles are whole
    widths or lane multiples, as Mosaic's (8, 128) block rule needs."""
    from repro.kernels.ops import segment_max_op
    from repro.kernels.segment_sum import _pick_block_d
    assert _pick_block_d(48) == 48             # under the cap: whole
    assert _pick_block_d(160) == 160
    assert _pick_block_d(1024) == 512          # largest lane multiple
    assert _pick_block_d(1152) == 384          # dividing D within 512
    assert _pick_block_d(640) == 128
    rng = np.random.default_rng(13)
    E, N, D = 700, 90, 1100                    # lane-padded to 1152
    ids = rng.integers(0, N, E).astype(np.int32)
    data = jnp.asarray(rng.normal(size=(E, D)), jnp.float32)
    plan = build_csc_plan(ids, N, block_n=32, block_e=64)
    out = segment_max_op(data, plan, interpret=True)
    # empty segments: kernel yields NEG, the jnp oracle -inf — same clamp
    # the combine engine applies
    from repro.kernels.segment_sum import NEG
    ref = jnp.maximum(jax.ops.segment_max(data, jnp.asarray(ids), N), NEG)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# fused backward kernels (kernels/backward.py) vs the reference bwd math
# ---------------------------------------------------------------------------


def test_plan_edge_dst_inverts_the_plan():
    """The plan's inverse map: lane e of edge_dst is the destination row
    of edge e (pad lanes hold num_segments), derived from
    gather_idx/local_ids on the host."""
    rng = np.random.default_rng(21)
    E, N = 530, 140
    ids = rng.integers(0, N, E).astype(np.int32)
    plan = build_csc_plan(ids, N, block_n=32, block_e=64)
    assert plan.edge_dst.shape[0] % plan.block_e == 0
    np.testing.assert_array_equal(plan.edge_dst[:E], ids)
    assert np.all(plan.edge_dst[E:] == N)


@pytest.mark.parametrize("E,N,D,blocks", [(400, 90, 8, (32, 64)),
                                          (777, 300, 48, (64, 128)),
                                          (300, 64, 160, (16, 64)),
                                          (100, 40, 8, (16, 20))])
def test_segment_sum_bwd_kernel(E, N, D, blocks):
    """d_data[e] = g[dst[e]] via the plan-driven gather kernel (D=160
    exercises the backward d-tiling; 20-lane chunks, a resident table's
    row copies past the last whole trip of 8)."""
    from repro.kernels.ops import segment_sum_bwd_op
    rng = np.random.default_rng(E + D)
    ids = rng.integers(0, N, E).astype(np.int32)
    g = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    plan = build_csc_plan(ids, N, block_n=blocks[0], block_e=blocks[1])
    out = segment_sum_bwd_op(g, plan, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(g)[ids],
                               rtol=1e-6, atol=1e-6)


def test_segment_max_bwd_kernel_hit_mask():
    """The argmax-hit mask runs inside the kernel: cotangent lands only
    on edges attaining their segment max (ties share, like
    jax.ops.segment_max)."""
    from repro.kernels.ops import (segment_max_bwd_op, segment_max_op)
    rng = np.random.default_rng(31)
    E, N, D = 450, 100, 12
    ids = rng.integers(0, N // 2, E).astype(np.int32)   # empty tail
    data = jnp.asarray(
        rng.integers(-4, 4, size=(E, D)).astype(np.float32))  # force ties
    g = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    plan = build_csc_plan(ids, N, block_n=32, block_e=64)
    fwd = segment_max_op(data, plan, interpret=True)
    out = segment_max_bwd_op(g, fwd, data, plan, interpret=True)
    hit = (np.asarray(data) == np.asarray(fwd)[ids]).astype(np.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(g)[ids] * hit,
                               rtol=1e-6, atol=1e-6)


def test_edge_softmax_fwd_op_stats_match_reference():
    """The forward launch's extra (m, den) outputs equal the reference
    per-destination softmax stats the backward rebuilds p_e from."""
    from repro.kernels.ops import edge_softmax_fwd_op
    from repro.kernels.segment_sum import NEG
    rng = np.random.default_rng(41)
    E, N, H, D = 500, 120, 2, 16
    ids = rng.integers(0, N // 2, E).astype(np.int32)
    logits = jnp.asarray(rng.normal(size=(E, H)) * 3, jnp.float32)
    vals = jnp.asarray(rng.normal(size=(E, H, D)), jnp.float32)
    plan = build_csc_plan(ids, N, block_n=32, block_e=64)
    _, m, den = edge_softmax_fwd_op(logits, vals, plan, interpret=True)
    seg_max = jnp.maximum(
        jax.ops.segment_max(logits, jnp.asarray(ids), N), NEG)
    ex = jnp.exp(logits - seg_max[jnp.asarray(ids)])
    den_ref = jax.ops.segment_sum(ex, jnp.asarray(ids), N)
    np.testing.assert_allclose(np.asarray(m), np.asarray(seg_max),
                               rtol=1e-6, atol=1e-6)
    # empty segments: kernel den is 0, reference sum is 0 too
    np.testing.assert_allclose(np.asarray(den), np.asarray(den_ref),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("H,D", [(1, 8), (3, 16)])
def test_edge_softmax_bwd_kernel_matches_reference(H, D):
    """The recompute-in-kernel softmax backward == the reference-math
    jacobian (kept in aggregate.reference_edge_softmax_bwd), including
    masked edges nulled to NEG."""
    from repro.core.aggregate import reference_edge_softmax_bwd
    from repro.kernels.ops import edge_softmax_bwd_op, edge_softmax_fwd_op
    from repro.kernels.segment_sum import NEG
    rng = np.random.default_rng(51 + H)
    E, N = 480, 110
    ids = rng.integers(0, N // 2, E).astype(np.int32)
    mask = rng.random(E) > 0.3
    logits = np.where(mask[:, None], rng.normal(size=(E, H)) * 3,
                      NEG).astype(np.float32)
    vals = (rng.normal(size=(E, H, D)).astype(np.float32)
            * mask[:, None, None])
    g = jnp.asarray(rng.normal(size=(N, H, D)), jnp.float32)
    plan = build_csc_plan(ids, N, block_n=32, block_e=64)
    out, m, den = edge_softmax_fwd_op(jnp.asarray(logits),
                                      jnp.asarray(vals), plan,
                                      interpret=True)
    d_logits, d_values = edge_softmax_bwd_op(
        g, jnp.asarray(logits), jnp.asarray(vals), out, m, den, plan,
        interpret=True)
    ref_dl, ref_dv = reference_edge_softmax_bwd(
        g, jnp.asarray(logits), jnp.asarray(vals), out, jnp.asarray(ids),
        N)
    np.testing.assert_allclose(np.asarray(d_logits), np.asarray(ref_dl),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(d_values), np.asarray(ref_dv),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# packed CSC plans: layout, step table, bound, kernels on skewed plans
# ---------------------------------------------------------------------------


def _skewed_ids(case, n, e, rng):
    """Destination ids: ``hot`` puts half the edges on one block's rows
    (that block spans many chunks) and leaves gaps of empty blocks;
    ``tail`` leaves the upper half of the rows empty."""
    if case == "hot":
        hot = rng.integers(0, 8, e // 2)
        cold = rng.choice(np.arange(n)[(np.arange(n) // 16) % 3 == 0],
                          e - e // 2)
        return np.concatenate([hot, cold]).astype(np.int32)
    return rng.integers(0, n // 2, e).astype(np.int32)


# (case, nodes, edges, block_n, block_e, forced chunk count: 0 = live only)
SKEWED = [("hot", 160, 900, 16, 32, 0), ("hot", 160, 900, 16, 32, 80),
          ("tail", 200, 300, 32, 64, 0), ("tail", 200, 300, 32, 64, 25)]


def _plan_case(case, n, e, bn, be, n_chunks, seed=0):
    from repro.kernels.ops import build_csc_plan as build
    ids = _skewed_ids(case, n, e, np.random.default_rng(seed))
    return ids, build(ids, n, block_n=bn, block_e=be, n_chunks=n_chunks)


@pytest.mark.parametrize("case", SKEWED, ids=str)
def test_packed_plan_layout_and_step_table(case):
    """Each block's edges, in CSC order (by destination, then edge id),
    fill max(1, ceil(len/BE))
    whole chunks packed back to back; dead lanes name their block
    (-1 - b, trailing chunks -1 - nb); the step table reads every chunk's
    block and every block's first chunk back from that."""
    from repro.kernels.segment_sum import step_table
    ids, plan = _plan_case(*case)
    n, bn, be = case[1], case[3], case[4]
    nb = plan.num_blocks
    lens = np.bincount(ids // bn, minlength=nb)
    per_block = np.maximum(1, -(-lens // be))
    first = np.concatenate([[0], np.cumsum(per_block)])
    assert plan.gather_idx.shape == plan.local_ids.shape
    assert plan.gather_idx.shape == (case[5] or first[-1], be)
    for b in range(nb):
        lanes = slice(first[b] * be, first[b + 1] * be)
        edges = plan.gather_idx.reshape(-1)[lanes][:lens[b]]
        rows = plan.local_ids.reshape(-1)[lanes]
        mine = np.flatnonzero(ids // bn == b)
        np.testing.assert_array_equal(
            edges, mine[np.argsort(ids[mine], kind="stable")])
        np.testing.assert_array_equal(rows[:lens[b]], ids[edges])
        assert np.all(rows[lens[b]:] == -1 - b)
    assert np.all(plan.local_ids[first[-1]:] == -1 - nb)
    assert np.all(plan.gather_idx.reshape(-1)[plan.local_ids.reshape(-1)
                                              < 0] == len(ids))
    block, start = map(np.asarray, step_table(
        jnp.asarray(plan.local_ids), nb, bn))
    np.testing.assert_array_equal(start, first)
    np.testing.assert_array_equal(
        block, np.minimum(np.repeat(np.arange(nb + 1), np.append(
            per_block, len(plan.local_ids) - first[-1])), nb - 1))
    np.testing.assert_array_equal(plan.edge_dst[:len(ids)], ids)
    assert n == plan.num_segments


@pytest.mark.parametrize("kernel", ["sum", "max", "softmax"])
@pytest.mark.parametrize("case", SKEWED, ids=str)
def test_packed_kernels_match_reference(case, kernel):
    """Sum, d-tiled max (D = 200, lane-padded to 256, in two tiles of
    128) and multi-head softmax over skewed packed plans: one block across
    many chunks, empty blocks, trailing dead steps."""
    from repro.kernels.ops import edge_softmax_op
    from repro.kernels.ref import edge_softmax_ref
    from repro.kernels.segment_sum import NEG, segment_max_csc
    ids, plan = _plan_case(*case)
    n, e = case[1], len(ids)
    rng = np.random.default_rng(7)
    if kernel == "softmax":
        H, D = 3, 8
        logits = jnp.asarray(rng.normal(size=(e, H)) * 3, jnp.float32)
        vals = jnp.asarray(rng.normal(size=(e, H, D)), jnp.float32)
        out = edge_softmax_op(logits, vals, plan, interpret=True)
        for h in range(H):
            np.testing.assert_allclose(
                np.asarray(out[:, h]), np.asarray(edge_softmax_ref(
                    logits[:, h], vals[:, h], jnp.asarray(ids), n)),
                rtol=2e-5, atol=2e-5)
        return
    data = jnp.asarray(rng.normal(size=(e, 200)), jnp.float32)
    if kernel == "sum":
        out = segment_sum_op(data, plan, interpret=True)
        ref = segment_sum_ref(data, jnp.asarray(ids), n)
    else:
        out = segment_max_csc(data, jnp.asarray(plan.gather_idx),
                              jnp.asarray(plan.local_ids), plan.num_blocks,
                              plan.block_n, plan.block_e, block_d=128,
                              interpret=True)[:n, :200]
        ref = jnp.maximum(jax.ops.segment_max(data, jnp.asarray(ids), n),
                          NEG)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kernel", ["sum", "max", "softmax"])
def test_packed_kernels_are_chunk_invariant(kernel):
    """A destination gives the same bits whether its block's edges sit in
    one chunk or are split over many: each folds its edges in edge-id
    order whatever the chunk width."""
    from repro.kernels.ops import edge_softmax_op, segment_max_op
    rng = np.random.default_rng(3)
    n, e = 64, 700
    ids = _skewed_ids("hot", n, e, rng)
    logits = jnp.asarray(rng.normal(size=(e, 2)) * 3, jnp.float32)
    vals = jnp.asarray(rng.normal(size=(e, 2, 8)), jnp.float32)
    data = vals.reshape(e, 16)
    outs = []
    for be in (8, 32, 512):      # the hot block: 44 chunks .. one chunk
        plan = build_csc_plan(ids, n, block_n=16, block_e=be)
        if kernel == "softmax":
            out = edge_softmax_op(logits, vals, plan, interpret=True)
        elif kernel == "sum":
            out = segment_sum_op(data, plan, interpret=True)
        else:
            out = segment_max_op(data, plan, interpret=True)
        outs.append(np.asarray(out))
    for out in outs[1:]:
        np.testing.assert_array_equal(out, outs[0])


@pytest.mark.parametrize("rung", [(256, 1024), (4096, 16384),
                                  (16384, 65536)])
def test_bucket_plan_chunk_bound(rung):
    """Bucket plans take ceil(e_pad/BE) + nb chunks whatever the view —
    edges all on one block, spread over every block, or none — and never
    more lanes than the unpacked nb x L_pad layout of the same edges."""
    from repro.kernels.ops import build_bucket_csc_plan, bucket_plan_chunks
    n_pad, e_pad = rung
    nb = n_pad // 128
    bound = -(-e_pad // 256) + nb
    assert bucket_plan_chunks(n_pad, e_pad) == bound
    rng = np.random.default_rng(n_pad)
    views = {"one_block": rng.integers(0, 128, e_pad),
             "every_block": rng.integers(0, n_pad, e_pad - 1),
             "bucket_full_skew": np.sort(rng.integers(0, n_pad, e_pad)),
             "empty": np.zeros(0, np.int64)}
    for name, dst in views.items():
        plan = build_bucket_csc_plan(dst.astype(np.int32), n_pad, e_pad)
        assert plan.gather_idx.shape == (bound, 256), name
        lens = np.bincount(dst // 128, minlength=nb)
        live = int(np.maximum(1, -(-lens // 256)).sum())
        assert live <= bound, name
        l_pad = max(256, -(-int(lens.max(initial=0)) // 256) * 256)
        assert live * 256 <= nb * l_pad, name


def test_stacked_plans_of_unequal_shards_share_a_shape():
    """The engine's per-shard plans pad to the largest chunk count with
    dead chunks, and each padded plan gives what its own unpadded plan
    gives."""
    from repro.kernels.ops import build_csc_plans_stacked
    rng = np.random.default_rng(8)
    n = 96
    rows = [rng.integers(0, n, 400), rng.integers(0, 8, 400),
            np.full(400, n, np.int64)]       # the last: no edge in range
    plans = build_csc_plans_stacked(np.stack(rows).astype(np.int32), n,
                                    block_n=32, block_e=32)
    assert len({p.gather_idx.shape for p in plans}) == 1
    data = jnp.asarray(rng.normal(size=(400, 8)), jnp.float32)
    for ids, plan in zip(rows, plans):
        own = build_csc_plan(ids.astype(np.int32), n, block_n=32,
                             block_e=32)
        assert own.gather_idx.shape[0] <= plan.gather_idx.shape[0]
        np.testing.assert_array_equal(
            np.asarray(segment_sum_op(data, plan, interpret=True)),
            np.asarray(segment_sum_op(data, own, interpret=True)))
