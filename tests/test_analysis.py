"""The repro.analysis subsystem (PR 7): rule registry, VMEM budget
checker, source lint, and the CLI gate.

Both acceptance directions are asserted here:

- every negative fixture (a pre-gathered step, a reference segment
  scatter, a backward gather, a full-graph aval in a compact step, an
  f64-promoting loss, a host transfer inside jit, a donation mismatch,
  an oversized-block kernel, a bare-assert module, a hot-path alloc)
  is flagged by its named rule;
- the real csc train/infer steps — all four combine modes, both
  trainers — and the shipped source tree produce zero findings.
"""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (ContractError, JaxprContext, RULES,
                            check_vmem, iter_kernel_stats, lint_source,
                            lint_tree, run_rules)
from repro.analysis.cli import (COMBINE_RULES, COMPACT_RULES, TRAIN_RULES,
                                Report, check_combine_modes,
                                check_compact_buckets, check_trainers,
                                run_analysis)
from repro.kernels.ops import build_csc_plan

SRC_ROOT = Path(__file__).resolve().parents[1] / "src" / "repro"


def _plan(E=96, N=40):
    rng = np.random.default_rng(0)
    ids = rng.integers(0, N, E).astype(np.int32)
    return ids, build_csc_plan(ids, N, block_n=16, block_e=32)


def _rule_ids(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# negative fixtures: each rule MUST flag its fixture by name
# ---------------------------------------------------------------------------


def test_pregather_fixture_flagged():
    ids, plan = _plan()
    data = jnp.ones((plan.num_edges, 8), jnp.float32)

    def pregathered(d):
        # the (nb, L_pad, D) float layout the fused kernels eliminated
        gathered = d[jnp.asarray(plan.gather_idx) % plan.num_edges]
        return jnp.sum(gathered)

    jx = jax.make_jaxpr(pregathered)(data)
    findings = run_rules(JaxprContext(jx, plan=plan),
                         ids=["jaxpr.pregather"])
    assert _rule_ids(findings) == {"jaxpr.pregather"}


def test_segment_scatter_fixture_flagged():
    ids, plan = _plan()
    data = jnp.ones((plan.num_edges, 8), jnp.float32)
    jx = jax.make_jaxpr(
        lambda d: jax.ops.segment_sum(d, jnp.asarray(ids),
                                      plan.num_segments))(data)
    findings = run_rules(JaxprContext(jx, plan=plan),
                         ids=["jaxpr.segment-scatter"])
    assert _rule_ids(findings) == {"jaxpr.segment-scatter"}


def test_backward_gather_fixture_flagged():
    ids, plan = _plan()
    g = jnp.ones((plan.num_segments, 8), jnp.float32)
    jx = jax.make_jaxpr(lambda g_: g_[jnp.asarray(ids)])(g)
    findings = run_rules(JaxprContext(jx, plan=plan),
                         ids=["jaxpr.backward-gather"])
    assert _rule_ids(findings) == {"jaxpr.backward-gather"}


def test_full_graph_aval_fixture_flagged():
    N, E = 500, 2000
    x = jnp.ones((N, 16), jnp.float32)
    jx = jax.make_jaxpr(lambda x: jnp.tanh(x).sum())(x)
    findings = run_rules(JaxprContext(jx, graph_shape=(N, E)),
                         ids=["jaxpr.full-graph-aval"])
    assert _rule_ids(findings) == {"jaxpr.full-graph-aval"}
    # an exempted (colliding) dim is not flagged
    assert run_rules(JaxprContext(jx, graph_shape=(N, E),
                                  exempt_dims=(N,)),
                     ids=["jaxpr.full-graph-aval"]) == []
    # integer avals of graph width (plan indices) are allowed
    jx_int = jax.make_jaxpr(lambda i: i + 1)(jnp.ones(N, jnp.int32))
    assert run_rules(JaxprContext(jx_int, graph_shape=(N, E)),
                     ids=["jaxpr.full-graph-aval"]) == []


def test_f64_fixture_flagged():
    with jax.enable_x64(True):
        jx = jax.make_jaxpr(lambda x: x * np.float64(2.0))(
            jnp.ones(4, jnp.float64))
    findings = run_rules(JaxprContext(jx), ids=["jaxpr.f64-promotion"])
    assert _rule_ids(findings) == {"jaxpr.f64-promotion"}


def test_host_transfer_fixture_flagged():
    def step(x):
        y = jax.device_put(x)
        return jax.pure_callback(
            lambda a: np.asarray(a), jax.ShapeDtypeStruct(x.shape, x.dtype),
            y)

    jx = jax.make_jaxpr(jax.jit(step))(jnp.ones(4, jnp.float32))
    findings = run_rules(JaxprContext(jx), ids=["jaxpr.host-transfer"])
    assert _rule_ids(findings) == {"jaxpr.host-transfer"}
    assert len(findings) >= 2        # device_put AND the callback


def test_donation_fixture_flagged():
    f = jax.jit(lambda a, b: a + b, donate_argnums=(1,))
    jx = jax.make_jaxpr(f)(jnp.ones(4), jnp.ones(4))
    # expecting 2 donated but only 1 is: mismatch finding
    findings = run_rules(JaxprContext(jx, expect_donated=2),
                         ids=["jaxpr.donation"])
    assert _rule_ids(findings) == {"jaxpr.donation"}
    # the true count verifies clean
    assert run_rules(JaxprContext(jx, expect_donated=1),
                     ids=["jaxpr.donation"]) == []
    # a trace without any pjit equation cannot be verified -> finding
    jx_plain = jax.make_jaxpr(lambda a: a + 1)(jnp.ones(4))
    assert _rule_ids(run_rules(JaxprContext(jx_plain, expect_donated=1),
                               ids=["jaxpr.donation"])) == {"jaxpr.donation"}


def test_vmem_budget_fixture_flagged():
    """segment_max_csc at the documented block geometry with an unsplit
    feature axis (block_d == d == 16384) needs a (BE, BD) = (256, 16384)
    gather scratch — 16 MiB on its own, plus the (BN, BD) output tile and
    the loaded rows — over the 16 MiB budget; the auto-tiled pick (512
    lanes) stays under it. The messages themselves stay in HBM and count
    nothing."""
    from repro.kernels.segment_sum import segment_max_csc
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 40, 96).astype(np.int32)
    plan = build_csc_plan(ids, 40, block_n=128, block_e=256)
    data = jnp.ones((plan.num_edges, 16384), jnp.float32)
    jx = jax.make_jaxpr(lambda d: segment_max_csc(
        d, jnp.asarray(plan.gather_idx), jnp.asarray(plan.local_ids),
        plan.num_blocks, plan.block_n, plan.block_e, block_d=16384,
        interpret=True))(data)
    findings = check_vmem(jx)
    assert _rule_ids(findings) == {"vmem.budget"}
    # the same launch passes at the default 16 MiB? not necessarily —
    # what matters is the auto-tiled geometry stays under it
    jx_auto = jax.make_jaxpr(lambda d: segment_max_csc(
        d, jnp.asarray(plan.gather_idx), jnp.asarray(plan.local_ids),
        plan.num_blocks, plan.block_n, plan.block_e,
        interpret=True))(data)
    assert check_vmem(jx_auto) == []
    # stats reconstruction is sane: every launch reports a grid and bytes
    stats = iter_kernel_stats(jx)
    assert stats and all(s.vmem_bytes > 0 and s.grid for s in stats)
    st = stats[0]
    assert st.scratch_bytes >= 256 * 16384 * 4        # the gather buffer
    # the HBM-resident messages are walked but not priced as VMEM
    assert any(b["memory_space"] == "any" for b in st.blocks)
    assert st.block_bytes == sum(b["bytes"] for b in st.blocks
                                 if b["memory_space"] == "vmem")


def test_srclint_bare_assert_fixture_flagged():
    src = "def f(x):\n    assert x > 0\n    return x\n"
    findings = lint_source(src, "fixture.py")
    assert _rule_ids(findings) == {"src.bare-assert"}


def test_srclint_hot_path_fixtures_flagged():
    src = (
        "import numpy as np\n"
        "def hot(g, sel):\n"
        "    n = g.num_nodes\n"
        "    buf = np.zeros(n, bool)\n"
        "    mask = np.isin(np.arange(g.num_nodes), sel)\n"
        "    return buf, mask\n"
    )
    findings = lint_source(src, "fixture.py", hot={"hot"})
    assert _rule_ids(findings) == {"src.hot-full-graph-alloc",
                                   "src.hot-membership-scan"}
    # outside the hot set the same code is fine
    assert lint_source(src, "fixture.py", hot=set()) == []


def test_srclint_waiver():
    src = ("def f(x):\n"
           "    assert x > 0  # lint: waive=src.bare-assert\n"
           "    assert x < 9\n")
    findings = lint_source(src, "fixture.py")
    assert len(findings) == 1 and findings[0].location.endswith(":3")


def test_srclint_silent_except_flagged():
    src = ("def f():\n"
           "    try:\n"
           "        g()\n"
           "    except OSError:\n"
           "        pass\n"
           "    try:\n"
           "        g()\n"
           "    except Exception:\n"
           "        ...\n")
    findings = lint_source(src, "fixture.py")
    assert _rule_ids(findings) == {"src.silent-except"}
    assert len(findings) == 2
    # a handler that does anything with the error is fine
    ok = ("def f():\n"
          "    try:\n"
          "        g()\n"
          "    except OSError:\n"
          "        return None\n")
    assert lint_source(ok, "fixture.py") == []


def test_srclint_silent_except_waiver_on_pass_line():
    src = ("def f():\n"
           "    try:\n"
           "        g()\n"
           "    except OSError:\n"
           "        pass  # lint: waive=src.silent-except\n")
    assert lint_source(src, "fixture.py") == []


def test_srclint_unjoined_process_flagged():
    src = ("import multiprocessing as mp\n"
           "def launch(fn):\n"
           "    p = mp.Process(target=fn)\n"
           "    p.start()\n"
           "    return p\n")
    findings = lint_source(src, "fixture.py")
    assert _rule_ids(findings) == {"src.unjoined-process"}
    # any join/terminate/kill path anywhere in the file clears it
    supervised = src + ("def close(p):\n"
                        "    p.terminate()\n")
    assert lint_source(supervised, "fixture.py") == []
    joined = src + ("def wait(p):\n"
                    "    p.join()\n")
    assert lint_source(joined, "fixture.py") == []
    # bare-name Process() (from-import) is caught too
    bare = ("from multiprocessing import Process\n"
            "def launch(fn):\n"
            "    Process(target=fn).start()\n")
    assert _rule_ids(lint_source(bare, "fixture.py")) == {
        "src.unjoined-process"}


def test_srclint_unjoined_process_waiver():
    src = ("import multiprocessing as mp\n"
           "def launch(fn):\n"
           "    p = mp.Process(target=fn)  # lint: waive=src.unjoined-process\n"
           "    p.start()\n")
    assert lint_source(src, "fixture.py") == []


# ---------------------------------------------------------------------------
# zero findings on the real thing
# ---------------------------------------------------------------------------


def test_combine_modes_clean():
    """All four combine modes' value_and_grad jaxprs on the csc backend
    pass the full Sum-stage ruleset (incl. VMEM)."""
    report = Report(16 * 1024 * 1024)
    check_combine_modes(report)
    assert report.findings == []
    assert report.contexts == 4
    assert report.kernels        # pallas launches were actually walked


def test_trainer_steps_clean():
    """Every zoo model x backend train step + infer trace passes the
    step-hygiene rules (pregather, f64, host transfer, donation, VMEM)."""
    report = Report(16 * 1024 * 1024)
    check_trainers(report, full=False)
    assert report.findings == []
    assert report.contexts == 16      # 4 models x 2 backends x (step+infer)


def test_compact_trainer_steps_clean():
    """CompactTrainer bucketed steps honor the O(view) aval contract."""
    report = Report(16 * 1024 * 1024)
    check_compact_buckets(report, full=False)
    assert report.findings == []
    assert report.contexts >= 2


def test_srclint_tree_clean():
    assert lint_tree(SRC_ROOT) == []


def test_cli_strict_smoke(tmp_path):
    out = tmp_path / "BENCH_analysis.json"
    rc = run_analysis(strict=True, json_path=str(out),
                      out=lambda *a, **k: None)
    assert rc == 0
    import json
    report = json.loads(out.read_text())
    assert report["findings"] == []
    assert report["contexts_traced"] >= 24
    assert report["kernels"]


def test_cli_strict_fails_on_findings(tmp_path):
    """--strict exits nonzero when the lint root contains a violation."""
    bad = tmp_path / "pkg"
    bad.mkdir()
    (bad / "mod.py").write_text("def f(x):\n    assert x\n    return x\n")
    rc = run_analysis(strict=True, lint_root=str(bad),
                      out=lambda *a, **k: None)
    assert rc == 1


# ---------------------------------------------------------------------------
# registry + shims + satellites
# ---------------------------------------------------------------------------


def test_registry_is_complete():
    for rule_id in ("jaxpr.pregather", "jaxpr.segment-scatter",
                    "jaxpr.backward-gather", "jaxpr.full-graph-aval",
                    "jaxpr.f64-promotion", "jaxpr.host-transfer",
                    "jaxpr.donation", "vmem.budget"):
        assert rule_id in RULES, rule_id
        assert RULES[rule_id].description
    # the CLI rule subsets reference only registered rules
    for subset in (COMBINE_RULES, TRAIN_RULES, COMPACT_RULES):
        assert set(subset) <= set(RULES)


def test_jaxpr_walker_version_robust():
    """The walker's class collection works on this jax (satellite 1) and
    unwraps duck-typed jaxpr-likes."""
    from repro.analysis.jaxpr import (_CLOSED_TYPES, _JAXPR_TYPES,
                                      _as_jaxpr, jaxpr_eqns)
    assert _CLOSED_TYPES and _JAXPR_TYPES
    jx = jax.make_jaxpr(lambda x: x * 2 + 1)(jnp.ones(3))
    assert _as_jaxpr(jx) is jx.jaxpr
    assert len(list(jaxpr_eqns(jx))) >= 2

    class Ducky:     # a foreign ClosedJaxpr-alike
        def __init__(self, inner):
            self.jaxpr = inner

    assert _as_jaxpr(Ducky(jx.jaxpr)) is jx.jaxpr


def test_ops_shims_still_raise_assertionerror():
    """Legacy callers use pytest.raises(AssertionError): ContractError
    must satisfy them, with the historical message fragments."""
    from repro.kernels.ops import (assert_pregather_free,
                                   assert_sum_stage_fused)
    ids, plan = _plan()
    data = jnp.ones((plan.num_edges, 8), jnp.float32)
    jx = jax.make_jaxpr(
        lambda d: jax.ops.segment_sum(d, jnp.asarray(ids),
                                      plan.num_segments))(data)
    with pytest.raises(AssertionError, match="reference"):
        assert_sum_stage_fused(jx, plan)
    with pytest.raises(ContractError):
        assert_sum_stage_fused(jx, plan)
    jx_pre = jax.make_jaxpr(
        lambda d: d[jnp.asarray(plan.gather_idx) % plan.num_edges].sum())(
            data)
    with pytest.raises(AssertionError, match="pre-gather"):
        assert_pregather_free(jx_pre, plan)


def test_bare_assert_sweep_raises_valueerror():
    """The converted guards raise typed errors with messages (satellite
    2) — spot-check the kernel wrappers' preconditions."""
    from repro.kernels.ops import segment_sum_op
    ids, plan = _plan()
    with pytest.raises(ValueError, match="edge axis"):
        segment_sum_op(jnp.ones((plan.num_edges + 1, 4), jnp.float32),
                       plan)
    with pytest.raises(ValueError, match="n_chunks"):
        build_csc_plan(ids, 40, block_n=16, block_e=32, n_chunks=1)
