"""Jaxpr rule registry: the contract asserts of ``kernels/ops.py``, generalized.

The repo's memory/fusion invariants (no pre-gathered message tensor, no
reference segment scatter on the csc path, O(view) compact steps, ...)
used to live as one-off ``assert`` helpers scattered through
``kernels/ops.py`` and the trainers. This module turns them into a
:class:`Rule` registry over traced jaxprs: every rule walks the same
generalized :func:`jaxpr_eqns` iterator, produces :class:`Finding`
records (rule id, severity, location), and is runnable from tests, the
benches, and the ``python -m repro.analysis`` CI gate alike.

Rule catalog (jaxpr family):

=======================  ====================================================
``jaxpr.pregather``      no ``(n_chunks, BE, ...)`` float aval — the
                         pre-gathered message layout the fused kernels
                         eliminated
``jaxpr.segment-scatter``no scatter primitive whose updates carry the plan's
                         edge axis (a reference ``jax.ops.segment_*`` call)
``jaxpr.backward-gather``no ``(N, ...) -> (E, ...)`` gather outside the
                         kernels (the old ``g[segment_ids]`` backward)
``jaxpr.full-graph-aval``no full-graph-shaped ``(N_full, ...)``/``(E_full,
                         ...)`` float aval inside a bucketed compact step —
                         PR 6's O(view) memory claim, machine-checked
``jaxpr.f64-promotion``  no float64 aval anywhere (dtype-promotion drift)
``jaxpr.host-transfer``  no host<->device transfer / callback primitive
                         inside the jitted step
``jaxpr.donation``       the staged view buffers are donated exactly as the
                         trainer promised (``donated_invars`` of the step's
                         pjit equation)
=======================  ====================================================

``vmem.budget`` (Pallas launch geometry) registers itself from
:mod:`repro.analysis.vmem`; the source lint lives in
:mod:`repro.analysis.srclint`.

The legacy helpers (``assert_pregather_free`` / ``assert_sum_stage_fused``
/ ``count_segment_scatters``) survive as thin shims in ``kernels/ops.py``
delegating here and raising :class:`ContractError` — an
``AssertionError`` subclass, so existing ``pytest.raises(AssertionError)``
callers keep passing.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Optional, Tuple

import jax.numpy as jnp


class ContractError(AssertionError):
    """A registry rule found a violation in assert-mode (the shim API)."""


# ---------------------------------------------------------------------------
# findings
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Finding:
    """One rule violation: what rule, where, and what was seen."""
    rule: str                    # registry id, e.g. "jaxpr.pregather"
    message: str                 # human-readable description of the hit
    severity: str = "error"      # "error" | "warning"
    label: str = ""              # which traced computation was analyzed
    location: str = ""           # eqn/aval/source location when known

    def render(self) -> str:
        where = f" [{self.label}]" if self.label else ""
        loc = f" ({self.location})" if self.location else ""
        return f"{self.severity}: {self.rule}{where}: {self.message}{loc}"

    def to_json(self) -> dict:
        return {"rule": self.rule, "severity": self.severity,
                "label": self.label, "message": self.message,
                "location": self.location}


# ---------------------------------------------------------------------------
# the generalized jaxpr walker (version-robust across jax releases)
# ---------------------------------------------------------------------------


def _jaxpr_classes() -> Tuple[tuple, tuple]:
    """(ClosedJaxpr types, Jaxpr types) across jax versions.

    Newer jax exposes the public copies under ``jax.extend.core`` and
    deprecates (then removes) the ``jax.core`` names; older releases only
    have ``jax.core``. Collect every importable variant so isinstance
    checks hold whichever module produced the object.
    """
    closed, plain = [], []
    for modname in ("jax.extend.core", "jax.core"):
        try:
            import importlib
            mod = importlib.import_module(modname)
        except ImportError:
            continue
        for name, bucket in (("ClosedJaxpr", closed), ("Jaxpr", plain)):
            cls = getattr(mod, name, None)
            if isinstance(cls, type) and cls not in bucket:
                bucket.append(cls)
    return tuple(closed), tuple(plain)


_CLOSED_TYPES, _JAXPR_TYPES = _jaxpr_classes()


def _as_jaxpr(obj):
    """Duck-typed unwrap: ClosedJaxpr-like -> Jaxpr-like -> None."""
    if isinstance(obj, _JAXPR_TYPES):
        return obj
    if isinstance(obj, _CLOSED_TYPES):
        return obj.jaxpr
    # fallback for versions whose classes import from neither module:
    # anything with .eqns is jaxpr-like; anything wrapping one is closed
    if hasattr(obj, "eqns"):
        return obj
    inner = getattr(obj, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return inner
    return None


def jaxpr_eqns(closed_jaxpr, skip_pallas_bodies: bool = False):
    """Yield every equation, recursing into sub-jaxprs (pjit bodies,
    custom_vjp calls, scans, pallas kernel bodies ...) — including the
    VJP jaxprs ``jax.grad``/``jax.value_and_grad`` splice in, so the
    fused-path contracts certify the backward pass too.

    ``skip_pallas_bodies`` stops the recursion at ``pallas_call``
    equations: the gather/scatter fallback checks must not flag the
    kernels' own on-chip block gathers (whose tile shapes can collide
    with the edge/segment dims, e.g. when E == block_e).
    """
    root = _as_jaxpr(closed_jaxpr)
    stack = [root] if root is not None else []
    while stack:
        jaxpr = stack.pop()
        for eqn in jaxpr.eqns:
            yield eqn
            if skip_pallas_bodies and eqn.primitive.name == "pallas_call":
                continue
            for val in eqn.params.values():
                for sub in (val if isinstance(val, (tuple, list))
                            else (val,)):
                    inner = None
                    if isinstance(sub, (str, bytes, int, float, bool,
                                        type(None))):
                        continue
                    inner = _as_jaxpr(sub)
                    if inner is not None:
                        stack.append(inner)


def jaxpr_avals(closed_jaxpr, skip_pallas_bodies: bool = False):
    """Yield the output aval of every equation, recursing into sub-jaxprs
    (``skip_pallas_bodies`` as in :func:`jaxpr_eqns`)."""
    for eqn in jaxpr_eqns(closed_jaxpr, skip_pallas_bodies):
        for var in eqn.outvars:
            yield var.aval


def pallas_src(eqn) -> str:
    """Best-effort kernel source location of a ``pallas_call`` equation."""
    info = eqn.params.get("name_and_src_info")
    return str(info) if info is not None else eqn.primitive.name


# ---------------------------------------------------------------------------
# rule framework
# ---------------------------------------------------------------------------


@dataclass
class JaxprContext:
    """Everything a jaxpr rule may need about one traced computation.

    Optional fields gate rules: a rule requiring ``plan`` (the CSC
    contracts) silently skips contexts without one, and so on — so one
    ``run_rules`` call over a context runs exactly the applicable subset.
    """
    closed_jaxpr: object
    label: str = ""
    # CSC-plan contracts (pregather / segment-scatter / backward-gather)
    plan: Optional[object] = None            # kernels.ops.CSCPlan
    # compact-step O(view) contract: the FULL graph's (N, E); dims that
    # legitimately appear (e.g. a bucket pad that collides) go in exempt
    graph_shape: Optional[Tuple[int, int]] = None
    exempt_dims: Tuple[int, ...] = ()
    # donation contract: how many invars of the step's pjit equation must
    # be donated (None = not checked for this context)
    expect_donated: Optional[int] = None
    # VMEM budget for pallas_call launches (bytes)
    vmem_budget: int = 16 * 1024 * 1024
    extra: dict = field(default_factory=dict)


@dataclass(frozen=True)
class Rule:
    id: str
    description: str
    check: Callable[[JaxprContext], List[Finding]]


RULES: Dict[str, Rule] = {}


def register(rule: Rule) -> Rule:
    if rule.id in RULES:
        raise ValueError(f"duplicate rule id {rule.id!r}")
    RULES[rule.id] = rule
    return rule


def rule(id: str, description: str):
    """Decorator: register ``fn(ctx) -> [Finding, ...]`` under ``id``."""
    def wrap(fn):
        register(Rule(id, description, fn))
        return fn
    return wrap


def run_rules(ctx: JaxprContext,
              ids: Optional[Iterable[str]] = None) -> List[Finding]:
    """Run the selected rules (default: all registered) over one context."""
    selected = list(RULES.values()) if ids is None else [
        RULES[i] for i in ids]
    findings: List[Finding] = []
    for r in selected:
        findings.extend(r.check(ctx))
    return findings


def check_or_raise(findings: List[Finding]) -> None:
    """Shim helper: raise :class:`ContractError` on any error finding."""
    errors = [f for f in findings if f.severity == "error"]
    if errors:
        raise ContractError("\n".join(f.render() for f in errors))


# ---------------------------------------------------------------------------
# ported CSC-plan contracts (from kernels/ops.py)
# ---------------------------------------------------------------------------


_SCATTER_PRIMS = ("scatter", "scatter-add", "scatter-max", "scatter-min",
                  "scatter-mul")


def _is_segment_scatter(eqn, num_edges: int) -> bool:
    """A scatter whose updates carry the plan's edge axis — the signature
    of a reference ``jax.ops.segment_*`` call (forward or transpose)."""
    if eqn.primitive.name not in _SCATTER_PRIMS:
        return False
    upd = tuple(getattr(eqn.invars[-1].aval, "shape", ()))
    return bool(upd) and upd[0] == num_edges


def count_segment_scatters(closed_jaxpr, plan) -> int:
    """Number of scatter equations whose updates carry the plan's edge
    axis. On model-level jaxprs this can't distinguish a Sum-stage
    fallback from the legitimate NN-Gather transpose, so the end-to-end
    certificate compares the count across backends (csc strictly below
    reference) while the combine-level rules demand zero."""
    return sum(_is_segment_scatter(eqn, plan.num_edges)
               for eqn in jaxpr_eqns(closed_jaxpr, skip_pallas_bodies=True))


@rule("jaxpr.pregather",
      "no (n_chunks, BE, ...) float aval — the pre-gathered message "
      "layout the fused kernels eliminated")
def _check_pregather(ctx: JaxprContext) -> List[Finding]:
    if ctx.plan is None:
        return []
    n_chunks, block_e = ctx.plan.gather_idx.shape[-2:]
    findings = []
    # kernel-body values are VMEM tiles, not HBM tensors: a (1, BE) tile
    # collides with (n_chunks, BE) when the plan has one chunk
    for aval in jaxpr_avals(ctx.closed_jaxpr, skip_pallas_bodies=True):
        shape = tuple(getattr(aval, "shape", ()))
        if len(shape) < 2 or shape[:2] != (n_chunks, block_e):
            continue
        pregather = len(shape) >= 3 or jnp.issubdtype(
            getattr(aval, "dtype", jnp.int32), jnp.floating)
        if pregather:
            findings.append(Finding(
                "jaxpr.pregather",
                f"pre-gathered message tensor {shape} found in jaxpr "
                f"(plan: n_chunks={n_chunks}, BE={block_e})",
                label=ctx.label))
    return findings


@rule("jaxpr.segment-scatter",
      "no scatter primitive with edge-axis updates on the csc path (a "
      "reference jax.ops.segment_* fallback)")
def _check_segment_scatter(ctx: JaxprContext) -> List[Finding]:
    if ctx.plan is None:
        return []
    E = ctx.plan.num_edges
    findings = []
    for eqn in jaxpr_eqns(ctx.closed_jaxpr, skip_pallas_bodies=True):
        if _is_segment_scatter(eqn, E):
            findings.append(Finding(
                "jaxpr.segment-scatter",
                f"reference segment scatter ({eqn.primitive.name}) found "
                f"on the csc path (E={E})", label=ctx.label))
    return findings


@rule("jaxpr.backward-gather",
      "no (N, ...) -> (E, ...) gather outside the kernels (the old "
      "g[segment_ids] reference backward)")
def _check_backward_gather(ctx: JaxprContext) -> List[Finding]:
    if ctx.plan is None:
        return []
    E, N = ctx.plan.num_edges, ctx.plan.num_segments
    findings = []
    for eqn in jaxpr_eqns(ctx.closed_jaxpr, skip_pallas_bodies=True):
        if eqn.primitive.name != "gather":
            continue
        src = tuple(getattr(eqn.invars[0].aval, "shape", ()))
        out = tuple(getattr(eqn.outvars[0].aval, "shape", ()))
        if out and src and out[0] == E and src[0] == N:
            findings.append(Finding(
                "jaxpr.backward-gather",
                f"reference backward gather ({src} -> {out}) found on "
                f"the csc path (E={E}, N={N})", label=ctx.label))
    return findings


# ---------------------------------------------------------------------------
# new rules
# ---------------------------------------------------------------------------


@rule("jaxpr.full-graph-aval",
      "no full-graph-shaped (N, ...)/(E, ...) float aval inside a "
      "bucketed compact step (the O(view) memory contract)")
def _check_full_graph_aval(ctx: JaxprContext) -> List[Finding]:
    if ctx.graph_shape is None:
        return []
    forbidden = {d for d in ctx.graph_shape if d not in ctx.exempt_dims}
    if not forbidden:
        return []
    findings = []
    for aval in jaxpr_avals(ctx.closed_jaxpr):
        shape = tuple(getattr(aval, "shape", ()))
        if not shape or shape[0] not in forbidden:
            continue
        if not jnp.issubdtype(getattr(aval, "dtype", jnp.int32),
                              jnp.floating):
            continue
        findings.append(Finding(
            "jaxpr.full-graph-aval",
            f"full-graph-shaped float aval {shape} inside a compact "
            f"step (graph N, E = {ctx.graph_shape}) — device memory "
            "must scale with the view, not the graph", label=ctx.label))
    return findings


@rule("jaxpr.f64-promotion",
      "no float64 aval anywhere in the step (dtype-promotion drift)")
def _check_f64(ctx: JaxprContext) -> List[Finding]:
    findings = []
    for eqn in jaxpr_eqns(ctx.closed_jaxpr):
        for var in eqn.outvars:
            dtype = getattr(var.aval, "dtype", None)
            if dtype is not None and str(dtype) == "float64":
                findings.append(Finding(
                    "jaxpr.f64-promotion",
                    f"float64 aval {tuple(var.aval.shape)} produced by "
                    f"'{eqn.primitive.name}' — a weak f64 constant or "
                    "np.float64 scalar is promoting the compute dtype",
                    label=ctx.label))
                break       # one finding per equation is enough
    return findings


_TRANSFER_PRIMS = frozenset({
    "device_put", "copy_to_host_async", "pure_callback", "io_callback",
    "debug_callback", "callback", "infeed", "outfeed",
})


@rule("jaxpr.host-transfer",
      "no host<->device transfer or callback primitive inside the "
      "jitted train step")
def _check_host_transfer(ctx: JaxprContext) -> List[Finding]:
    findings = []
    for eqn in jaxpr_eqns(ctx.closed_jaxpr):
        if eqn.primitive.name in _TRANSFER_PRIMS:
            findings.append(Finding(
                "jaxpr.host-transfer",
                f"host-transfer primitive '{eqn.primitive.name}' inside "
                "the jitted step — every step pays a host sync",
                label=ctx.label))
    return findings


@rule("jaxpr.donation",
      "the staged view buffers are donated exactly as promised "
      "(donated_invars of the step's pjit equation)")
def _check_donation(ctx: JaxprContext) -> List[Finding]:
    if ctx.expect_donated is None:
        return []
    # the traced step is itself jitted, so the outermost equation(s) are
    # pjit calls carrying donated_invars; sum over them
    donated = None
    root = _as_jaxpr(ctx.closed_jaxpr)
    for eqn in (root.eqns if root is not None else ()):
        flags = eqn.params.get("donated_invars")
        if flags is not None:
            donated = (donated or 0) + sum(bool(f) for f in flags)
    if donated is None:
        return [Finding(
            "jaxpr.donation",
            "no pjit equation with donated_invars found — trace the "
            "jitted step itself (jax.make_jaxpr(trainer._step))",
            label=ctx.label)]
    if donated != ctx.expect_donated:
        return [Finding(
            "jaxpr.donation",
            f"{donated} invars donated, expected {ctx.expect_donated} "
            "(the staged view buffers must be donated on accelerator "
            "backends and not on cpu)", label=ctx.label)]
    return []
