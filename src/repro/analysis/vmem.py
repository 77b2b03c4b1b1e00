"""Static VMEM-budget analyzer for Pallas launches.

The block geometry of the CSC kernels is *documented* in
``kernels/segment_sum.py`` ("Block geometry & VMEM budget") — this module
checks it. Walking a traced jaxpr, every ``pallas_call`` equation carries
its full launch geometry in params: ``grid_mapping`` holds the grid and
one BlockMapping per tensor operand/output (the block's ref aval with its
memory space), and the kernel body rides along as a sub-jaxpr whose
trailing invars are the scratch buffers. Per-launch residency is
reconstructed as:

- **block residency** — Σ over VMEM BlockMappings of the block's bytes:
  what the pipeline keeps in VMEM per grid step. Operands left in HBM
  (``memory_space=pl.ANY``, e.g. the gathered messages and cotangents)
  are not staged and price in at zero;
- **scratch** — Σ over the kernel's VMEM scratch buffers (the
  ``(BE, BD)`` gather buffers, softmax accumulators);
- **peak temporary** — max over kernel-body equations of that equation's
  summed output-aval bytes: a lower-bound proxy for the largest
  intermediate the body materializes (a loaded gather buffer, the
  one-hot tile);
- **SMEM residency** — scalar-prefetch operands, SMEM blocks and SMEM
  scratch, reported but not budgeted (plan chunks are KiB-scale).

A kernel whose ``block + scratch + peak-temp`` bytes exceed the
configurable budget (default 16 MiB — one TPU core's VMEM) yields a
``vmem.budget`` finding, so geometry regressions die in CI instead of
OOMing on a TPU. The model is deliberately conservative-simple:
double-buffering overhead and compiler scratch aren't modeled, which is
why the default budget is the full core rather than the documented
half-core design point.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from repro.analysis.jaxpr import (Finding, JaxprContext, jaxpr_eqns,
                                  pallas_src, rule)

DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024      # one TPU core, bytes


def _aval_bytes(aval) -> int:
    shape = tuple(getattr(aval, "shape", ()))
    dtype = getattr(aval, "dtype", None)
    try:
        itemsize = np.dtype(str(dtype)).itemsize
    except TypeError:             # refs, semaphores, tokens: no bytes
        return 0
    return int(math.prod(shape)) * itemsize


def _space(aval) -> str:
    """Memory space of a kernel ref ("vmem" when unannotated)."""
    return str(getattr(aval, "memory_space", None) or "vmem").lower()


@dataclass
class KernelStats:
    """Reconstructed per-launch residency of one ``pallas_call``."""
    name: str                   # kernel fn + source location
    grid: tuple
    block_bytes: int            # Σ VMEM block residency over operands
    peak_temp_bytes: int        # largest kernel-body intermediate
    smem_bytes: int             # scalar-prefetch / SMEM blocks + scratch
    scratch_bytes: int = 0      # Σ VMEM scratch buffers
    blocks: List[dict] = field(default_factory=list)

    @property
    def vmem_bytes(self) -> int:
        return self.block_bytes + self.scratch_bytes + self.peak_temp_bytes

    def to_json(self) -> dict:
        return {"name": self.name, "grid": list(self.grid),
                "block_bytes": self.block_bytes,
                "scratch_bytes": self.scratch_bytes,
                "peak_temp_bytes": self.peak_temp_bytes,
                "vmem_bytes": self.vmem_bytes,
                "smem_bytes": self.smem_bytes,
                "blocks": self.blocks}


def analyze_pallas_eqn(eqn) -> Optional[KernelStats]:
    """KernelStats for one ``pallas_call`` equation (None if the params
    don't carry a grid mapping — foreign/legacy lowering)."""
    gm = eqn.params.get("grid_mapping")
    if gm is None:
        return None
    block_bytes = smem = 0
    blocks = []
    for bm in gm.block_mappings:
        aval = bm.transformed_block_aval
        space, nbytes = _space(aval), _aval_bytes(aval)
        if space == "vmem":
            block_bytes += nbytes
        elif space == "smem":
            smem += nbytes
        blocks.append({"block_shape": list(aval.shape),
                       "dtype": str(aval.dtype), "bytes": nbytes,
                       "memory_space": space})
    # scalar-prefetch operands are the leading invars, excluded from
    # block_mappings; they live in SMEM
    n_idx = int(getattr(gm, "num_index_operands", 0))
    smem += sum(_aval_bytes(v.aval) for v in eqn.invars[:n_idx])
    body = eqn.params.get("jaxpr")
    scratch = peak = 0
    if body is not None:
        n_scratch = int(getattr(gm, "num_scratch_operands", 0))
        for v in body.invars[len(body.invars) - n_scratch:]:
            space = _space(v.aval)
            if space == "vmem":
                scratch += _aval_bytes(v.aval)
            elif space == "smem":
                smem += _aval_bytes(v.aval)
        # peak body intermediate: the largest single equation's outputs
        for beqn in jaxpr_eqns(body):
            peak = max(peak, sum(_aval_bytes(v.aval)
                                 for v in beqn.outvars))
    return KernelStats(name=pallas_src(eqn),
                       grid=tuple(int(g) for g in gm.grid),
                       block_bytes=block_bytes, peak_temp_bytes=peak,
                       smem_bytes=smem, scratch_bytes=scratch,
                       blocks=blocks)


def iter_kernel_stats(closed_jaxpr) -> List[KernelStats]:
    """Stats for every ``pallas_call`` reachable from the traced jaxpr
    (including those spliced into VJP sub-jaxprs by value_and_grad)."""
    out = []
    for eqn in jaxpr_eqns(closed_jaxpr):
        if eqn.primitive.name == "pallas_call":
            stats = analyze_pallas_eqn(eqn)
            if stats is not None:
                out.append(stats)
    return out


def check_vmem(closed_jaxpr, budget: int = DEFAULT_VMEM_BUDGET,
               label: str = "") -> List[Finding]:
    """``vmem.budget`` findings for every launch exceeding ``budget``."""
    findings = []
    for stats in iter_kernel_stats(closed_jaxpr):
        if stats.vmem_bytes > budget:
            findings.append(Finding(
                "vmem.budget",
                f"per-launch VMEM residency {stats.vmem_bytes / 2**20:.1f}"
                f" MiB (blocks {stats.block_bytes / 2**20:.1f} MiB + "
                f"scratch {stats.scratch_bytes / 2**20:.1f} MiB + peak "
                f"temp {stats.peak_temp_bytes / 2**20:.1f} MiB) exceeds "
                f"the {budget / 2**20:.1f} MiB budget; grid={stats.grid}",
                label=label, location=stats.name))
    return findings


@rule("vmem.budget",
      "every pallas_call launch's reconstructed VMEM residency (blocks "
      "+ scratch + peak body temporary) fits the configured budget")
def _check_vmem_rule(ctx: JaxprContext) -> List[Finding]:
    return check_vmem(ctx.closed_jaxpr, budget=ctx.vmem_budget,
                      label=ctx.label)
