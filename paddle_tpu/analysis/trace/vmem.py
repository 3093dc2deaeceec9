"""TPU504 — static VMEM-budget estimation for Pallas kernels.

Every Pallas kernel's per-core working set is statically determined by its
BlockSpecs: Mosaic keeps one ``block_shape`` tile per input/output operand
resident in VMEM (double-buffered whenever the grid revisits the buffer,
which is the common case), plus every ``pltpu.VMEM`` scratch allocation in
full.  A candidate whose tiles don't fit the ~16 MiB per-core VMEM faults
*on device* — after a TPU session was already burned on tracing, compiling
and shipping it.  This module reads the exact same ``grid_mapping`` the
compiler consumes (off the traced ``pallas_call`` equation) and prices the
working set up front, so:

* the **TPU504 pass** audits every registered kernel-variant program in
  the canonical registry, and
* :func:`paddle_tpu.kernels.autotune.tune` rejects unfittable candidates
  **before compile** (they show up as ``rejected: vmem`` in the timing
  table instead of faulting mid-warm).

The model is deliberately a *budget*, not a simulator: operands mapped to
``ANY`` memory stay in HBM (their kernels DMA chunks through explicit
scratch, which IS counted), index/scalar-prefetch operands live in SMEM,
and a safety reserve is held back for Mosaic's own spills/semaphores.
Overestimating by a tile is fine; underestimating wastes a TPU session.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Iterable, List, Optional

from ..core import Finding
from .core import TracePass, TraceProgram, walk_eqns

__all__ = ["VMEM_LIMIT_BYTES", "VMEM_RESERVE_BYTES", "KernelFootprint",
           "pallas_footprints", "footprint_of_callable", "fits_vmem",
           "VmemBudgetPass"]

#: per-core VMEM on the supported TPU generations (v4/v5e/v5p all carry
#: 16 MiB per TensorCore; PERF.md's measured overflow at s=8192 confirms
#: the kernels are budgeted against this number).  Overridable for future
#: parts via PADDLE_TPU_VMEM_LIMIT_MB.
VMEM_LIMIT_BYTES = int(float(os.environ.get("PADDLE_TPU_VMEM_LIMIT_MB",
                                            "16")) * 1024 * 1024)

#: held back for Mosaic-managed temporaries, semaphores and register
#: spills — the compiler's own working set that BlockSpecs don't show.
VMEM_RESERVE_BYTES = 1024 * 1024


class KernelFootprint:
    """Static VMEM price of one ``pallas_call``."""

    def __init__(self, name: str, op_path: str):
        self.name = name
        self.op_path = op_path
        self.operand_bytes = 0      # double-buffered block tiles
        self.scratch_bytes = 0      # explicit VMEM scratch, counted once
        self.detail: List[str] = []

    @property
    def total_bytes(self) -> int:
        return self.operand_bytes + self.scratch_bytes

    def fits(self, limit: Optional[int] = None,
             reserve: Optional[int] = None) -> bool:
        limit = VMEM_LIMIT_BYTES if limit is None else limit
        reserve = VMEM_RESERVE_BYTES if reserve is None else reserve
        return self.total_bytes <= max(0, limit - reserve)

    def summary(self) -> str:
        return ("%s: %.0f KiB blocks + %.0f KiB scratch = %.0f KiB "
                "(limit %.0f KiB - %.0f KiB reserve)"
                % (self.name, self.operand_bytes / 1024,
                   self.scratch_bytes / 1024, self.total_bytes / 1024,
                   VMEM_LIMIT_BYTES / 1024, VMEM_RESERVE_BYTES / 1024))


def _ref_bytes(ref) -> int:
    """VMEM bytes of one kernel ref aval (``shape``/``dtype``/
    ``memory_space``): operands left in HBM (ANY), SMEM refs and
    semaphores are not VMEM tiles and price to 0."""
    space = str(getattr(ref, "memory_space", None)).lower()
    if "any" in space or "smem" in space or "semaphore" in space:
        return 0
    n = 1
    for d in ref.shape:
        n *= int(d)
    return n * ref.dtype.itemsize


def pallas_footprints(closed_jaxpr, name: str = "<program>"
                      ) -> List[KernelFootprint]:
    """Footprint of every ``pallas_call`` reachable in a (Closed)Jaxpr."""
    out = []
    for site in walk_eqns(closed_jaxpr, into_pallas=False):
        if site.eqn.primitive.name != "pallas_call":
            continue
        gm = site.eqn.params["grid_mapping"]
        fp = KernelFootprint(name, site.path)
        # grid of extent 1 is visited once — no pipelining, single buffer
        multi_step = 1
        for g in gm.grid:
            multi_step *= int(g) if isinstance(g, int) else 2
        dbuf = 2 if multi_step > 1 else 1
        for bm in gm.block_mappings:
            # block_aval is the per-grid-step tile the kernel body sees
            # (squeezed dims already dropped to extent 1)
            ref = bm.block_aval
            b = _ref_bytes(ref) * dbuf
            if not b:
                continue      # ANY-space: stays in HBM, DMA'd via scratch
            fp.operand_bytes += b
            fp.detail.append("block%s %s x%d = %d B"
                             % (tuple(ref.shape), ref.dtype, dbuf, b))
        for ref in gm.scratch_avals:
            b = _ref_bytes(ref)
            if b:
                fp.scratch_bytes += b
                fp.detail.append("scratch%s %s = %d B"
                                 % (tuple(ref.shape), ref.dtype, b))
        out.append(fp)
    return out


def footprint_of_callable(fn, *example_args) -> List[KernelFootprint]:
    """Trace ``fn`` abstractly (ShapeDtypeStructs work; nothing executes,
    nothing compiles) and price its pallas_calls.  The autotuner's
    pre-compile gate."""
    import jax
    jaxpr = jax.make_jaxpr(fn)(*example_args)
    return pallas_footprints(jaxpr)


def fits_vmem(fn, *example_args) -> (bool, str):
    """(fits, human reason) for every pallas_call in ``fn``."""
    fps = footprint_of_callable(fn, *example_args)
    for fp in fps:
        if not fp.fits():
            return False, fp.summary()
    return True, ""


class VmemBudgetPass(TracePass):
    """TPU504: every Pallas kernel program's static block+scratch working
    set fits the per-core VMEM budget."""

    rule = "TPU504"
    name = "vmem_budget"
    description = ("Pallas BlockSpec working set (double-buffered blocks + "
                   "VMEM scratch) fits per-core VMEM")

    def check(self, program: TraceProgram) -> Iterable[Finding]:
        if program.jaxpr is None:
            return
        for fp in pallas_footprints(program.jaxpr, program.name):
            if not fp.fits():
                yield self.finding(
                    program, fp.op_path,
                    "VMEM budget exceeded: %s" % fp.summary())
