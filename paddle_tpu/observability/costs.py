"""Compiled-program cost & memory reports — XLA's own numbers, surfaced.

COVERAGE.md §2.3 declared the reference framework's op-level cost model a
non-goal *because* "XLA cost analysis runs on the actual lowered program".
This module cashes that claim: every lowered/compiled entry point can be
priced with the compiler's own ``cost_analysis()`` (FLOPs, bytes accessed,
transcendentals) and ``memory_analysis()`` (argument/output/temp/alias/
generated-code bytes), and the canonical trace-audit registry
(:mod:`paddle_tpu.analysis.trace.programs`) is priced wholesale:

* :func:`registry_reports` — one :class:`ProgramReport` per canonical
  program (the ``python -m paddle_tpu.observability programs`` CLI);
* TPU506 (:mod:`paddle_tpu.analysis.trace.hbm_budget`) compares each
  report's derived peak-HBM against a declared per-program budget — the
  post-compile complement to TPU504's pre-compile VMEM estimate;
* :func:`cost_block` — the schema'd ``cost`` block bench.py /
  bench_decode.py attach to their JSON lines ({flops, hbm_bytes,
  peak_bytes, mfu, bw_util}), with MFU / HBM-bandwidth-utilization
  derived only when on-chip step timings exist (CPU lines carry the
  static fields and ``null`` utilizations — the trajectory gate
  validates their shape but never perf-gates them).

Graceful degradation is the contract, not an accident: backends report
different subsets (CPU's ``generated_code_size_in_bytes`` is 0, TPU adds
real code/temp sizes; Pallas kernels price their interpret-mode lowering
off-chip; ONE extraction helper — :func:`cost_analysis_dict` — which
``hapi.flops`` also routes through), and a missing field is ``None``,
never a guess.

Derived peak: ``memory_analysis()`` exposes no single peak-memory scalar, so
``peak_bytes = argument + output + temp - alias`` — the executable's
whole-BUFFER high-water bound (donated/aliased buffers counted once;
generated code is reported separately and excluded on purpose: code
size varies wildly per backend and is not the data-buffer regression
vector the TPU506 budgets gate).  The budgets are sized against this
same derivation, so the gate is self-consistent.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import scopes as _scopes

__all__ = [
    "ProgramReport", "cost_analysis_dict", "memory_analysis_dict",
    "report_from_compiled", "compile_program", "report_for_program",
    "registry_reports", "peak_flops", "peak_hbm_bandwidth", "mfu",
    "bw_util", "cost_block", "format_table",
]

# ---------------------------------------------------------------------------
# per-part peak specs (published numbers, per chip; Google Cloud TPU
# documentation), substring-matched against jax's device_kind.  One v5e
# chip reports itself as "TPU v5 lite".  A TPU that is not in the table
# is an error, never an assumed peak.
# ---------------------------------------------------------------------------

#: bf16 peak FLOP/s per chip by device-kind substring (lowercase).
PEAK_FLOPS_BY_KIND = (
    ("v6e", 918e12), ("v5p", 459e12),
    ("v5 lite", 197e12), ("v5e", 197e12),
    ("v4", 275e12), ("v3", 123e12), ("v2", 46e12),
)

#: HBM bandwidth bytes/s per chip by device-kind substring (lowercase).
PEAK_HBM_BW_BY_KIND = (
    ("v6e", 1640e9), ("v5p", 2765e9),
    ("v5 lite", 819e9), ("v5e", 819e9),
    ("v4", 1228e9), ("v3", 900e9), ("v2", 700e9),
)


def _kind_lookup(table, kind: Optional[str]) -> Optional[float]:
    """Peak for ``kind`` (default: this process's first device).  None on
    a CPU; an accelerator kind missing from the table raises."""
    if kind is None:
        import jax
        kind = jax.devices()[0].device_kind
    kind = kind.lower()
    if kind == "cpu":
        return None
    for sub, v in table:
        if sub in kind:
            return v
    raise ValueError(
        "no published peak for device kind %r — add the part to "
        "observability/costs.py PEAK_*_BY_KIND with its source" % kind)


def peak_flops(device_kind: Optional[str] = None) -> Optional[float]:
    """Peak bf16 FLOP/s of one chip (None on a CPU)."""
    return _kind_lookup(PEAK_FLOPS_BY_KIND, device_kind)


def peak_hbm_bandwidth(device_kind: Optional[str] = None
                       ) -> Optional[float]:
    """Peak HBM bytes/s of one chip (None on a CPU)."""
    return _kind_lookup(PEAK_HBM_BW_BY_KIND, device_kind)


def mfu(flops: Optional[float], step_seconds: Optional[float],
        device_kind: Optional[str] = None) -> Optional[float]:
    """Model FLOPs utilization of one compiled step: program FLOPs /
    (step wall seconds * chip peak).  None whenever any input is
    unknown — a fabricated 0.0 would enter the trajectory as a datum."""
    if not flops or not step_seconds or step_seconds <= 0:
        return None
    peak = peak_flops(device_kind)
    return flops / (step_seconds * peak) if peak else None


def bw_util(hbm_bytes: Optional[float], step_seconds: Optional[float],
            device_kind: Optional[str] = None) -> Optional[float]:
    """HBM bandwidth utilization: program bytes-accessed / (step wall
    seconds * chip peak bandwidth)."""
    if not hbm_bytes or not step_seconds or step_seconds <= 0:
        return None
    peak = peak_hbm_bandwidth(device_kind)
    return hbm_bytes / (step_seconds * peak) if peak else None


# ---------------------------------------------------------------------------
# extraction (hapi.flops routes through these too)
# ---------------------------------------------------------------------------

def cost_analysis_dict(compiled, strict: bool = False) -> Dict[str, float]:
    """``compiled.cost_analysis()`` as a plain dict.  A backend
    that reports nothing yields ``{}``; a RAISING backend is swallowed
    to ``{}`` only under ``strict=False`` (the ProgramReport path, which
    carries available/note fields for the degradation) — ``strict=True``
    propagates it for callers with no such channel (``hapi.flops``
    must error, not answer 0, when the analysis itself fails)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        if strict:
            raise
        return {}
    return dict(ca) if ca else {}


#: memory_analysis attributes extracted when present (per-backend subset)
_MEMORY_FIELDS = (
    ("argument_bytes", "argument_size_in_bytes"),
    ("output_bytes", "output_size_in_bytes"),
    ("temp_bytes", "temp_size_in_bytes"),
    ("alias_bytes", "alias_size_in_bytes"),
    ("generated_code_bytes", "generated_code_size_in_bytes"),
)


def memory_analysis_dict(compiled) -> Dict[str, int]:
    """``compiled.memory_analysis()`` as a plain dict of the fields this
    backend reports (missing attributes are omitted, not guessed)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    out: Dict[str, int] = {}
    for name, attr in _MEMORY_FIELDS:
        v = getattr(ma, attr, None)
        if v is not None:
            out[name] = int(v)
    return out


# ---------------------------------------------------------------------------
# partitioned-collective pricing (ISSUE 12): XLA's cost_analysis does not
# break bytes out by collective, so the SPMD-partitioned HLO text is the
# source — every all-reduce/all-gather/... instruction's result shape,
# summed.  The serving engine's per-step collective-bytes counter and the
# TPU503 SPMD audit both read this.
# ---------------------------------------------------------------------------

_COLLECTIVE_HLO_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                       "collective-permute", "all-to-all")

_HLO_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

#: `dtype[d0,d1,...]` shape tokens in an HLO instruction's result slot
_HLO_SHAPE_RE = None


def _hlo_shape_bytes(span: str) -> int:
    """Sum the bytes of every ``dtype[dims]`` shape token in ``span``
    (handles tuple-shaped results like async collective starts)."""
    global _HLO_SHAPE_RE
    import re
    if _HLO_SHAPE_RE is None:
        _HLO_SHAPE_RE = re.compile(
            r"\b(%s)\[([\d,]*)\]" % "|".join(_HLO_DTYPE_BYTES))
    total = 0
    for dt, dims in _HLO_SHAPE_RE.findall(span):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _HLO_DTYPE_BYTES[dt]
    return total


def collective_stats(compiled) -> Optional[Dict[str, Any]]:
    """``{"ops": N, "bytes": B, "by_kind": {...}}`` over the collective
    instructions of a compiled (post-SPMD-partitioning) executable's
    optimized HLO, or ``None`` when the backend exposes no HLO text.
    ``bytes`` sums each collective's RESULT shape — the data one step
    moves over the mesh.  ``by_kind`` breaks both figures out per HLO
    op (``{"all-gather": {"ops": n, "bytes": b}, ...}``) — ISSUE 20
    reads it as a *launches vs bytes* split: a decomposed overlap ring
    replaces ONE all-gather with ``chunks*(n-1)`` collective-permutes
    whose summed result bytes stay in the same band, so a raw op-count
    diff would read the rewrite as an Nx collective regression while
    the by-kind view shows what actually happened (monolithic kind
    GONE, permute chain present, bytes ~flat).  Async pairs are counted
    once, at the ``-done`` (whose result is the OUTPUT buffer alone; a
    ``-start``'s tuple result carries the input buffer and context
    fields too, which would over-price an async lowering ~1.5x vs the
    sync form of the same program).  Caveat: these are STATIC
    instruction counts — a collective inside a while/scan body is
    priced once, not per trip (the serving decode's per-layer walk is a
    python loop, so its entries unroll; priced exactly — but the
    overlap rings' chunk loops are also fully unrolled at trace time,
    so every hop of a chunked ring IS a distinct priced instruction)."""
    import re
    try:
        text = compiled.as_text()
    except Exception:
        return None
    if not isinstance(text, str):
        return None
    by_kind: Dict[str, Dict[str, int]] = {}

    def _tally(kind, nbytes):
        slot = by_kind.setdefault(kind, {"ops": 0, "bytes": 0})
        slot["ops"] += 1
        slot["bytes"] += nbytes

    names = "|".join(_COLLECTIVE_HLO_OPS)
    head = (r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+?)\s+(" + names + r")")
    sync_pat = re.compile(head + r"\(")
    done_pat = re.compile(head + r"-done\(")
    start_pat = re.compile(head + r"-start\(")
    for line in text.splitlines():
        m = done_pat.match(line)
        if m:
            _tally(m.group(2), _hlo_shape_bytes(m.group(1)))
            continue
        if start_pat.match(line):
            continue    # priced at its -done
        m = sync_pat.match(line)
        if m:
            _tally(m.group(2), _hlo_shape_bytes(m.group(1)))
    return {"ops": sum(s["ops"] for s in by_kind.values()),
            "bytes": sum(s["bytes"] for s in by_kind.values()),
            "by_kind": by_kind}


#: the ``provenance_ops`` key of instructions no role was found for
UNRESOLVED = "unresolved"


def instruction_counts(compiled) -> Optional[Dict[str, Dict[str, int]]]:
    """How many instructions of a compiled program (those that run as
    operations of their own: :func:`.scopes.instruction_provenance` over
    its HLO text) carry what: ``{"scope_ops": {scope: n}}`` by the role of
    their own ``op_name`` (``unscoped`` for none), ``{"provenance_ops":
    {"role/phase/how": n}}`` by role, phase and where the role came from,
    those with no role even from their neighbours under ``unresolved``.
    None when the backend gives no text."""
    try:
        text = compiled.as_text()
    except Exception:
        return None
    if not text:
        return None
    own: Dict[str, int] = {}
    found: Dict[str, int] = {}
    for p in _scopes.instruction_provenance(text)[1].values():
        role = (p.role if p.how == "own" else None) or _scopes.UNSCOPED
        own[role] = own.get(role, 0) + 1
        key = ("%s/%s/%s" % (p.role, p.phase or "none", p.how) if p.role
               else UNRESOLVED)
        found[key] = found.get(key, 0) + 1
    return {"scope_ops": own, "provenance_ops": found}


@dataclasses.dataclass
class ProgramReport:
    """XLA's cost + memory view of one compiled program.

    ``flops`` / ``bytes_accessed`` / ``transcendentals`` come from
    ``cost_analysis()``; the ``*_bytes`` fields from
    ``memory_analysis()``; ``peak_bytes`` is the derived whole-buffer
    high-water bound (see module docstring).  ``available=False`` means
    the program could not be compiled on this backend (``note`` says
    why) — a row is still emitted so the CLI shows all 40+ canonical
    programs, never a silently-shrunken registry."""

    name: str
    backend: str = ""
    available: bool = True
    note: str = ""
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    transcendentals: Optional[float] = None
    argument_bytes: Optional[int] = None
    output_bytes: Optional[int] = None
    temp_bytes: Optional[int] = None
    alias_bytes: Optional[int] = None
    generated_code_bytes: Optional[int] = None
    peak_bytes: Optional[int] = None
    #: ISSUE 12: collective instructions / result bytes in the
    #: partitioned HLO (None when the backend exposes no HLO text;
    #: 0/0 for a genuinely collective-free single-chip program)
    collective_ops: Optional[int] = None
    collective_bytes: Optional[int] = None
    #: ISSUE 20: the launches-vs-bytes split per HLO collective kind
    #: (``{"collective-permute": {"ops": n, "bytes": b}, ...}``) — an
    #: overlap ring trades one big launch for many small ones, which
    #: only this view can tell apart from a genuine byte regression
    collective_by_kind: Optional[Dict[str, Dict[str, int]]] = None
    #: PR 25: instructions that run as operations of their own, counted by
    #: the program's scope they carry (``observability.scopes``; fused
    #: instructions left out, ``unscoped`` for those with no role) — which
    #: names a device trace of this program can be read by.  None when the
    #: backend exposes no HLO text
    scope_ops: Optional[Dict[str, int]] = None
    #: PR 37: the same instructions by ``role/phase/how`` (forward,
    #: recompute, backward, update or none; the role from the instruction's
    #: ``own`` name, its ``user``s or its ``operand``s), and ``unresolved``
    #: for those no role was found for: which of a new model's instructions
    #: have no owner, seen without a chip
    provenance_ops: Optional[Dict[str, int]] = None

    def as_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def _derive_peak(mem: Dict[str, int]) -> Optional[int]:
    if not mem:
        return None
    have = [k for k in ("argument_bytes", "output_bytes", "temp_bytes")
            if k in mem]
    if not have:
        return None
    return (mem.get("argument_bytes", 0) + mem.get("output_bytes", 0)
            + mem.get("temp_bytes", 0) - mem.get("alias_bytes", 0))


def report_from_compiled(name: str, compiled, backend: Optional[str] = None,
                         note: str = "") -> ProgramReport:
    """Extract a :class:`ProgramReport` from a ``jax.stages.Compiled``."""
    if backend is None:
        try:
            import jax
            backend = jax.default_backend()
        except Exception:
            backend = ""
    ca = cost_analysis_dict(compiled)
    mem = memory_analysis_dict(compiled)
    coll = collective_stats(compiled)
    return ProgramReport(
        **(instruction_counts(compiled) or {}),
        name=name, backend=backend, available=True, note=note,
        flops=(float(ca["flops"]) if "flops" in ca else None),
        bytes_accessed=(float(ca["bytes accessed"])
                        if "bytes accessed" in ca else None),
        transcendentals=(float(ca["transcendentals"])
                         if "transcendentals" in ca else None),
        argument_bytes=mem.get("argument_bytes"),
        output_bytes=mem.get("output_bytes"),
        temp_bytes=mem.get("temp_bytes"),
        alias_bytes=mem.get("alias_bytes"),
        generated_code_bytes=mem.get("generated_code_bytes"),
        peak_bytes=_derive_peak(mem),
        collective_ops=(None if coll is None else coll["ops"]),
        collective_bytes=(None if coll is None else coll["bytes"]),
        collective_by_kind=(None if coll is None else coll["by_kind"]),
    )


# ---------------------------------------------------------------------------
# canonical-registry pricing (the CLI + TPU506 share this)
# ---------------------------------------------------------------------------

def compile_program(program) -> Optional[Any]:
    """The compiled executable of a :class:`TraceProgram` — from its
    stored ``lowered`` entry, or its ``lower_thunk`` (Pallas kernel
    programs, which the registry keeps at the jaxpr level and lowers on
    demand).  None when the program carries neither.  Cached on the
    program's meta so TPU506 and the CLI never compile twice in one
    process; compile failures cache too (and re-raise) — retrying a
    deterministic failure would just double the cost of a red run."""
    cached = program.meta.get("_compiled")
    if cached is not None:
        if isinstance(cached, Exception):
            raise cached
        return cached
    lowered = getattr(program, "lowered", None)
    if lowered is None:
        thunk = getattr(program, "lower_thunk", None)
        if thunk is None:
            return None
        try:
            lowered = thunk()
        except Exception as e:
            program.meta["_compiled"] = e
            raise
    try:
        compiled = lowered.compile()
    except Exception as e:
        program.meta["_compiled"] = e
        raise
    program.meta["_compiled"] = compiled
    return compiled


def report_for_program(program) -> ProgramReport:
    """Price one canonical program; degradation per backend is a row
    with ``available=False`` and the reason, never a dropped row."""
    try:
        compiled = compile_program(program)
    except Exception as e:
        return ProgramReport(
            name=program.name, backend=_backend_name(), available=False,
            note="compile failed: %s: %s" % (type(e).__name__, e))
    if compiled is None:
        return ProgramReport(
            name=program.name, backend=_backend_name(), available=False,
            note="no lowered entry (jaxpr-only program)")
    note = ""
    if program.name.startswith("pallas/") and _backend_name() != "tpu":
        note = "interpret-mode lowering (off-chip Pallas pricing)"
    return report_from_compiled(program.name, compiled, note=note)


def _backend_name() -> str:
    try:
        import jax
        return jax.default_backend()
    except Exception:
        return ""


def registry_reports(patterns: Optional[Sequence[str]] = None
                     ) -> Tuple[List[ProgramReport], List[str], List[str]]:
    """One report per canonical-registry program (optionally
    fnmatch-filtered).  Returns ``(reports, skipped, errors)`` with the
    registry's own builder-skip/builder-error semantics — an empty
    report list must never look green (the CLI exits 2)."""
    from ..analysis.trace.programs import build_programs
    programs, skipped, errors = build_programs(patterns)
    return [report_for_program(p) for p in programs], skipped, errors


# ---------------------------------------------------------------------------
# the bench `cost` block
# ---------------------------------------------------------------------------

def cost_block(report: ProgramReport,
               step_seconds: Optional[float] = None,
               on_chip: bool = False,
               device_kind: Optional[str] = None) -> Dict[str, Any]:
    """The schema'd ``cost`` block for a bench JSON line.

    Static fields always present (None when the backend reports no
    number); ``mfu`` / ``bw_util`` derived only when ``on_chip`` and a
    positive step timing exist — CPU smoke lines carry ``null`` there
    and the trajectory gate validates shape only."""
    use_t = step_seconds if on_chip else None
    m = mfu(report.flops, use_t, device_kind)
    b = bw_util(report.bytes_accessed, use_t, device_kind)
    return {
        "flops": report.flops,
        "hbm_bytes": report.bytes_accessed,
        "peak_bytes": report.peak_bytes,
        "mfu": (round(m, 6) if m is not None else None),
        "bw_util": (round(b, 6) if b is not None else None),
    }


# ---------------------------------------------------------------------------
# CLI rendering
# ---------------------------------------------------------------------------

def _fmt_num(v: Optional[float]) -> str:
    if v is None:
        return "-"
    v = float(v)
    for unit, div in (("G", 1e9), ("M", 1e6), ("K", 1e3)):
        if abs(v) >= div:
            return "%.2f%s" % (v / div, unit)
    return "%.0f" % v


def _provenance_lines(counts: Dict[str, int]) -> List[str]:
    """``provenance_ops`` as one line a role: for each phase the
    instructions by their own name + by their users + by their operands."""
    rows: Dict[str, Dict[str, Dict[str, int]]] = {}
    for key, n in counts.items():
        if key != UNRESOLVED:
            role, phase, how = key.split("/")
            rows.setdefault(role, {}).setdefault(phase, {})[how] = n
    order = _scopes.PHASES + ("none",)
    lines = ["    %-16s %s" % (role, "  ".join(
        "%s %s" % (phase, "+".join(
            str(rows[role][phase].get(how, 0))
            for how in ("own", "user", "operand")))
        for phase in order if phase in rows[role]))
        for role in sorted(rows)]
    lines.append("    %-16s %d" % (UNRESOLVED, counts.get(UNRESOLVED, 0)))
    return lines


def format_table(reports: Sequence[ProgramReport]) -> str:
    """Human table for ``python -m paddle_tpu.observability programs``:
    under each program's row its instructions by role and phase, counted
    ``own+user+operand`` (where the role came from), and how many have no
    role even from their neighbours."""
    lines = ["%-42s %10s %10s %10s %10s %10s  %s"
             % ("program", "flops", "hbm_bytes", "peak", "args", "temps",
                "scopes (instructions) / note")]
    for r in reports:
        roles = " ".join("%s:%d" % kv for kv in sorted(
            (r.scope_ops or {}).items()) if kv[0] != _scopes.UNSCOPED)
        note = r.note or ("" if r.available else "UNAVAILABLE")
        lines.append("%-42s %10s %10s %10s %10s %10s  %s"
                     % (r.name, _fmt_num(r.flops),
                        _fmt_num(r.bytes_accessed), _fmt_num(r.peak_bytes),
                        _fmt_num(r.argument_bytes), _fmt_num(r.temp_bytes),
                        " / ".join(x for x in (roles, note) if x)))
        if r.provenance_ops:
            lines.extend(_provenance_lines(r.provenance_ops))
    avail = sum(1 for r in reports if r.available)
    lines.append("%d program(s), %d priced (backend: %s)"
                 % (len(reports), avail, _backend_name()))
    return "\n".join(lines)
