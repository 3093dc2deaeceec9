"""paddle_tpu.observability — unified runtime telemetry.

The reference framework ships a full platform-layer observability stack
(profiler scheduler windows, RecordEvent spans, chrome-trace export); this
package is its metrics half for the TPU build, wired through every
subsystem:

* :mod:`.registry` — process-wide Counter / Gauge / Histogram registry:
  thread-safe, host-side only (never traced — ``float()`` guard), no-op
  singletons when disabled, fixed log-spaced histogram buckets with
  p50/p95/p99 readout.
* :mod:`.catalog` — the declared metric-name catalog (ops_schema-style:
  the default registry rejects undeclared names; a test keeps catalog and
  runtime emission in sync).
* :mod:`.watchdog` — the recompile watchdog over the compile-once jit
  entries (TrainStep, serving decode/prefill, 1F1B): counts compiles,
  warns on budget violations, raises under ``PADDLE_TPU_STRICT_COMPILE=1``;
  files JAX's trace / lower / backend seconds and cache verdicts under
  the entry that pays them (``compile.phase_seconds``, ``compile.cache``).
* :mod:`.scopes` — the program's own names inside its compiled programs:
  the one vocabulary of ``jax.named_scope`` roles (``attn``, ``mlp``,
  ``optimizer``, ``decode_attn``, ...) and the index from a compiled
  program's instructions back to them, by which a device trace is read:
  each instruction's role, its phase (forward, recompute, backward,
  update), and for what the compiler made the role of the work it serves.
* :mod:`.exporters` — Prometheus text, JSONL snapshots, chrome-trace
  metric marks injected into the :mod:`paddle_tpu.profiler` stream.
* :mod:`.tracing` — request-scoped span tracing (ISSUE 9): a trace_id
  per serving request, spans with parent links over queue/prefill-chunk/
  decode/verify/preemption phases, chrome-trace + JSONL export, and the
  ``trace-report`` timeline/attribution analyzer.  Disabled by default
  (``PADDLE_TPU_TRACING=1`` arms it — no-op identity tracer otherwise).
* :mod:`.flight` — the black-box flight recorder: a bounded ring of
  recent span/engine events plus metrics + engine-state + HBM-ledger
  snapshots, dumped to a file on DivergenceError / strict
  RecompileError / preemption-guard fires / faultpoint-raised crashes
  (``PADDLE_TPU_FLIGHT=1`` arms it).
* :mod:`.costs` — compiled-program cost reports (ISSUE 11): XLA
  ``cost_analysis()`` + ``memory_analysis()`` extracted into
  :class:`~.costs.ProgramReport` for every canonical-registry program
  and every serving entry, MFU / HBM-bandwidth-utilization derivation,
  and the schema'd bench ``cost`` block.
* :mod:`.hbm` — the live HBM ledger: catalog'd gauges for per-device
  live bytes / engine KV-pool bytes / checkpoint-restore transients,
  sampled at step boundaries when armed (``PADDLE_TPU_HBM=1``), with
  chrome-trace counter lanes and flight-dump snapshots.
* :mod:`.liveness` — the liveness watchdog (ISSUE 14): named progress
  beacons at every hot boundary (train step, fit batch, scheduler
  step, frontend threads, checkpoint writer, store ops, autotune),
  watched by a monitor thread with per-beacon deadlines; a stall dumps
  all-thread stacks into a ``"stall"`` flight dump, increments
  ``liveness.stalls{beacon=}``, and can hard-exit with a configurable
  rc so the elastic launcher respawns the wedged worker
  (``PADDLE_TPU_LIVENESS=1`` arms it — no-op beacon singleton
  otherwise).
* :mod:`.aggregate` — cross-host telemetry (ISSUE 14): per-host
  snapshot publication through the retry-wrapped distributed store and
  the host-0 cluster merge with step-time straggler detection
  (``liveness.straggler{host=}``).
* CLI: ``python -m paddle_tpu.observability
  dump|serve|tail|trace-report|programs|cluster`` over the JSONL
  snapshot stream (``PADDLE_TPU_METRICS_FILE``), span trace files, the
  canonical program registry, and the distributed-store telemetry
  keys.

Import discipline: this package must stay importable before (and without)
jax — the registry is pure stdlib; jax-adjacent pieces (profiler marks)
import lazily.  See OBSERVABILITY.md for the metric catalog and knobs.
"""
from __future__ import annotations

from . import aggregate, costs, flight, hbm, liveness, scopes
from .catalog import CATALOG
from .registry import (NOOP_COUNTER, NOOP_GAUGE, NOOP_HISTOGRAM, Counter,
                       Gauge, Histogram, Registry, counter, default_registry,
                       flush, gauge, histogram)
from .tracing import NOOP_SPAN, NOOP_TRACER, Tracer, default_tracer
from .watchdog import (RecompileError, RecompileWarning, WatchedEntry,
                       compile_counts, watch)

__all__ = [
    "CATALOG", "Counter", "Gauge", "Histogram", "Registry",
    "NOOP_COUNTER", "NOOP_GAUGE", "NOOP_HISTOGRAM",
    "counter", "gauge", "histogram", "default_registry", "flush",
    "RecompileError", "RecompileWarning", "WatchedEntry", "watch",
    "compile_counts",
    "Tracer", "NOOP_TRACER", "NOOP_SPAN", "default_tracer", "flight",
    "costs", "hbm", "liveness", "aggregate", "scopes",
]
