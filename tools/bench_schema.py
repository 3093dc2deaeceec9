"""Schema validator for the BENCH_* trajectory (ISSUE 6 satellite).

Two input shapes:

* **Wrapper files** (``BENCH_<series>_<round>.json``, as an earlier
  driver wrote them): ``{"n", "cmd", "rc", "tail", "parsed"}`` where
  ``parsed`` is the bench's JSON line.  None is committed today — the
  round 1-5 records were removed in PR 21 (taken on a retired remote
  set-up; PERF.md keeps their figures) and the driver now records every
  run in ``PERF_LEDGER.jsonl``.
* **Raw lines** (``--line -`` reads stdin, or ``--line '<json>'``): the
  JSON line a bench prints — what the CI bench-smoke pipes in.

The line schema is the contract bench.py / bench_decode.py /
bench_serve.py print: required ``metric``/``value``/``unit``; optional
``compile_counts`` (a {entry: count>=1} int map), the ISSUE-6
``metrics`` block::

    "metrics": {
      "histograms": {"<name>": {"p50_ms", "p95_ms", "p99_ms", "count"}},
      "compile_counts": {"<watchdog entry>": int}
    }

and the ISSUE-11 ``cost`` block (XLA cost/memory analysis of the
compiled step the bench timed)::

    "cost": {"flops": N|null, "hbm_bytes": N|null, "peak_bytes": N|null,
             "mfu": f|null, "bw_util": f|null}

— all five keys required when the block is present; ``mfu``/``bw_util``
are null off-chip (CPU smoke validates SHAPE only, per-backend
degradation is the costs.py contract).  ``--expect-cost`` makes the
block mandatory (the CI bench-smoke gate).

Lines without the blocks validate clean: each block is optional, but
WHEN present it must be well-formed
(percentiles ordered p50<=p95<=p99, non-negative counts).

``--expect-compile-once ENTRY`` additionally requires the watchdog's
count for ENTRY to be exactly 1 — the CI smoke gate that replaced
bench_decode's ad-hoc assert (the watchdog also enforces it at runtime
under PADDLE_TPU_STRICT_COMPILE=1; this checks the *reported* line).

**Serve lines (ISSUE 13).**  ``bench_serve.py``'s
``serve_goodput_tokens_per_sec`` lines additionally carry the load-
harness fields — ``qps``, ``mix``, client-observed ``ttft_p50_ms``/
``ttft_p99_ms``/``tpot_p50_ms``/``tpot_p99_ms``, and ``shed_rate`` —
validated whenever the metric matches (a serve line missing its p99 is
rejected, not skipped).

**Trajectory mode (ISSUE 7 / ROADMAP item 5 payoff).**  ``--trajectory``
promotes the loose ``BENCH_r*`` / ``BENCH_decode_*`` / ``BENCH_serve_*``
wrapper files into one schema'd, *gated* series: every wrapper is
validated, grouped by metric into ordered series (round order = sorted
filename), and these gates run over each series —

* **compile counts, every backend**: any entry that reports
  ``compile_counts``/``metrics.compile_counts`` must satisfy the
  compile-once contract for the decode entry (``serving.decode == 1``;
  the CPU CI run is exactly as able to catch a retrace as a chip run —
  program-cache sizes don't depend on the backend);
* **on-chip regression**: between CONSECUTIVE entries of one series
  whose ``config.backend == "tpu"`` and whose ``(model, cache_layout,
  kv_dtype, spec, tp, overlap, overlap_comm, kv_host, disagg, qps,
  mix, replicas)`` cursor key matches (the ISSUE-8 A/B matrix
  interleaves quantized/speculative lines in one trajectory, ISSUE 12
  adds the ``--tp`` axis, ISSUE 13 adds the sync-vs-overlapped loop
  axis plus the serve harness's (QPS, mix) operating points, ISSUE 15
  adds the colocated-vs-disaggregated axis, ISSUE 17 adds the
  ``--kv-host`` tier axis, ISSUE 19 adds the ``--replicas`` fleet
  axis, and ISSUE 20 adds the ``--overlap-comm`` decomposed-collective
  axis — a tp=2, sync-loop, disagg, kv-host-on, qps=16, 2-replica, or
  overlap-comm-on line must never gate against a different series;
  legacy lines without a field keep their own ``None``-keyed cursor,
  regression-tested), a >3% drop in ``value`` fails.  CPU entries never perf-gate (smoke numbers), so
  the gate arms itself automatically the first session that records
  chip numbers;
* **repeat-prompt TTFT (ISSUE 17)**: over the same like-for-like
  on-chip decode pairs, >3% growth in ``repeat_ttft_ms`` fails — the
  host-tier re-admission (or the tier-off recompute baseline) must not
  slide while tokens/s holds.  Armed on-chip only: the CPU smoke's
  repeat window is compile-dominated noise;
* **serve latency (ISSUE 13)**: over the same like-for-like on-chip
  pairs of ``serve_goodput_tokens_per_sec`` lines, >3% growth in
  client-observed p99 TTFT fails — a PR that holds goodput by letting
  tail latency slide does not pass;
* **cost cursors (ISSUE 11)**: over the same like-for-like on-chip
  pairs, a >3% ``cost.mfu`` drop or >5% ``cost.peak_bytes`` growth
  fails — a perf PR that holds tokens/s by burning memory (or that
  silently halves utilization behind a bigger batch) no longer sails
  through.  CPU entries contribute shape validation only.

``--trajectory --write OUT`` additionally emits the assembled series as
one JSON document (the trajectory file CI archives).

Exit 0 = every input valid.  No third-party deps (hand-rolled checks:
the CI image has no jsonschema).
"""
from __future__ import annotations

import argparse
import glob
import json
import sys
from typing import Any, List


class SchemaError(Exception):
    pass


def _require(cond: bool, path: str, msg: str):
    if not cond:
        raise SchemaError("%s: %s" % (path, msg))


def _is_num(x: Any) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool)


def validate_histogram_block(name: str, h: Any, path: str):
    _require(isinstance(h, dict), path, "histogram %r must be an object"
             % name)
    for k in ("p50_ms", "p95_ms", "p99_ms", "count"):
        _require(k in h, path, "histogram %r missing %r" % (name, k))
        _require(_is_num(h[k]), path, "histogram %r field %r must be a "
                 "number, got %r" % (name, k, type(h[k]).__name__))
        _require(h[k] >= 0, path, "histogram %r field %r is negative"
                 % (name, k))
    _require(h["p50_ms"] <= h["p95_ms"] <= h["p99_ms"], path,
             "histogram %r percentiles are not ordered "
             "(p50<=p95<=p99): %r" % (name, h))
    _require(isinstance(h["count"], int), path,
             "histogram %r count must be an int" % name)


def validate_compile_counts(cc: Any, path: str, where: str):
    _require(isinstance(cc, dict), path, "%s must be an object" % where)
    for entry, count in cc.items():
        _require(isinstance(entry, str) and entry, path,
                 "%s keys must be non-empty strings" % where)
        _require(isinstance(count, int) and not isinstance(count, bool),
                 path, "%s[%r] must be an int, got %r"
                 % (where, entry, count))
        _require(count >= 1, path,
                 "%s[%r] = %d — a reported entry must have compiled at "
                 "least once" % (where, entry, count))


#: the ISSUE-11 cost block: all five keys required when present; static
#: fields may be null (a backend that reports no number never fabricates
#: one) and utilizations are null off-chip by contract.
_COST_KEYS = ("flops", "hbm_bytes", "peak_bytes", "mfu", "bw_util")


def validate_cost_block(c: Any, path: str):
    _require(isinstance(c, dict), path, "'cost' must be an object")
    for k in _COST_KEYS:
        _require(k in c, path, "cost block missing %r" % k)
        v = c[k]
        if v is None:
            continue
        _require(_is_num(v), path,
                 "cost[%r] must be a number or null, got %r" % (k, v))
        _require(v >= 0, path, "cost[%r] is negative" % k)
    for k in ("mfu", "bw_util"):
        if c[k] is not None:
            # a utilization over 2.0 means the peak table or the timing
            # is wrong — reject the line rather than archive nonsense
            _require(c[k] <= 2.0, path,
                     "cost[%r] = %r is not a plausible utilization"
                     % (k, c[k]))


def validate_trace_block(t: Any, path: str):
    """The ISSUE-9 optional ``trace`` block (bench_decode --trace-file):
    span counts per request plus the exported file path.  Optional —
    old lines without it validate clean (regression-tested)."""
    _require(isinstance(t, dict), path, "'trace' must be an object")
    for k in ("spans", "requests"):
        _require(k in t, path, "trace block missing %r" % k)
        _require(isinstance(t[k], int) and not isinstance(t[k], bool)
                 and t[k] >= 0, path,
                 "trace[%r] must be a non-negative int, got %r"
                 % (k, t[k]))
    if "file" in t:
        _require(isinstance(t["file"], str) and t["file"], path,
                 "trace['file'] must be a non-empty string")
    if "per_request_spans" in t:
        prs = t["per_request_spans"]
        _require(isinstance(prs, dict), path,
                 "trace['per_request_spans'] must be an object")
        for rid, n in prs.items():
            _require(isinstance(n, int) and not isinstance(n, bool)
                     and n >= 0, path,
                     "trace.per_request_spans[%r] must be a non-negative "
                     "int, got %r" % (rid, n))


#: fields every serve (load-harness) line must carry beside the generic
#: metric/value/unit triple — the trajectory's latency gate reads them.
SERVE_METRIC = "serve_goodput_tokens_per_sec"
_SERVE_NUM_FIELDS = ("qps", "ttft_p50_ms", "ttft_p99_ms", "tpot_p50_ms",
                     "tpot_p99_ms", "shed_rate")


def validate_serve_fields(doc: Any, path: str):
    for k in _SERVE_NUM_FIELDS:
        _require(k in doc, path, "serve line missing %r" % k)
        _require(_is_num(doc[k]) and doc[k] >= 0, path,
                 "serve line field %r must be a non-negative number, "
                 "got %r" % (k, doc[k]))
    _require(doc["qps"] > 0, path, "serve line 'qps' must be positive")
    _require(doc["shed_rate"] <= 1.0, path,
             "serve line 'shed_rate' must be in [0, 1]")
    _require(doc["ttft_p50_ms"] <= doc["ttft_p99_ms"], path,
             "serve line TTFT percentiles are not ordered (p50<=p99)")
    _require(doc["tpot_p50_ms"] <= doc["tpot_p99_ms"], path,
             "serve line TPOT percentiles are not ordered (p50<=p99)")
    _require(isinstance(doc.get("mix"), str) and doc.get("mix"), path,
             "serve line 'mix' must be a non-empty string")
    # ISSUE-15 optional fields: absent on pre-disagg lines (their own
    # legacy cursor), validated whenever present
    if "disagg" in doc:
        _require(isinstance(doc["disagg"], bool), path,
                 "serve line 'disagg' must be a bool")
        if doc["disagg"]:
            _require(_is_num(doc.get("handoff_bytes"))
                     and doc["handoff_bytes"] >= 0, path,
                     "a disagg serve line must report non-negative "
                     "'handoff_bytes'")
    # ISSUE-19 optional fields: absent on pre-fleet lines (their own
    # legacy cursor — a replicated line must never gate against
    # single-replica history), validated whenever present
    if "replicas" in doc:
        _require(isinstance(doc["replicas"], int)
                 and not isinstance(doc["replicas"], bool)
                 and doc["replicas"] >= 1, path,
                 "serve line 'replicas' must be an int >= 1, got %r"
                 % (doc["replicas"],))
    if "dropped_streams" in doc:
        _require(isinstance(doc["dropped_streams"], int)
                 and not isinstance(doc["dropped_streams"], bool)
                 and doc["dropped_streams"] >= 0, path,
                 "serve line 'dropped_streams' must be a non-negative "
                 "int, got %r" % (doc["dropped_streams"],))
    if "wave" in doc:
        w = doc["wave"]
        _require(isinstance(w, dict), path, "'wave' must be an object")
        for k in ("quiet_tpot_p50_ms", "quiet_tpot_p99_ms",
                  "wave_tpot_p50_ms", "wave_tpot_p99_ms"):
            _require(_is_num(w.get(k)) and w[k] >= 0, path,
                     "wave block field %r must be a non-negative "
                     "number, got %r" % (k, w.get(k)))
        _require(w["quiet_tpot_p50_ms"] <= w["quiet_tpot_p99_ms"], path,
                 "wave block quiet percentiles not ordered (p50<=p99)")
        _require(w["wave_tpot_p50_ms"] <= w["wave_tpot_p99_ms"], path,
                 "wave block wave percentiles not ordered (p50<=p99)")


def validate_line(doc: Any, path: str,
                  expect_compile_once: List[str] = (),
                  expect_cost: bool = False):
    _require(isinstance(doc, dict), path, "bench line must be a JSON object")
    for k, t in (("metric", str), ("unit", str)):
        _require(isinstance(doc.get(k), t), path,
                 "%r must be a %s, got %r" % (k, t.__name__, doc.get(k)))
    _require(_is_num(doc.get("value")), path, "'value' must be a number")
    if doc.get("metric") == SERVE_METRIC:
        validate_serve_fields(doc, path)
    if "vs_baseline" in doc:
        _require(_is_num(doc["vs_baseline"]), path,
                 "'vs_baseline' must be a number")
    # ISSUE-17 optional fields (tiered KV host cache): absent on
    # pre-tier lines (their own legacy cursor), validated when present
    if "kv_host" in doc:
        _require(doc["kv_host"] in ("on", "off"), path,
                 "'kv_host' must be 'on' or 'off', got %r"
                 % (doc["kv_host"],))
    # ISSUE-20 optional field (decomposed collective overlap): absent on
    # pre-overlap lines (their own legacy cursor), validated when present
    if "overlap_comm" in doc:
        _require(doc["overlap_comm"] in ("on", "off"), path,
                 "'overlap_comm' must be 'on' or 'off', got %r"
                 % (doc["overlap_comm"],))
    if "repeat_ttft_ms" in doc:
        _require(_is_num(doc["repeat_ttft_ms"])
                 and doc["repeat_ttft_ms"] >= 0, path,
                 "'repeat_ttft_ms' must be a non-negative number")
    if "host_hit_pages" in doc:
        _require(isinstance(doc["host_hit_pages"], int)
                 and not isinstance(doc["host_hit_pages"], bool)
                 and doc["host_hit_pages"] >= 0, path,
                 "'host_hit_pages' must be a non-negative int")
    if doc.get("kv_host") == "on":
        _require(doc.get("host_hit_pages", 0) >= 1, path,
                 "a kv_host=on line must report host_hit_pages >= 1 — "
                 "the repeat-prompt phase pulled nothing from the tier "
                 "it claims to bench")
    if expect_cost:
        _require("cost" in doc, path,
                 "--expect-cost: the bench line carries no 'cost' block")
    if "cost" in doc:
        validate_cost_block(doc["cost"], path)
    if "trace" in doc:
        validate_trace_block(doc["trace"], path)
    if "compile_counts" in doc:
        validate_compile_counts(doc["compile_counts"], path,
                                "compile_counts")
    if "metrics" in doc:
        m = doc["metrics"]
        _require(isinstance(m, dict), path, "'metrics' must be an object")
        _require("histograms" in m, path,
                 "metrics block missing 'histograms'")
        _require(isinstance(m["histograms"], dict), path,
                 "metrics.histograms must be an object")
        for name, h in m["histograms"].items():
            validate_histogram_block(name, h, path)
        _require("compile_counts" in m, path,
                 "metrics block missing 'compile_counts' (the watchdog "
                 "report)")
        validate_compile_counts(m["compile_counts"], path,
                                "metrics.compile_counts")
    for entry in expect_compile_once:
        _require("metrics" in doc, path,
                 "--expect-compile-once needs the metrics block")
        got = doc["metrics"]["compile_counts"].get(entry)
        # a replicated-fleet line (ISSUE 19) sums same-name entries over
        # its N live engines: compile-once there means exactly N — one
        # per replica, zero respawn recompiles
        want = (doc["replicas"]
                if isinstance(doc.get("replicas"), int)
                and not isinstance(doc.get("replicas"), bool)
                and doc["replicas"] >= 1 else 1)
        _require(got == want, path,
                 "watchdog reports compile_counts[%r] = %r, expected "
                 "exactly %d (compile-once contract, %d replica(s))"
                 % (entry, got, want, want))


def validate_wrapper(doc: Any, path: str,
                     expect_compile_once: List[str] = ()):
    _require(isinstance(doc, dict), path, "wrapper must be a JSON object")
    _require("parsed" in doc or "tail" in doc, path,
             "wrapper has neither 'parsed' nor 'tail'")
    if "rc" in doc:
        _require(doc["rc"] == 0, path,
                 "bench exited rc=%r — a failed run must not enter the "
                 "trajectory" % (doc["rc"],))
    parsed = _extract_line(doc, path)
    validate_line(parsed, path + ":parsed", expect_compile_once)
    return parsed


def validate_doc(doc: Any, path: str, expect_compile_once: List[str] = ()):
    """Validate an already-loaded document (wrapper file or raw line);
    returns the bench line inside (the doc itself when raw)."""
    if isinstance(doc, dict) and ("parsed" in doc or "cmd" in doc
                                  or "tail" in doc):
        return validate_wrapper(doc, path, expect_compile_once)
    validate_line(doc, path, expect_compile_once)
    return doc


def validate_path(path: str, expect_compile_once: List[str] = ()):
    with open(path) as f:
        doc = json.load(f)
    validate_doc(doc, path, expect_compile_once)


def _extract_line(doc: Any, path: str) -> Any:
    """The bench JSON line inside a wrapper (or the doc itself)."""
    if isinstance(doc, dict) and ("parsed" in doc or "cmd" in doc
                                  or "tail" in doc):
        parsed = doc.get("parsed")
        if parsed is None:
            for raw in reversed(doc.get("tail", "").splitlines()):
                raw = raw.strip()
                if raw.startswith("{"):
                    parsed = json.loads(raw)
                    break
        _require(parsed is not None, path,
                 "no JSON line found in wrapper 'tail'")
        return parsed
    return doc


# the compile-once contract per metric series: which watchdog entries (or
# legacy top-level compile_counts keys) must be exactly 1 whenever the
# line reports them at all.  A speculative line carries
# serving.spec_verify instead of serving.decode (the single-token
# fallback never ran, and a zero count is omitted by contract), so each
# key gates only when present.
_COMPILE_ONCE = {
    "decode_tokens_per_sec": (("metrics", "serving.decode"),
                              ("metrics", "serving.spec_verify"),
                              # ISSUE 17: the host-tier spill/fetch path
                              # reuses the disagg page programs — budget
                              # stays 1 each whenever the line ran them
                              ("metrics", "serving.kv_export"),
                              ("metrics", "serving.kv_import"),
                              ("top", "decode"),
                              ("top", "verify")),
    SERVE_METRIC: (("metrics", "serving.decode"),
                   ("metrics", "serving.spec_verify"),
                   # ISSUE 15: the disaggregated page-handoff programs —
                   # a second export/import program would mean the fixed
                   # chunk shape silently varied
                   ("metrics", "serving.kv_export"),
                   ("metrics", "serving.kv_import")),
}

REGRESSION_TOLERANCE = 0.03     # >3% on-chip drop fails
MFU_TOLERANCE = 0.03            # >3% on-chip cost.mfu drop fails
PEAK_HBM_TOLERANCE = 0.05      # >5% on-chip cost.peak_bytes growth fails
TTFT_P99_TOLERANCE = 0.03      # >3% on-chip serve p99-TTFT growth fails
REPEAT_TTFT_TOLERANCE = 0.03   # >3% on-chip repeat-prompt TTFT growth
                               # fails (ISSUE 17; CPU smoke never gates —
                               # its repeat window is compile-dominated)


def check_trajectory(paths: List[str], write: str = None) -> List[str]:
    """Validate + gate the ordered BENCH_* series; returns failures."""
    failures: List[str] = []
    series: dict = {}
    for p in sorted(paths):
        try:
            with open(p) as f:
                doc = json.load(f)
            line = validate_doc(doc, p)
        except (SchemaError, json.JSONDecodeError, OSError) as e:
            failures.append(str(e) if isinstance(e, SchemaError)
                            else "%s: %s" % (p, e))
            continue
        cfg = line.get("config", {}) if isinstance(
            line.get("config"), dict) else {}
        entry = {
            "file": p,
            "metric": line.get("metric"),
            "value": line.get("value"),
            "unit": line.get("unit"),
            "backend": cfg.get("backend"),
            "model": cfg.get("model"),
            "cache_layout": line.get("cache_layout"),
            # ISSUE-8/12/13 A/B axes: absent on older lines — None then
            # keys its own legacy cursor, so old series stay gated
            "kv_dtype": line.get("kv_dtype"),
            "spec": line.get("spec"),
            "tp": line.get("tp"),
            "overlap": line.get("overlap"),
            # ISSUE-20 axis: None on pre-overlap lines keys their own
            # legacy cursor — an overlapped-ring line never gates
            # against monolithic-collective history
            "overlap_comm": line.get("overlap_comm"),
            "kv_host": line.get("kv_host"),
            "disagg": line.get("disagg"),
            "qps": line.get("qps"),
            "mix": line.get("mix"),
            # ISSUE-19 fleet axis: None on pre-fleet lines keys their
            # own legacy cursor (regression-tested) — a 2-replica
            # goodput number never gates against a 1-replica anchor
            "replicas": line.get("replicas"),
            "ttft_p99_ms": line.get("ttft_p99_ms"),
            "repeat_ttft_ms": line.get("repeat_ttft_ms"),
            "compile_counts": (line.get("metrics", {}) or {}).get(
                "compile_counts", line.get("compile_counts")),
            "cost": (line.get("cost")
                     if isinstance(line.get("cost"), dict) else None),
        }
        series.setdefault(entry["metric"], []).append(entry)

        # gate 1 — compile counts (ANY backend: the jit cache size a CPU
        # run reports catches a retrace exactly as well as a chip run)
        for kind, key in _COMPILE_ONCE.get(entry["metric"], ()):
            cc = ((line.get("metrics") or {}).get("compile_counts")
                  if kind == "metrics" else line.get("compile_counts"))
            if cc is None or key not in cc:
                continue
            # fleet lines (ISSUE 19) sum same-name watchdog entries
            # over N live engines: once-per-replica is the contract
            want = (entry["replicas"]
                    if isinstance(entry.get("replicas"), int)
                    and not isinstance(entry.get("replicas"), bool)
                    and entry["replicas"] >= 1 else 1)
            if cc[key] != want:
                failures.append(
                    "%s: compile-once violated — %s compile count for "
                    "%r is %r, expected exactly %d (%d replica(s))"
                    % (p, kind, key, cc[key], want, want))

    # gate 2 — on-chip regression between consecutive chip entries.
    # One cursor per (model, cache_layout, kv_dtype, spec, tp) within
    # each metric: a series that interleaves layouts (bench_decode
    # --both), the ISSUE-8 quant/speculation axes, or the ISSUE-12
    # tensor-parallel axis (--tp 1,2 emits both lines per round) still
    # compares like-for-like — a single cursor would skip every
    # mismatched pair AND lose its anchor, leaving the gate silently
    # inert (regression-tested).
    for metric, entries in series.items():
        prev_by_key = {}
        # PER-METRIC cost anchors: the last like-for-like entry whose
        # cost block carried THAT number.  One shared anchor would let a
        # round with a partial block (mfu null but peak_bytes present —
        # a real on-chip case when the part is missing from the peak
        # table) displace the MFU anchor and silently disarm that gate
        # across the gap; a fully cost-less round (older bench checkout)
        # must not displace either.
        prev_mfu_by_key = {}
        prev_peak_by_key = {}
        for e in entries:
            if e["backend"] != "tpu":
                continue
            key = (e.get("model"), e.get("cache_layout"),
                   e.get("kv_dtype"), e.get("spec"), e.get("tp"),
                   e.get("overlap"), e.get("overlap_comm"),
                   e.get("kv_host"), e.get("disagg"),
                   e.get("qps"), e.get("mix"), e.get("replicas"))
            prev = prev_by_key.get(key)
            if (prev is not None and _is_num(e["value"])
                    and _is_num(prev["value"]) and prev["value"] > 0):
                drop = 1.0 - e["value"] / prev["value"]
                if drop > REGRESSION_TOLERANCE:
                    failures.append(
                        "%s: on-chip regression — %s fell %.1f%% vs %s "
                        "(%.2f -> %.2f; tolerance %.0f%%)"
                        % (e["file"], metric, 100 * drop, prev["file"],
                           prev["value"], e["value"],
                           100 * REGRESSION_TOLERANCE))
            # gate 2b — serve tail latency (ISSUE 13): like-for-like
            # on-chip serve pairs also gate the CLIENT-observed p99
            # TTFT — goodput held by letting the tail slide fails
            if (metric == SERVE_METRIC and prev is not None
                    and _is_num(e.get("ttft_p99_ms"))
                    and _is_num(prev.get("ttft_p99_ms"))
                    and prev["ttft_p99_ms"] > 0):
                growth = e["ttft_p99_ms"] / prev["ttft_p99_ms"] - 1.0
                if growth > TTFT_P99_TOLERANCE:
                    failures.append(
                        "%s: on-chip serve regression — p99 TTFT grew "
                        "%.1f%% vs %s (%.3f -> %.3f ms; tolerance "
                        "%.0f%%)" % (e["file"], 100 * growth,
                                     prev["file"], prev["ttft_p99_ms"],
                                     e["ttft_p99_ms"],
                                     100 * TTFT_P99_TOLERANCE))
            # gate 2c — repeat-prompt TTFT (ISSUE 17): like-for-like
            # on-chip decode pairs gate the repeat-admission latency —
            # a PR that keeps tokens/s but lets the host-tier (or
            # recompute) repeat path slide fails.  kv_host is a cursor
            # field, so the on and off arms each gate their own series;
            # armed on-chip only (the loop's backend guard) — the CPU
            # smoke's repeat window is compile-dominated noise.
            if (prev is not None and _is_num(e.get("repeat_ttft_ms"))
                    and _is_num(prev.get("repeat_ttft_ms"))
                    and prev["repeat_ttft_ms"] > 0):
                growth = e["repeat_ttft_ms"] / prev["repeat_ttft_ms"] \
                    - 1.0
                if growth > REPEAT_TTFT_TOLERANCE:
                    failures.append(
                        "%s: on-chip regression — repeat-prompt TTFT "
                        "grew %.1f%% vs %s (%.3f -> %.3f ms; tolerance "
                        "%.0f%%)" % (e["file"], 100 * growth,
                                     prev["file"],
                                     prev["repeat_ttft_ms"],
                                     e["repeat_ttft_ms"],
                                     100 * REPEAT_TTFT_TOLERANCE))
            # gate 3 — cost cursors (ISSUE 11): like-for-like on-chip
            # pairs also gate MFU (>3% drop) and peak HBM (>5% growth),
            # each against ITS OWN last-carrying anchor.
            ec = e["cost"] or {}
            prev_m = prev_mfu_by_key.get(key)
            pm = ((prev_m or {}).get("cost") or {})
            if (prev_m is not None and _is_num(ec.get("mfu"))
                    and _is_num(pm.get("mfu")) and pm["mfu"] > 0):
                mfu_drop = 1.0 - ec["mfu"] / pm["mfu"]
                if mfu_drop > MFU_TOLERANCE:
                    failures.append(
                        "%s: on-chip cost regression — MFU fell %.1f%% "
                        "vs %s (%.4f -> %.4f; tolerance %.0f%%)"
                        % (e["file"], 100 * mfu_drop, prev_m["file"],
                           pm["mfu"], ec["mfu"], 100 * MFU_TOLERANCE))
            prev_p = prev_peak_by_key.get(key)
            pp = ((prev_p or {}).get("cost") or {})
            if (prev_p is not None and _is_num(ec.get("peak_bytes"))
                    and _is_num(pp.get("peak_bytes"))
                    and pp["peak_bytes"] > 0):
                growth = ec["peak_bytes"] / pp["peak_bytes"] - 1.0
                if growth > PEAK_HBM_TOLERANCE:
                    failures.append(
                        "%s: on-chip cost regression — peak HBM grew "
                        "%.1f%% vs %s (%d -> %d bytes; tolerance %.0f%%)"
                        % (e["file"], 100 * growth, prev_p["file"],
                           pp["peak_bytes"], ec["peak_bytes"],
                           100 * PEAK_HBM_TOLERANCE))
            if _is_num(ec.get("mfu")):
                prev_mfu_by_key[key] = e
            if _is_num(ec.get("peak_bytes")):
                prev_peak_by_key[key] = e
            prev_by_key[key] = e

    if write and not failures:
        out = {"schema": 1, "tolerance": REGRESSION_TOLERANCE,
               "series": series}
        with open(write, "w") as f:
            json.dump(out, f, indent=2, sort_keys=True)
            f.write("\n")
    for metric, entries in sorted(series.items()):
        chip = sum(1 for e in entries if e["backend"] == "tpu")
        print("trajectory %r: %d entries (%d on-chip)"
              % (metric, len(entries), chip))
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python tools/bench_schema.py",
        description="validate BENCH_* trajectory files / bench JSON lines")
    ap.add_argument("paths", nargs="*",
                    help="files to validate (default: BENCH_*.json)")
    ap.add_argument("--line", default=None,
                    help="validate ONE raw bench line: a JSON string, or "
                         "'-' to read it from stdin (last non-empty line)")
    ap.add_argument("--expect-compile-once", action="append", default=[],
                    metavar="ENTRY",
                    help="require metrics.compile_counts[ENTRY] == 1")
    ap.add_argument("--expect-cost", action="store_true",
                    help="require the ISSUE-11 'cost' block on the line "
                         "(the CI bench-smoke gate; shape-validated on "
                         "every backend)")
    ap.add_argument("--trajectory", action="store_true",
                    help="series mode: validate the ordered BENCH_r*/"
                         "BENCH_decode_* trajectory, assert compile "
                         "counts on every backend, fail on >3%% on-chip "
                         "regression between consecutive chip entries")
    ap.add_argument("--write", default=None, metavar="OUT",
                    help="with --trajectory: write the assembled series "
                         "document to OUT")
    args = ap.parse_args(argv)

    if args.trajectory:
        paths = args.paths or sorted(
            glob.glob("BENCH_r*.json") + glob.glob("BENCH_decode_*.json")
            + glob.glob("BENCH_serve_*.json"))
        failures = check_trajectory(paths, write=args.write)
        for f in failures:
            print("TRAJECTORY ERROR — %s" % f, file=sys.stderr)
        return 1 if failures else 0

    failures = []
    try:
        if args.line is not None:
            raw = args.line
            if raw == "-":
                lines = [l for l in sys.stdin.read().splitlines()
                         if l.strip()]
                if not lines:
                    raise SchemaError("<stdin>: no input line")
                raw = lines[-1]
            validate_line(json.loads(raw), "<line>",
                          args.expect_compile_once,
                          expect_cost=args.expect_cost)
            print("ok: <line>")
    except SchemaError as e:
        failures.append(str(e))

    paths = args.paths or (sorted(glob.glob("BENCH_*.json"))
                           if args.line is None else [])
    for p in paths:
        try:
            validate_path(p, args.expect_compile_once)
            print("ok: %s" % p)
        except (SchemaError, json.JSONDecodeError, OSError) as e:
            failures.append("%s: %s" % (p, e) if not isinstance(
                e, SchemaError) else str(e))

    if failures:
        for f in failures:
            print("SCHEMA ERROR — %s" % f, file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
