"""Kernel autotuner — timed variant/config selection for the Pallas kernels.

The hand-tuned kernel configs (flash 512/512 blocks, the head groups) were
each found by one-off on-chip A/Bs (PERF.md section 6).  That search is
exhausted at the *config* level; what remains is the variant*config product
space (bf16 softmax chains, DMA-pipelined K/V — see
flash_attention_pallas.py), which is too large to A/B by hand.  This module
makes the search systematic:

- a **registry** of kernel families (flash_fwd, flash_bwd, flash_bwd_dq,
  flash_bwd_dkv, the decode-attention families, mp_overlap), each exposing
  the per-key candidate list (variant name + config dict; candidate [0] is
  ALWAYS the hand-tuned default) and a runner that executes one candidate
  on synthetic data;
- **timed selection** at first call per (shape, dtype, platform, causal)
  key: median-of-k on-device wall times per candidate, best wins
  (off by default — enable with FLAGS_autotune=1 / PADDLE_TPU_AUTOTUNE=1,
  or warm explicitly via the CLI);
- a **persistent JSON cache** (`PADDLE_TPU_AUTOTUNE_CACHE`, default
  `~/.cache/paddle_tpu/autotune.json`; set to the empty string to disable)
  plus an in-process memo, so tuning cost is paid once per machine;
- **pin overrides**: `FLAGS_autotune_pin` / `PADDLE_TPU_AUTOTUNE_PIN` =
  ``"family=variant[:k=v,...][;family2=...]"`` forces a candidate without
  timing (highest precedence — above memo, cache and tuning);
- a **CLI**: ``python -m paddle_tpu.kernels.autotune dump|table|clear|warm``
  to inspect, reset or pre-populate the cache.

With tuning disabled, no pin and no cache entry, ``resolve()`` returns the
registered default, so every kernel family lowers to a program bit-identical
to the hand-tuned one (asserted by tests/test_autotune.py).
"""
from __future__ import annotations

import json
import os
import statistics
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from ..observability import liveness as _liveness

# liveness beacon over one timed candidate-selection run: candidates
# compile + run on device in a loop, and a hung device wedges the warm
# silently.  900s default: a full family sweep pays one compile per
# candidate.
_liveness.declare_beacon(
    "autotune.tune", "one timed autotune selection (compile + time "
    "every candidate for one key)", deadline=900.0)

__all__ = [
    "register_family", "resolve", "tune", "warm", "clear_cache",
    "cache_path", "enabled", "key_str", "families",
]

DEFAULT_CACHE = os.path.join("~", ".cache", "paddle_tpu", "autotune.json")
_CACHE_VERSION = 1

_LOCK = threading.RLock()
_FAMILIES: Dict[str, "KernelFamily"] = {}
#: (family, key_str) -> candidate dict — the in-process memo (hit on every
#: trace after the first; resolve() must stay cheap, it runs at trace time).
#: Holds TUNED/CACHED picks only; defaults memoise separately in
#: _MEMO_DEFAULT so enabling autotune mid-process still tunes keys that
#: were first resolved while tuning was off.
_MEMO: Dict[tuple, Dict[str, Any]] = {}
_MEMO_DEFAULT: Dict[tuple, Dict[str, Any]] = {}
#: (family, key_str) -> candidate as last RETURNED by resolve() — unlike
#: _MEMO this includes pin-resolved candidates, so report() (and bench.py's
#: "autotune" JSON field) reflects what actually ran, pins included
_RESOLVED: Dict[tuple, Dict[str, Any]] = {}
_CACHE: Optional[dict] = None
_CACHE_LOADED_FROM: Optional[str] = None


class KernelFamily:
    """One tunable kernel family.

    ``candidates(key)`` returns the ordered candidate list for a key dict —
    each ``{"variant": str, "config": {...}}``, candidate [0] the hand-tuned
    default.  ``runner(candidate, key)`` builds a zero-arg callable that
    executes the candidate on synthetic data of the key's shape/dtype and
    blocks until the result is ready (None runner = resolvable but not
    timeable — resolve() falls back to the default instead of tuning).
    """

    def __init__(self, name: str,
                 candidates: Callable[[dict], List[dict]],
                 runner: Optional[Callable[[dict, dict], Callable]] = None,
                 cleanup: Optional[Callable[[dict], None]] = None,
                 traceable: Optional[Callable] = None):
        self.name = name
        self.candidates = candidates
        self.runner = runner
        # called with the key after tune() finishes — frees any synthetic
        # device operands the runners cached for that key (they would
        # otherwise pin HBM for the life of the training process)
        self.cleanup = cleanup
        # ``traceable(candidate, key) -> (fn, abstract_args)`` builds the
        # candidate's program for ABSTRACT tracing only (args are
        # ShapeDtypeStructs; nothing executes).  Feeds the TPU504 static
        # VMEM estimator: tune() prices every candidate's BlockSpec
        # working set BEFORE compiling and rejects the unfittable ones,
        # and the trace-tier audit registers one canonical program per
        # variant from the same hook.
        self.traceable = traceable


def register_family(name: str, candidates, runner=None,
                    cleanup=None, traceable=None) -> KernelFamily:
    fam = KernelFamily(name, candidates, runner, cleanup, traceable)
    with _LOCK:
        _FAMILIES[name] = fam
    return fam


def families() -> Dict[str, KernelFamily]:
    return dict(_FAMILIES)


# ---------------------------------------------------------------------------
# keys, flags, pins
# ---------------------------------------------------------------------------

def platform() -> str:
    import jax
    return jax.default_backend()


def key_str(key: dict) -> str:
    """Canonical cache key: sorted k=v pairs (values stringified)."""
    return ",".join("%s=%s" % (k, key[k]) for k in sorted(key))


def _flag(name):
    try:
        from ..utils import flags as _flags
        return _flags.fast_get(name)
    except Exception:
        return None


def enabled() -> bool:
    """Timed selection on unseen keys (pins/cache/memo are always live)."""
    if os.environ.get("PADDLE_TPU_AUTOTUNE", "").lower() in ("1", "true",
                                                             "yes"):
        return True
    return bool(_flag("autotune"))


def _single_process() -> bool:
    """Lazy in-line tuning is restricted to single-process jobs: hosts of
    a multi-controller SPMD fleet timing candidates independently can pick
    DIFFERENT variants for the same key (wall-clock noise, or a real
    per-host difference) and silently trace divergent programs / diverging
    numerics (bf16chain) across replicas.  Multi-host jobs must pre-tune —
    `python -m paddle_tpu.kernels.autotune warm` on ONE host — and ship
    the resulting cache file to every host (PADDLE_TPU_AUTOTUNE_CACHE):
    cache/pin resolution is deterministic and therefore fleet-consistent.
    """
    try:
        import jax
        return jax.process_count() == 1
    except Exception:
        return True


def _samples() -> int:
    env = os.environ.get("PADDLE_TPU_AUTOTUNE_SAMPLES")
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            pass
    v = _flag("autotune_samples")
    return max(1, int(v)) if v else 5


def cache_path() -> Optional[str]:
    """Cache file path, or None when persistence is disabled
    (PADDLE_TPU_AUTOTUNE_CACHE set to the empty string)."""
    raw = os.environ.get("PADDLE_TPU_AUTOTUNE_CACHE")
    if raw is None:
        raw = DEFAULT_CACHE
    if not raw:
        return None
    return os.path.expanduser(raw)


def _parse_scalar(v: str):
    for cast in (int, float):
        try:
            return cast(v)
        except ValueError:
            continue
    if v.lower() in ("true", "false"):
        return v.lower() == "true"
    return v


def _pins() -> Dict[str, dict]:
    """``family=variant[:k=v,...];...`` -> {family: {variant, config}}.
    FLAGS_autotune_pin wins over the PADDLE_TPU_AUTOTUNE_PIN env."""
    raw = _flag("autotune_pin") or os.environ.get(
        "PADDLE_TPU_AUTOTUNE_PIN", "")
    out = {}
    for part in str(raw).split(";"):
        part = part.strip()
        if not part or "=" not in part:
            continue
        fam, _, rest = part.partition("=")
        variant, _, cfg_s = rest.partition(":")
        config = {}
        for kv in cfg_s.split(","):
            if "=" in kv:
                ck, _, cv = kv.partition("=")
                config[ck.strip()] = _parse_scalar(cv.strip())
        out[fam.strip()] = {"variant": variant.strip(), "config": config}
    return out


# ---------------------------------------------------------------------------
# persistent cache
# ---------------------------------------------------------------------------

def _load_cache() -> dict:
    global _CACHE, _CACHE_LOADED_FROM
    path = cache_path()
    with _LOCK:
        if _CACHE is not None and _CACHE_LOADED_FROM == path:
            return _CACHE
    # file I/O outside the lock (blocking while locked stalls every
    # autotune lookup behind a slow disk): racing first loads both read
    # the file; the loser re-checks below and adopts the winner's copy
    data = {"version": _CACHE_VERSION, "families": {}}
    if path and os.path.isfile(path):
        try:
            with open(path) as f:
                loaded = json.load(f)
            if isinstance(loaded, dict) and \
                    loaded.get("version") == _CACHE_VERSION:
                data = loaded
        except (OSError, ValueError):
            pass  # unreadable/corrupt cache = empty cache
    with _LOCK:
        if _CACHE is None or _CACHE_LOADED_FROM != path:
            _CACHE = data
            _CACHE_LOADED_FROM = path
        return _CACHE


def _save_cache():
    path = cache_path()
    if not path or _CACHE is None:
        return
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".tmp.%d" % os.getpid()
        with open(tmp, "w") as f:
            json.dump(_CACHE, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass  # read-only FS etc. — memo still holds the result


def clear_cache(in_process_too: bool = True):
    """Delete the persistent cache file (and the in-process memo)."""
    global _CACHE, _CACHE_LOADED_FROM
    with _LOCK:
        path = cache_path()
        if path and os.path.isfile(path):
            os.remove(path)
        _CACHE = None
        _CACHE_LOADED_FROM = None
        if in_process_too:
            _MEMO.clear()
            _MEMO_DEFAULT.clear()


# ---------------------------------------------------------------------------
# timing + selection
# ---------------------------------------------------------------------------

def _time_callable(fn: Callable, samples: int) -> float:
    """Median-of-``samples`` wall ms.  ``fn`` must block until its device
    work is done (runners call jax.block_until_ready).  One untimed warmup
    run absorbs compilation."""
    fn()
    times = []
    for _ in range(samples):
        t0 = time.perf_counter()
        fn()
        times.append((time.perf_counter() - t0) * 1000.0)
    return float(statistics.median(times))


def _cand_sig(cand: dict) -> str:
    cfg = cand.get("config", {})
    return cand["variant"] + ":" + ",".join(
        "%s=%s" % (k, cfg[k]) for k in sorted(cfg))


def _record_event(name: str):
    try:
        from ..profiler import RecordEvent
        return RecordEvent(name)
    except Exception:
        import contextlib
        return contextlib.nullcontext()


def _vmem_reject(fam: "KernelFamily", cand: dict, key: dict
                 ) -> Optional[str]:
    """Non-empty rejection reason when the candidate's static VMEM
    footprint (TPU504 estimator, paddle_tpu.analysis.trace.vmem) exceeds
    the per-core budget.  Estimator problems never block tuning — a
    candidate we cannot price is timed normally (and fails on-device the
    way it always did)."""
    if fam.traceable is None:
        return None
    try:
        from ..analysis.trace.vmem import fits_vmem
        fn, args = fam.traceable(cand, key)
        ok, why = fits_vmem(fn, *args)
    except Exception:
        return None
    return None if ok else "rejected: vmem (%s)" % why


def tune(family_name: str, key: dict, persist: bool = True,
         verbose: bool = False, run_cleanup: bool = True) -> dict:
    """Time every candidate for ``key`` and select the fastest.

    Candidates whose build/run raises (e.g. a VMEM overflow on the real
    chip) are recorded as failed and skipped.  The winner is memoised and —
    when ``persist`` — written to the JSON cache with the full timing table.
    ``run_cleanup=False`` defers the family's operand-cache cleanup to the
    caller (warm() batches several families over the same key and would
    otherwise rebuild the shared synthetic operands per family).
    """
    fam = _FAMILIES[family_name]
    if fam.runner is None:
        raise ValueError("family %r has no runner registered" % family_name)
    cands = fam.candidates(key)
    if not cands:
        raise ValueError("family %r produced no candidates for %s"
                         % (family_name, key))
    ks = key_str(key)
    samples = _samples()
    timings: Dict[str, Any] = {}
    best, best_ms = None, None
    from ..observability import registry as _obs
    _tune_t0 = time.perf_counter()
    try:
        # tune() is cold-path: fetching the beacon per call is fine
        with _liveness.beacon("autotune.tune"), \
                _record_event("autotune::%s::%s" % (family_name, ks)):
            for cand in cands:
                sig = _cand_sig(cand)
                rejected = _vmem_reject(fam, cand, key)
                if rejected:
                    # TPU504 pre-compile gate: the static BlockSpec
                    # working set cannot fit per-core VMEM — recorded in
                    # the timing table instead of faulting on-device
                    # mid-warm (and wasting a TPU session on it)
                    timings[sig] = rejected
                    if verbose:
                        print("  %-48s %s" % (sig, rejected))
                    continue
                try:
                    fn = fam.runner(cand, key)
                    ms = _time_callable(fn, samples)
                except Exception as e:  # candidate illegal at this key
                    timings[sig] = "failed: %s" % type(e).__name__
                    continue
                timings[sig] = round(ms, 4)
                if verbose:
                    print("  %-48s %10.3f ms" % (sig, ms))
                if best_ms is None or ms < best_ms:
                    best, best_ms = cand, ms
    finally:
        _obs.histogram("autotune.tune_seconds").observe(
            time.perf_counter() - _tune_t0)
        if run_cleanup and fam.cleanup is not None:
            try:
                fam.cleanup(key)
            except Exception:
                pass
    if best is None:
        # nothing timed successfully.  A statically VMEM-rejected
        # candidate must NEVER be the fallback — the gate just proved it
        # faults on device; fall back to the first candidate that at
        # least fits (runtime failures may be transient/key-specific),
        # and fail loudly when no candidate fits at all.
        vmem_rejected = {sig for sig, v in timings.items()
                         if isinstance(v, str)
                         and v.startswith("rejected: vmem")}
        best = next((c for c in cands
                     if _cand_sig(c) not in vmem_rejected), None)
        if best is None:
            raise ValueError(
                "autotune %s [%s]: no candidate fits per-core VMEM — %s"
                % (family_name, ks, "; ".join(
                    "%s -> %s" % kv for kv in sorted(timings.items()))))
        best_ms = float("nan")
    entry = {"variant": best["variant"], "config": dict(best["config"]),
             "ms": None if best_ms != best_ms else round(best_ms, 4),
             "samples": samples, "timings": timings}
    with _LOCK:
        _MEMO[(family_name, ks)] = {"variant": entry["variant"],
                                    "config": dict(entry["config"])}
        if persist:
            cache = _load_cache()
            cache.setdefault("families", {}).setdefault(
                family_name, {})[ks] = entry
            _save_cache()
    return _MEMO[(family_name, ks)]


def resolve(family_name: str, key: dict) -> dict:
    """The hot-path lookup the kernel wrappers call at trace time.

    Precedence: pin override > in-process memo > persistent cache > timed
    selection (only when autotuning is enabled) > registered default.
    Always returns ``{"variant": str, "config": dict}``.
    """
    fam = _FAMILIES.get(family_name)
    if fam is None:
        raise KeyError("unknown autotune family %r" % family_name)
    ks = key_str(key)

    def _log(cand):
        with _LOCK:
            _RESOLVED[(family_name, ks)] = cand
        return cand

    from ..observability import registry as _obs
    pin = _pins().get(family_name)
    if pin is not None:
        default = fam.candidates(key)[0]
        _obs.counter("autotune.cache_hits").inc()
        return _log({"variant": pin["variant"] or default["variant"],
                     "config": {**default["config"], **pin["config"]}})
    with _LOCK:
        hit = _MEMO.get((family_name, ks))
        if hit is not None:
            _RESOLVED[(family_name, ks)] = hit
            _obs.counter("autotune.cache_hits").inc()
            return hit
        entry = _load_cache().get("families", {}).get(
            family_name, {}).get(ks)
        if entry is not None:
            cand = {"variant": entry["variant"],
                    "config": dict(entry["config"])}
            _MEMO[(family_name, ks)] = cand
            _RESOLVED[(family_name, ks)] = cand
            _obs.counter("autotune.cache_hits").inc()
            return cand
    _obs.counter("autotune.cache_misses").inc()
    if enabled() and fam.runner is not None and _single_process():
        return _log(tune(family_name, key))
    with _LOCK:
        default = _MEMO_DEFAULT.get((family_name, ks))
        if default is None:
            default = fam.candidates(key)[0]
            _MEMO_DEFAULT[(family_name, ks)] = default
    return _log(default)


def report() -> Dict[str, Dict[str, dict]]:
    """Snapshot of every candidate resolved in THIS process (pins
    included), keyed family -> key_str -> candidate — what bench.py
    attaches to its JSON line so the measured throughput is tied to the
    configs that ran."""
    with _LOCK:
        out: Dict[str, Dict[str, dict]] = {}
        for (fam, ks), cand in sorted(_RESOLVED.items()):
            out.setdefault(fam, {})[ks] = {"variant": cand["variant"],
                                           "config": dict(cand["config"])}
        return out


# ---------------------------------------------------------------------------
# warm — pre-populate the cache for the bench-standard keys
# ---------------------------------------------------------------------------

def _import_kernel_families():
    """Family registration happens at kernel-module import."""
    from . import decode_attention, flash_attention_pallas  # noqa: F401


def standard_keys() -> List[tuple]:
    """(family, key) pairs for the GPT-2 345M bench shapes — what the CLI
    warms by default (override shapes via the warm subcommand flags)."""
    _import_kernel_families()
    from . import flash_attention_pallas as fap
    plat = platform()
    dtype = "bfloat16" if plat == "tpu" else "float32"
    out = []
    for fam_name in ("flash_fwd", "flash_bwd", "flash_bwd_dq",
                     "flash_bwd_dkv"):
        out.append((fam_name, fap.autotune_key(
            b=8, s=1024, sk=1024, h=16, d=64, dtype=dtype, causal=True)))
    from . import decode_attention as dat
    # the serving decode step's attention at the bench-standard serving
    # shape (8 slots, 1024-token cache, GPT-2 345M heads)
    out.append(("decode_attn", dat.autotune_key(
        slots=8, t=1024, h=16, d=64, qlen=1, dtype=dtype)))
    # the paged layout at the same serving shape: 64-token pages, 16
    # pages per slot, pool sized for all 8 slots at full depth
    out.append(("decode_attn_paged", dat.paged_autotune_key(
        slots=8, pages=128, page_size=64, max_pages=16, h=16, d=64,
        qlen=1, dtype=dtype)))
    # int8 KV (ISSUE 8): the q8 gather schedules tune under their own
    # key, and the speculative verify shape (qlen = k+1) tunes the
    # multi-token masked path the verify program runs
    out.append(("decode_attn_paged", dat.paged_autotune_key(
        slots=8, pages=128, page_size=64, max_pages=16, h=16, d=64,
        qlen=1, dtype=dtype, kv_dtype="int8")))
    out.append(("decode_attn_paged", dat.paged_autotune_key(
        slots=8, pages=128, page_size=64, max_pages=16, h=16, d=64,
        qlen=5, dtype=dtype)))
    # tensor-parallel serving (ISSUE 12): the tp=2 sharded decode's
    # PER-SHARD shape (8 of the 16 heads per chip) tunes under its own
    # key so the next on-chip warm covers the multi-chip engine too
    out.append(("decode_attn_paged", dat.paged_autotune_key(
        slots=8, pages=128, page_size=64, max_pages=16, h=16, d=64,
        qlen=1, dtype=dtype, tp=2)))
    # fp8 KV (ISSUE 20) deliberately adds NO standard key: its codes
    # ride the exact q8 variant structure already registered under the
    # int8 key (another key would duplicate those pallas programs in
    # the trace registry), and the bench warms its own key on demand
    # (autotune_key carries kv_dtype, so the grids can never collide)
    # decomposed collective-matmul rings (ISSUE 20): the chunk count of
    # the tp=2 row ring at GPT-2 345M's projection shape — the family
    # exposes no pallas traceable (it is a shard_map schedule, not a
    # kernel), so this key is warm()-only and adds no registry programs
    from ..distributed import mp_overlap as mpo
    out.append(("mp_overlap", mpo.autotune_key(
        kind="row", m=8, k=4096 // 2, n=1024, n_dev=2, dtype=dtype)))
    return out


def warm(pairs=None, verbose: bool = True) -> List[dict]:
    """Tune every (family, key) pair (default: the bench-standard set) and
    persist the results.  Per-family operand-cache cleanups are deferred to
    the END of the batch: the four flash families share one per-key
    synthetic operand set, and cleaning between families would rebuild it
    (and re-run the baseline forward) four times."""
    _import_kernel_families()
    if pairs is None:
        pairs = standard_keys()
    results = []
    try:
        for fam_name, key in pairs:
            if verbose:
                print("tuning %s [%s] on %s ..." % (fam_name, key_str(key),
                                                    platform()))
            cand = tune(fam_name, key, verbose=verbose, run_cleanup=False)
            if verbose:
                print("  -> %s %s" % (cand["variant"], cand["config"]))
            results.append(cand)
    finally:
        for fam_name, key in pairs:
            fam = _FAMILIES.get(fam_name)
            if fam is not None and fam.cleanup is not None:
                try:
                    fam.cleanup(key)
                except Exception:
                    pass
    return results


# ---------------------------------------------------------------------------
# CLI: python -m paddle_tpu.kernels.autotune {dump,table,clear,warm}
# ---------------------------------------------------------------------------

def _cli_table():
    cache = _load_cache()
    fams = cache.get("families", {})
    if not any(fams.values()):
        print("autotune cache empty (%s)" % (cache_path() or "disabled"))
        return
    for fam_name in sorted(fams):
        for ks, entry in sorted(fams[fam_name].items()):
            print("%s [%s]" % (fam_name, ks))
            print("  chosen: %s %s  (median %s ms of %s)" % (
                entry["variant"], entry["config"], entry.get("ms"),
                entry.get("samples")))
            for sig, ms in sorted(entry.get("timings", {}).items(),
                                  key=lambda kv: (isinstance(kv[1], str),
                                                  kv[1])):
                print("    %-52s %s" % (sig, ms if isinstance(ms, str)
                                        else "%.3f ms" % ms))


def _cli_main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.kernels.autotune",
        description="Inspect, clear or warm the kernel autotune cache.")
    sub = ap.add_subparsers(dest="cmd", required=True)
    sub.add_parser("dump", help="print the raw cache JSON")
    sub.add_parser("table", help="print a per-key timing table")
    sub.add_parser("clear", help="delete the cache file")
    w = sub.add_parser("warm", help="run timed selection for the "
                       "bench-standard keys on this platform")
    w.add_argument("--family", help="warm only this family")
    w.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)
    if args.cmd == "dump":
        print(json.dumps(_load_cache(), indent=1, sort_keys=True))
    elif args.cmd == "table":
        _cli_table()
    elif args.cmd == "clear":
        path = cache_path()
        clear_cache()
        print("cleared %s" % (path or "(persistence disabled)"))
    elif args.cmd == "warm":
        pairs = standard_keys()
        if args.family:
            pairs = [(f, k) for f, k in pairs if f == args.family]
            if not pairs:
                raise SystemExit("no standard key for family %r"
                                 % args.family)
        warm(pairs, verbose=not args.quiet)
        _cli_table()
    return 0


if __name__ == "__main__":
    raise SystemExit(_cli_main())
