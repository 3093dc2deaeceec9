"""The recompile watchdog — turn silent retraces into a loud runtime signal.

The serving engine's headline bug class (PR 5: a per-token retrace of the
decode step that cost ~100x throughput and was invisible for five PRs) is
structural: jax.jit happily compiles a fresh program for every new
argument-shape/dtype signature, and nothing in the runtime says so.  The
watchdog instruments the compile-once entry points — ``TrainStep``,
serving decode/prefill, the 1F1B pipeline step — by checking the jit's
program-cache size after every call:

* every growth increments ``compile.count{entry=<name>}`` in the default
  metrics registry (so bench JSON lines and Prometheus scrapes carry
  compile counts from now on), and
* growth past the entry's ``expected`` budget emits ONE structured
  :class:`RecompileWarning` per excess compile — or raises
  :class:`RecompileError` immediately under ``PADDLE_TPU_STRICT_COMPILE=1``
  (the CI bench-smoke mode).

``watch()`` wraps the jitted callable transparently: attribute access
(``_cache_size``, ``lower``, ...) is delegated, so existing audit hooks
and compile-count properties keep working on a watched entry.

The same wrapper names what set-up pays for.  JAX publishes the seconds of
every trace, lowering and backend compile (or cache load) with the
function's name; a watched entry marks itself as the thread's current entry
for the length of a call, and one listener files each duration under it as
``compile.phase_seconds{entry, phase}`` and each persistent-cache verdict
as ``compile.cache{entry, result}``.  What arrives outside any watched call
(eager ops, model construction, weight loading) is filed under
``entry="(unwatched)"``.  And a watched entry remembers each program it
compiled (:class:`Program`), so that the program's text can be read later
for which instruction carries which of the program's scopes
(:mod:`.scopes`).
"""
from __future__ import annotations

import collections
import contextlib
import json
import os
import threading
import time
import warnings
import weakref
from typing import Callable, Dict, Optional

from . import registry as _registry

__all__ = ["RecompileWarning", "RecompileError", "WatchedEntry", "watch",
           "Program", "compile_counts", "listen", "live_entries",
           "programs", "resync_counter", "strict_mode", "UNWATCHED"]

#: the ``entry`` label of compile events that arrive outside any watched call
UNWATCHED = "(unwatched)"

_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    # on a persistent-cache hit this is the read and deserialisation
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_RESULTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
# .entry: the WatchedEntry being called (or the Program being re-read)
_current = threading.local()
_listening = False


@contextlib.contextmanager
def _filing_under(owner):
    """Compile events of this thread are ``owner``'s inside the block (the
    cold paths' form; ``WatchedEntry.__call__`` spells it out)."""
    outer = getattr(_current, "entry", None)
    _current.entry = owner
    try:
        yield
    finally:
        _current.entry = outer


def _on_duration(event, seconds, fun_name=None, **_):
    phase = _PHASES.get(event)
    if phase is None:
        return
    entry = getattr(_current, "entry", None)
    if entry is None:
        name = UNWATCHED
    elif _same_function(entry.fn_name, fun_name):
        name = entry.entry_name
    else:
        # a jit traced inside the entry's own trace, or an eager op on a
        # constant: its seconds are already inside the entry's trace phase
        return
    _registry.counter("compile.phase_seconds", ("entry", "phase")).labels(
        entry=name, phase=phase).inc(seconds)


def _on_event(event, **_):
    result = _CACHE_RESULTS.get(event)
    if result is None:
        return
    entry = getattr(_current, "entry", None)
    _registry.counter("compile.cache", ("entry", "result")).labels(
        entry=UNWATCHED if entry is None else entry.entry_name,
        result=result).inc()


def listen():
    """Register the two listeners, once a process.  ``import paddle_tpu``
    calls it, so that the eager programs of model construction are counted
    from the first; ``watch`` calls it for a process that imported this
    package alone.  Imports jax, starts no backend."""
    global _listening
    with _ENTRIES_LOCK:
        if _listening:
            return
        _listening = True
    from jax import monitoring
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


class RecompileWarning(UserWarning):
    """A supposedly compile-once jit entry compiled again at runtime."""


class RecompileError(RuntimeError):
    """Strict-mode (PADDLE_TPU_STRICT_COMPILE=1) recompile failure.

    Fatal by design — a CI/bench kill switch, not a recoverable signal:
    the offending call has already EXECUTED when the cache growth is
    detected, so for entries with donated operands (TrainStep, serving
    decode) the caller's input buffers are consumed and the step's output
    is discarded with the raise.  Catching this to log-and-continue will
    hit deleted-buffer errors on the next call; let it terminate the run.
    """


def strict_mode() -> bool:
    return os.environ.get("PADDLE_TPU_STRICT_COMPILE", "0") not in (
        "0", "", "false", "off")


#: process-wide table of watched entries: name -> [weakref, ...] (several
#: engines may watch the same logical entry name; counts sum).  Weak on
#: purpose: a WatchedEntry holds the jit, which holds its compiled
#: programs AND the model closure — a strong global table would pin every
#: TrainStep/engine ever built for the life of the process.
_ENTRIES: Dict[str, list] = {}
_ENTRIES_LOCK = threading.Lock()


class WatchedEntry:
    """A jitted callable plus its compile budget.  Call it like the jit;
    every program-cache growth is metered and budget-checked."""

    def __init__(self, name: str, fn: Callable,
                 expected: Optional[int] = None):
        self._name = name
        self._fn = fn
        self._expected = expected
        self.fn_name = getattr(fn, "__name__", "")
        self._seen = self._raw_cache_size()
        self._counter = _registry.counter("compile.count", ("entry",))
        self._lock = threading.Lock()
        self._programs: list = []     # one Program a compile
        with _ENTRIES_LOCK:
            refs = _ENTRIES.setdefault(name, [])
            refs[:] = [r for r in refs if r() is not None]
            refs.append(weakref.ref(self))

    # -- introspection -----------------------------------------------------

    @property
    def entry_name(self) -> str:
        return self._name

    @property
    def compile_count(self) -> int:
        """Programs this entry's jit cache holds right now."""
        return self._raw_cache_size()

    def _raw_cache_size(self) -> int:
        try:
            return int(self._fn._cache_size())
        except Exception:
            return 0

    def __getattr__(self, name):
        # transparent delegation: audit hooks (.lower), the engine's
        # _cache_size-based properties, functools metadata all pass through
        fn = self.__dict__.get("_fn")
        if fn is None:
            raise AttributeError(name)
        return getattr(fn, name)

    # -- the metered call --------------------------------------------------

    def __call__(self, *args, **kwargs):
        outer = getattr(_current, "entry", None)
        _current.entry = self
        try:
            out = self._fn(*args, **kwargs)
        finally:
            _current.entry = outer
        n = self._raw_cache_size()
        if n != self._seen:
            self._on_growth(n, args, kwargs)
        return out

    def _remember(self, args, kwargs):
        """Keep the program this call compiled (:class:`Program`).  The
        call has already run, on donated buffers: whatever goes wrong here
        costs the scope index one program, never the caller its step."""
        try:
            sig_args, sig_kwargs = _abstract((args, kwargs))
            with _filing_under(self):
                traced = self._fn.trace(*sig_args, **sig_kwargs)
        except Exception:
            return
        program = Program(self._name, self.fn_name, traced)
        with _ENTRIES_LOCK:
            self._programs.append(program)
            _PROGRAMS.setdefault(self._name, collections.deque(
                maxlen=_PROGRAMS_KEPT)).append(program)

    def _on_growth(self, n: int, args, kwargs):
        with self._lock:
            grew = n - self._seen
            if grew <= 0:       # cache cleared/shrunk: resync, no event
                self._seen = n
                return
            self._seen = n
        self._remember(args, kwargs)
        self._counter.labels(entry=self._name).inc(grew)
        from . import flight as _flight
        _flight.record("recompile", entry=self._name, compile_count=n,
                       expected=self._expected)
        if self._expected is not None and n > self._expected:
            payload = json.dumps({
                "event": "recompile", "entry": self._name,
                "compile_count": n, "expected": self._expected}, sort_keys=True)
            if strict_mode():
                # black-box dump BEFORE the raise: the strict error is
                # fatal by design, so this is the post-mortem's one shot
                # at the ring + engine state (no-op unless armed)
                _flight.crash_dump({
                    "kind": "recompile", "entry": self._name,
                    "compile_count": n, "expected": self._expected})
                raise RecompileError(
                    "compile-once violation: %s — the jit entry %r now "
                    "holds %d programs (budget %d); an argument "
                    "shape/dtype/structure is varying across calls"
                    % (payload, self._name, n, self._expected))
            warnings.warn(
                "RECOMPILE %s — entry %r compiled %d time(s) against a "
                "budget of %d; a supposedly-static argument is varying "
                "(set PADDLE_TPU_STRICT_COMPILE=1 to make this fatal)"
                % (payload, self._name, n, self._expected),
                RecompileWarning, stacklevel=3)


    # -- which instruction carries which scope (cold path) ------------------

    def instruction_scopes(self) -> Dict[str, dict]:
        """``{HLO module name: {instruction name: scope or None}}`` of the
        programs this entry compiled (:meth:`Program.instruction_scopes`)."""
        with self._lock:
            programs = list(self._programs)
        found: Dict[str, dict] = {}
        for program in programs:
            module, table = program.instruction_scopes()
            found.setdefault(module, {}).update(table)
        return found


class Program:
    """One program a watched entry compiled, kept so that its HLO text can
    be read later: JAX will not hand out the text of the executable a jit
    call built, so the traced program (the jaxpr, found again in JAX's
    trace cache: no Python is re-run) is lowered and compiled once more.
    While the jaxpr lives JAX still holds that lowering and its executable,
    so the second compile is a look-up; where they have gone it is a
    persistent-cache read, or a compile.  A cold path all the same: after a
    measurement, never inside one.

    The newest few of each entry name are held strongly at module level
    (``_PROGRAMS``): a jaxpr holds no model state, and a reader that comes
    after the step object has gone, as the benchmark's do, still finds its
    program."""

    __slots__ = ("entry_name", "fn_name", "_traced", "_provenance",
                 "_scopes", "read_seconds")

    def __init__(self, entry_name, fn_name, traced):
        self.entry_name = entry_name
        self.fn_name = fn_name
        self._traced = traced
        self._provenance = None
        self._scopes = None
        #: what reading the text cost (compile look-up and parse), once read
        self.read_seconds = None

    def provenance(self):
        """``(HLO module name, {instruction name: Provenance})``
        (:func:`.scopes.instruction_provenance`); kept, and the text is
        not, so a second call compiles and parses nothing."""
        if self._provenance is None:
            from . import scopes as _scopes
            t0 = time.perf_counter()
            with _filing_under(self):   # its compile events are the entry's
                text = self._traced.lower().compile().as_text()
            self._provenance = _scopes.instruction_provenance(text)
            self.read_seconds = time.perf_counter() - t0
        return self._provenance

    def instruction_scopes(self):
        """``(HLO module name, {instruction name: scope or None})``:
        :meth:`provenance` as :func:`.scopes.instruction_scopes` projects
        it, from the same compile and the same parse."""
        if self._scopes is None:
            from . import scopes as _scopes
            module, table = self.provenance()
            self._scopes = (module, _scopes.own_roles(table))
        return self._scopes


#: how many programs of one entry name stay readable after their entries
#: died (the bucketed prefill compiles one a bucket; replicas share a name)
_PROGRAMS_KEPT = 8
_PROGRAMS: Dict[str, "collections.deque[Program]"] = {}


def _same_function(fn_name: str, fun_name: Optional[str]) -> bool:
    """Whether a JAX compile event's ``fun_name`` names the function
    ``fn_name``: the trace event says ``step_fn``, the lowering and the
    backend ``jit(step_fn)``."""
    if not fun_name or not fn_name:
        return False
    return fun_name in (fn_name, "jit(%s)" % fn_name, "pjit(%s)" % fn_name,
                        "jit_" + fn_name)


def _abstract(tree):
    """The call's arguments with every array replaced by its shape, dtype
    and (where it was committed to one) sharding: what ``jit.trace`` needs
    to find the same program, holding no buffer.  Read after the call, so a
    donated array is already deleted; its aval and sharding still answer."""
    import jax

    def leaf(a):
        if not isinstance(a, jax.Array) or isinstance(a, jax.core.Tracer):
            return a
        sharding = a.sharding if getattr(a, "committed", True) else None
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding,
                                    weak_type=getattr(a, "weak_type", False))
    return jax.tree_util.tree_map(leaf, tree)


def programs() -> list:
    """Every remembered :class:`Program`, in entry-name order."""
    with _ENTRIES_LOCK:
        return [p for _, kept in sorted(_PROGRAMS.items()) for p in kept]


def watch(name: str, fn: Callable,
          expected: Optional[int] = None) -> WatchedEntry:
    """Wrap a jitted callable as a watched entry.  ``expected`` is the
    compile budget (1 for compile-once entries, ``len(buckets)`` for the
    bucketed prefill, None to meter without a budget)."""
    listen()
    return WatchedEntry(name, fn, expected)


def live_entries() -> list:
    """Every watched entry still alive, in name order."""
    with _ENTRIES_LOCK:
        return [e for _, refs in sorted(_ENTRIES.items())
                for e in (r() for r in refs) if e is not None]


def compile_counts() -> Dict[str, int]:
    """{entry name: total programs held} across every live watched entry
    in the process — what bench.py / bench_decode.py attach to their JSON
    lines."""
    counts: Dict[str, int] = {}
    for e in live_entries():
        counts[e.entry_name] = counts.get(e.entry_name, 0) + e.compile_count
    return counts


def resync_counter():
    """Re-align ``compile.count{entry=}`` with the live jit cache sizes.

    The watchdog's ground truth is the cache size; the registry counter is
    its exported shadow.  After ``Registry.reset()`` (e.g. a bench dropping
    warmup samples) the shadow reads 0 while the caches still hold their
    programs — call this to bring Prometheus/JSONL exports back into
    agreement with :func:`compile_counts`."""
    c = _registry.counter("compile.count", ("entry",))
    for name, n in compile_counts().items():
        leaf = c.labels(entry=name)
        delta = n - leaf.value
        if delta > 0:
            leaf.inc(delta)
