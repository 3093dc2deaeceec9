"""TPU505 — dead/duplicated subcomputation + stray host-callback audit.

Three program hygiene invariants at the jaxpr level:

* **dead subcomputation** — an effect-free equation whose every output is
  unused in its scope.  jax does not DCE at trace time, so work a
  refactor orphaned (a loss term no longer returned, a residual nobody
  consumes) silently rides along into every compile; XLA usually drops
  it, but the trace/compile time is paid forever and an *effectful* dead
  op (or one behind a custom call boundary) ships to the device.  Only
  expensive primitives fire (matmuls, convs, reductions, scans, kernel
  calls) — dead converts/broadcasts are routine tracing artifacts.
* **duplicated subcomputation** — two equations in one scope with the
  same primitive, same inputs and same parameters: a CSE miss at the
  program level (XLA's CSE runs per-fusion and misses cross-region
  duplicates, e.g. a re-computed lse that the bwd already receives as a
  residual).  Same expensive-primitive scoping.
* **stray host callback** — ``pure_callback`` / ``io_callback`` /
  ``debug_callback`` / ``debug_print`` (``jax.debug.print``) in a production program
  force a device→host round-trip per step; a leftover debug print in the
  train step is a silent multi-ms stall.  Programs that legitimately
  call back (registered with ``allow_callbacks``) are exempt.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, List, Tuple

from ..core import Finding
from .core import OpPathCounter, TracePass, TraceProgram, subjaxprs

__all__ = ["EXPENSIVE_PRIMS", "CALLBACK_PRIMS", "PurityPass"]

#: primitives worth flagging when dead or duplicated (cheap layout ops
#: are routine tracing artifacts and stay exempt).
EXPENSIVE_PRIMS = frozenset({
    "dot_general", "conv_general_dilated", "reduce_sum", "reduce_max",
    "reduce_min", "reduce_prod", "cumsum", "cumlogsumexp", "sort",
    "scatter", "scatter-add", "gather", "scan", "while", "jit",
    "pallas_call", "custom_vjp_call", "custom_jvp_call", "shard_map",
    "exp", "log", "tanh", "erf", "logistic", "rsqrt",
})

CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "host_callback_call", "outside_call",
})


def _is_drop(var) -> bool:
    # DropVar repr is "_"; isinstance check kept duck-typed so the pass
    # survives jax moving the class between core modules
    return type(var).__name__ == "DropVar" or repr(var) == "_"


def _param_sig(params: Dict[str, Any]) -> str:
    """Hashable parameter signature excluding jaxpr-valued params (eqns
    with subjaxprs are excluded from duplicate detection anyway)."""
    items = []
    for k in sorted(params):
        v = params[k]
        if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
            return ""  # not comparable
        items.append("%s=%r" % (k, v))
    return ";".join(items)


class PurityPass(TracePass):
    """TPU505: no dead/duplicated expensive work, no stray callbacks."""

    rule = "TPU505"
    name = "purity"
    description = ("no dead or duplicated expensive subcomputations, no "
                   "stray host callbacks in the traced program")

    def check(self, program: TraceProgram) -> Iterable[Finding]:
        if program.jaxpr is None:
            return
        jaxpr = getattr(program.jaxpr, "jaxpr", program.jaxpr)
        yield from self._scope(program, jaxpr, OpPathCounter())

    def _scope(self, program, jaxpr, counter) -> Iterable[Finding]:
        used = set()
        for eqn in jaxpr.eqns:
            for v in eqn.invars:
                if hasattr(v, "aval"):
                    used.add(id(v))
        for v in jaxpr.outvars:
            if hasattr(v, "aval"):
                used.add(id(v))

        seen: Dict[Tuple, str] = {}
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            path = counter.path_for(eqn)

            if prim in CALLBACK_PRIMS \
                    and not program.meta.get("allow_callbacks"):
                cb = eqn.params.get("callback")
                yield self.finding(
                    program, path,
                    "host callback %s%s in a production program — forces "
                    "a device->host round-trip every step (leftover "
                    "debug hook?)"
                    % (prim, " (%s)" % cb if cb is not None else ""))

            effects = getattr(eqn, "effects", None)
            # tracing erases the user-code/artifact distinction (an unused
            # result becomes a DropVar either way), so every effect-free
            # expensive eqn with no live output fires; KNOWN artifacts of
            # jax's own machinery (e.g. the softmax custom_jvp primal
            # re-trace in the train step) are baselined with reasons —
            # that is exactly what (rule, program, op-path) keys are for
            dead = (not effects
                    and all(_is_drop(v) or id(v) not in used
                            for v in eqn.outvars))
            if dead and prim in EXPENSIVE_PRIMS:
                yield self.finding(
                    program, path,
                    "dead subcomputation: %s result is never used in its "
                    "scope — orphaned work rides into every compile"
                    % prim)

            has_sub = bool(subjaxprs(eqn))
            if prim in EXPENSIVE_PRIMS and not has_sub and not dead:
                psig = _param_sig(eqn.params)
                invar_sig = tuple(
                    id(v) if hasattr(v, "aval") else repr(v)
                    for v in eqn.invars)
                dup_key = (prim, invar_sig, psig)
                if dup_key in seen:
                    yield self.finding(
                        program, path,
                        "duplicated subcomputation: identical %s (same "
                        "inputs, same parameters) already computed at %s "
                        "— CSE miss, compute it once and reuse"
                        % (prim, seen[dup_key]))
                else:
                    seen[dup_key] = path

            for _tag, sub in subjaxprs(eqn):
                yield from self._scope(program, sub, counter)
