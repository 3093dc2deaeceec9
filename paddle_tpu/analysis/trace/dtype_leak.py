"""TPU501 — bf16-region f32-upcast leak detection.

The f32 analogue of the s64 HLO audit (tests/test_x64_audit.py +
rule TPU201): in a program whose compute is declared bf16 (the flash/CE/LN
kernel variants traced at bf16, AMP regions), f32 is the *statistics and
accumulator* dtype — softmax max/sum chains, lse, variance, the optimizer
masters.  An f32 **compute** chain that re-materializes activations in
f32 — a transcendental activation (tanh/erf/logistic) applied to an
upcast, or a matmul fed f32-converted bf16 operands instead of bf16
operands with f32 accumulation — silently doubles VPU lane pressure and
HBM traffic in exactly the regions the bf16 variants exist to slim.

Mechanically: every ``convert_element_type`` bf16→f32 equation must feed
only primitives in :data:`F32_ACCUM_OPS` (the allowlist is shared at
``paddle_tpu.analysis.F32_ACCUM_OPS`` the way ``S64_COMPUTE_OPS`` is
shared between TPU201 and the runtime HLO audit, so the static and
runtime vocabularies cannot diverge).  A consumer outside the allowlist —
an MXU op or a transcendental — is the leak signal.

Scoping: consumers are resolved within the upcast's own jaxpr scope; a
value escaping into a subjaxpr is accounted to the call primitive
(``scan``/``cond``/``jit`` are allowlisted — the subjaxpr's own converts
are audited in their own scope).
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

from ..core import Finding
from .core import OpPathCounter, TracePass, TraceProgram, subjaxprs

__all__ = ["F32_ACCUM_OPS", "DtypeLeakPass"]

#: primitives allowed to consume a bf16→f32 upcast inside a bf16 region —
#: the statistics/accumulator vocabulary.  Reductions and running stats,
#: the softmax/lse chain (exp/log/sub/max against stats), normalization
#: (div/mul/rsqrt/sqrt by stats), structural/layout ops (free), compares,
#: select, and the call primitives whose bodies are audited separately.
#: NOT here — and therefore the leak signal: ``dot_general`` / conv (use
#: bf16 operands with ``preferred_element_type=f32`` accumulation), and
#: the transcendental activations (tanh/erf/logistic/pow/sin/cos...) that
#: re-run whole activation tensors on the f32 VPU path.
F32_ACCUM_OPS = frozenset({
    # reductions / accumulators
    "reduce_sum", "reduce_max", "reduce_min", "add_any", "cumsum",
    "cumlogsumexp", "argmax", "argmin",
    # softmax / lse statistic chain
    "exp", "exp2", "log", "log1p", "expm1", "sub", "add", "max", "min",
    "mul", "div", "neg", "abs", "sign",
    # normalization stats
    "rsqrt", "sqrt", "square", "integer_pow",
    # structural / layout (free at any dtype)
    "broadcast_in_dim", "reshape", "transpose", "slice", "squeeze",
    "dynamic_slice", "dynamic_update_slice", "concatenate", "pad",
    "rev", "select_n", "gather", "convert_element_type", "copy",
    "stop_gradient", "clamp",
    # comparisons (produce bool)
    "lt", "le", "gt", "ge", "eq", "ne", "is_finite",
    # call primitives — bodies audited in their own scope
    "scan", "while", "cond", "jit", "closed_call", "core_call",
    "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "remat", "checkpoint", "shard_map", "pallas_call", "named_call",
})

_BF16 = "bfloat16"
_F32 = "float32"


def _scope_consumers(jaxpr) -> Dict[int, List[str]]:
    """id(var) -> consuming primitive names within one jaxpr scope (a use
    as a scope output counts as the pseudo-consumer "output", which is
    always allowed — returning f32 stats is the point)."""
    cons: Dict[int, List[str]] = {}
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if hasattr(v, "aval"):
                cons.setdefault(id(v), []).append(eqn.primitive.name)
    for v in jaxpr.outvars:
        if hasattr(v, "aval"):
            cons.setdefault(id(v), []).append("output")
    return cons


class DtypeLeakPass(TracePass):
    """TPU501: no f32 compute leaks inside declared-bf16 regions."""

    rule = "TPU501"
    name = "dtype_leak"
    description = ("bf16-region bf16->f32 upcasts feed only the shared "
                   "statistics/accumulator allowlist (F32_ACCUM_OPS)")

    def check(self, program: TraceProgram) -> Iterable[Finding]:
        if not program.meta.get("bf16_region") or program.jaxpr is None:
            return
        yield from self._check_jaxpr(
            program, getattr(program.jaxpr, "jaxpr", program.jaxpr),
            OpPathCounter())

    def _check_jaxpr(self, program, jaxpr, counter) -> Iterable[Finding]:
        cons = _scope_consumers(jaxpr)
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            path = counter.path_for(eqn)
            if prim == "convert_element_type":
                src = eqn.invars[0]
                src_dt = str(getattr(getattr(src, "aval", None), "dtype",
                                     ""))
                dst_dt = str(eqn.params.get("new_dtype", ""))
                if src_dt == _BF16 and dst_dt == _F32:
                    bad = sorted({
                        c for c in cons.get(id(eqn.outvars[0]), [])
                        if c not in F32_ACCUM_OPS and c != "output"})
                    if bad:
                        yield self.finding(
                            program, path,
                            "bf16->f32 upcast consumed by non-accumulator "
                            "op%s %s — keep the chain bf16 (f32 is for "
                            "statistics/accumulators; matmuls should take "
                            "bf16 operands with preferred_element_type="
                            "f32)" % ("s" if len(bad) > 1 else "",
                                      ", ".join(bad)))
            for _tag, sub in subjaxprs(eqn):
                yield from self._check_jaxpr(program, sub, counter)
