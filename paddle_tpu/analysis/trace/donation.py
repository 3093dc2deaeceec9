"""TPU502 — donation audit: declared donations must materialize as
input-output aliasing in the lowered program.

``donate_argnums`` is a *request*: XLA only aliases a donated input onto
an output of identical shape/dtype/layout.  When a refactor breaks the
match — an output dtype drifts (fp32 master -> bf16 param), an output is
dropped, a tree reorders — jax silently downgrades the donation to a
warning-at-dispatch and the program holds BOTH buffers live: peak HBM for
the step state **doubles** with zero functional signal.  On the GPT
configs that is the difference between fitting and OOM.

Mechanically: the lowered StableHLO entry (``func.func public @main``)
carries ``tf.aliasing_output = N`` on every input argument whose donation
materialized; a flat input that the jaxpr declares donated
(``donated_invars`` on the jit equation, or the registry's recorded
metadata) but whose entry argument carries no aliasing attribute is a
donation miss.  Findings are keyed by the flat input's tree label
(``in[3]:params/linear.weight``) so baselines survive unrelated
signature growth.
"""
from __future__ import annotations

import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

from ..core import Finding
from .core import TracePass, TraceProgram, walk_eqns

__all__ = ["DonationPass", "parse_entry_aliasing", "declared_donations"]

_MAIN_RE = re.compile(
    r"func\.func\s+(?:public\s+)?@main\s*\((?P<args>.*?)\)\s*->"
    r"(?P<results>[^\n]*)",
    re.S)
_ARG_RE = re.compile(
    r"%arg(?P<idx>\d+):\s*(?P<type>(?:tensor|!stablehlo\.token)[^{,)]*)")
_TYPE_RE = re.compile(r"tensor<[^>]+>")


def _balanced_attrs(text: str, pos: int) -> str:
    """The ``{...}`` attribute dict starting at ``text[pos]`` (after
    optional blanks), or "" when the argument has none.  Braces nest — a
    sharded entry's arguments carry ``sdy.sharding = #sdy.sharding<@mesh,
    [{}, {"mp"}]>`` AHEAD of ``tf.aliasing_output`` — and may sit inside
    quoted strings, so the dict is matched by depth, not by regex: cutting
    it short reads materialized donations as misses on exactly the sharded
    entries."""
    while pos < len(text) and text[pos] in " \t\n":
        pos += 1
    if pos >= len(text) or text[pos] != "{":
        return ""
    depth, quoted, start = 0, False, pos
    for i in range(pos, len(text)):
        c = text[i]
        if c == '"':
            quoted = not quoted
        elif not quoted and c == "{":
            depth += 1
        elif not quoted and c == "}":
            depth -= 1
            if depth == 0:
                return text[start + 1:i]
    return text[start + 1:]


def parse_entry_aliasing(lowered_text: str
                         ) -> Optional[Dict[int, Dict[str, Any]]]:
    """{flat input index: {"aliased", "donor", "type", "result_match"}}
    parsed from the StableHLO entry signature, or None when no @main is
    found.

    jax emits two spellings of a live donation: ``tf.aliasing_output``
    when it paired input and output itself (single-device path), and
    ``jax.buffer_donor`` when pairing is deferred to XLA (GSPMD path) —
    for the latter the statically-checkable invariant is that a
    type-compatible output EXISTS for the donor.  *No attribute at all*
    on a declared-donated input means jax dropped the donation at
    lowering: the silent miss this pass exists to catch."""
    m = _MAIN_RE.search(lowered_text)
    if not m:
        return None
    result_types = _TYPE_RE.findall(m.group("results"))
    out: Dict[int, Dict[str, Any]] = {}
    args = m.group("args")
    for am in _ARG_RE.finditer(args):
        attrs = _balanced_attrs(args, am.end())
        ty = am.group("type").strip()
        out[int(am.group("idx"))] = {
            "aliased": "tf.aliasing_output" in attrs,
            "donor": "jax.buffer_donor" in attrs,
            "type": ty,
            "result_match": ty in result_types,
        }
    return out


def declared_donations(program: TraceProgram) -> Optional[Tuple[bool, ...]]:
    """Per-flat-input donation flags: the registry's recorded metadata
    first, else the ``donated_invars`` of the outermost jit equation."""
    meta = program.meta.get("donated_invars")
    if meta is not None:
        return tuple(bool(b) for b in meta)
    if program.jaxpr is None:
        return None
    for site in walk_eqns(program.jaxpr):
        if site.depth == 0 and site.eqn.primitive.name == "jit":
            di = site.eqn.params.get("donated_invars")
            if di is not None and any(di):
                return tuple(bool(b) for b in di)
    return None


class DonationPass(TracePass):
    """TPU502: every declared donation aliases an output in the lowering."""

    rule = "TPU502"
    name = "donation"
    description = ("declared donate_argnums materialize as input-output "
                   "aliasing (tf.aliasing_output) in the lowered entry")

    def check(self, program: TraceProgram) -> Iterable[Finding]:
        donated = declared_donations(program)
        if not donated or not any(donated):
            return
        text = program.lowered_text
        if not text:
            return  # jaxpr-only programs (kernels) carry no entry to audit
        entry = parse_entry_aliasing(text)
        if entry is None:
            yield self.finding(
                program, "entry",
                "program declares donations but its lowered text has no "
                "@main entry to audit")
            return
        labels = program.meta.get("donate_labels", {})
        if len(entry) != len(donated):
            # keep_unused=False dropped inputs: indices no longer align
            # 1:1 with the jaxpr's invars.  Refuse to guess — a misaligned
            # audit could baseline the wrong parameter forever.
            yield self.finding(
                program, "entry",
                "cannot align donation flags with the lowered entry: %d "
                "jaxpr inputs vs %d entry arguments (keep_unused "
                "pruning?) — re-register the program with used inputs"
                % (len(donated), len(entry)))
            return
        for i, don in enumerate(donated):
            if not don:
                continue
            info = entry.get(i, {"aliased": False, "donor": False,
                                 "type": "?", "result_match": False})
            if info["aliased"]:
                continue
            if info["donor"] and info["result_match"]:
                continue  # GSPMD path: XLA pairs it; a matching output
            label = labels.get(i) or labels.get(str(i)) or ""
            sym = "in[%d]%s" % (i, ":" + label if label else "")
            if info["donor"]:
                yield self.finding(
                    program, sym,
                    "donated input %d%s is marked jax.buffer_donor but NO "
                    "output shares its type %s — XLA cannot pair it and "
                    "the donation will be dropped at compile; peak HBM "
                    "holds both copies"
                    % (i, " (%s)" % label if label else "", info["type"]))
            else:
                yield self.finding(
                    program, sym,
                    "donated input %d%s does not alias any output in the "
                    "lowering — the donation silently failed (shape/dtype "
                    "drift between the donated buffer and every output?); "
                    "peak HBM holds both copies"
                    % (i, " (%s)" % label if label else ""))
