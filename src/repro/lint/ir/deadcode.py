"""I3 — dead code: expensive eqns whose results reach no output.

The fused-epilogue refactor class: a kernel rework leaves the old
intermediate (a full-vocab logits cube, a dequantized dense weight, an
extra materialized layout pass) still computed but no longer consumed.
XLA's DCE usually saves the FLOPs at compile time — but not across
`optimization_barrier`/donation boundaries, and either way the traced
graph documents intent: dead heavy compute in the jaxpr is a refactor
that forgot to delete something.

Liveness runs backward per jaxpr level. To keep the pass quiet on the
swept tree, only *expensive* dead eqns are findings: heavy primitives
(dot/conv/scan/pallas_call/sort) at any size, or any dead eqn whose
output exceeds ``MIN_DEAD_BYTES``. Effectful eqns are always live.
jit bodies are entered with the *caller's* liveness of the call's
outputs, so an output computed inside a jit but dropped by every caller
in the graph is found too. scan/while/cond bodies are analyzed with all
body outputs assumed live (conservative: no false positives from carry
plumbing).
"""
from __future__ import annotations

from typing import Iterable

from ..core import Finding
from .core import (
    CALL_LIKE, IREntry, aval_bytes, fmt_aval, ir_pass, subjaxprs,
)

_HEAVY = {
    "dot_general", "conv_general_dilated", "scan", "while", "pallas_call",
    "sort", "top_k", "custom_jvp_call", "custom_vjp_call",
}
#: a dead cheap eqn must at least materialize this much to be worth a report
MIN_DEAD_BYTES = 1 << 16


def _is_var(v) -> bool:
    return hasattr(v, "aval") and type(v).__name__ != "Literal"


def _analyze(jaxpr, live_out, entry, findings, where=""):
    """Backward liveness over one Jaxpr level.

    live_out: per-outvar liveness booleans from the caller's perspective.
    """
    live: set = set()
    for v, is_live in zip(jaxpr.outvars, live_out):
        if is_live and _is_var(v):
            live.add(v)

    for eqn in reversed(jaxpr.eqns):
        out_live = [
            _is_var(v) and v in live for v in eqn.outvars
        ]
        effectful = bool(getattr(eqn, "effects", None))
        if any(out_live) or effectful:
            for v in eqn.invars:
                if _is_var(v):
                    live.add(v)
            # enter jit-style bodies with the caller's output liveness so
            # dead compute *inside* a jit whose result is dropped outside
            # is still found
            name = eqn.primitive.name
            sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            sub = getattr(sub, "jaxpr", sub)
            if sub is not None and hasattr(sub, "eqns"):
                if name in CALL_LIKE:
                    _analyze(sub, out_live, entry, findings,
                             where=f"{where}{name}/")
                else:
                    # scan/while/cond etc: conservative — everything the
                    # body returns counts as live
                    for s in subjaxprs(eqn):
                        _analyze(s, [True] * len(s.outvars), entry,
                                 findings, where=f"{where}{name}/")
            continue
        # fully dead eqn — expensive enough to report?
        out_bytes = sum(aval_bytes(v.aval) for v in eqn.outvars)
        if eqn.primitive.name in _HEAVY or out_bytes >= MIN_DEAD_BYTES:
            shapes = ", ".join(fmt_aval(v.aval) for v in eqn.outvars)
            findings.append(Finding(
                "I3", entry.path, 0, 0,
                f"dead `{where}{eqn.primitive.name}` — its result(s) "
                f"[{shapes}] reach no output ({out_bytes} B computed and "
                f"dropped); a refactor left the old intermediate behind",
            ))


@ir_pass("I3", "dead code: heavy eqns / large intermediates whose results "
              "reach no jaxpr output (the fused-epilogue refactor class)")
def check_deadcode(entry: IREntry) -> Iterable[Finding]:
    findings: list[Finding] = []
    jaxpr = entry.jaxpr.jaxpr
    _analyze(jaxpr, [True] * len(jaxpr.outvars), entry, findings)
    return findings
