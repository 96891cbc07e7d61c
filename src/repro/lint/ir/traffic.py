"""I4 — traffic model: per-jaxpr bytes-moved estimate vs the roofline.

From eqn shapes alone, estimate the memory the traced graph moves: every
*materializing* leaf eqn contributes its operand + result bytes (a read
and a write per array), scan bodies are multiplied by their trip count,
jit bodies are entered (the call eqn itself contributes nothing — its
body does), and `pallas_call` contributes only its HBM operands/results
(kernel-internal VMEM movement is the AST R5 budget rule's
jurisdiction). Pure layout/view and cheap elementwise eqns
(reshape/transpose/broadcast/compare/...) are excluded — XLA fuses them
into their consumers, and counting them made the estimate track graph
*size* instead of graph *traffic*.

For mpGeMM entries the estimate is cross-checked against the analytic
`roofline.analysis.mpgemm_cost` model: a finding fires when

    estimate > factor * mpgemm_cost(m_out, k, m_tokens).bytes

with ``factor`` = entry.meta["traffic_factor"] (default
``DEFAULT_FACTOR``; the registry sets per-impl factors ~2x above the
measured ratio of the current graphs — see tests/test_lint_ir.py — so a
rework that suddenly materializes a few times more intermediates blows
through). Entries without cost meta (the engine graphs have no
single-GeMM cost model) are skipped.
"""
from __future__ import annotations

from typing import Iterable

from ..core import Finding
from .core import CALL_LIKE, IREntry, aval_bytes, ir_pass

#: serving-path default (vlut_packed/mad_int8 measure <= ~4x); the
#: registry overrides per impl for the table-materializing reference impls
DEFAULT_FACTOR = 8.0

#: eqns XLA fuses away (views, broadcasts, cheap elementwise/compare):
#: counted at zero so the estimate tracks materialized traffic
_FUSED_AWAY = frozenset({
    "reshape", "transpose", "broadcast_in_dim", "squeeze", "expand_dims",
    "convert_element_type", "slice", "pad", "rev", "copy",
    "add", "sub", "mul", "div", "rem", "neg", "sign", "abs", "max", "min",
    "floor", "ceil", "round", "exp", "log", "pow", "integer_pow", "clamp",
    "select_n", "eq", "ne", "ge", "gt", "le", "lt", "and", "or", "not",
    "xor", "shift_left", "shift_right_logical", "shift_right_arithmetic",
    "iota", "stop_gradient", "reduce_sum", "reduce_max", "reduce_min",
    "reduce_and", "reduce_or", "argmax", "argmin", "is_finite", "square",
    "sqrt", "rsqrt", "tanh", "logistic",
})


def _is_var(v) -> bool:
    return hasattr(v, "aval") and type(v).__name__ != "Literal"


def estimate_bytes(jaxpr, trip: float = 1.0) -> float:
    """Trip-count-aware materialized-bytes estimate over one Jaxpr level."""
    total = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        sub = getattr(sub, "jaxpr", sub)
        if name in CALL_LIKE and sub is not None:
            total += estimate_bytes(sub, trip)
            continue
        if name == "scan" and sub is not None:
            length = float(eqn.params.get("length", 1) or 1)
            total += estimate_bytes(sub, trip * length)
            continue
        if name == "while" and "body_jaxpr" in eqn.params:
            body = getattr(eqn.params["body_jaxpr"], "jaxpr",
                           eqn.params["body_jaxpr"])
            # unknown trip count: count one iteration (lower bound)
            total += estimate_bytes(body, trip)
            continue
        if name in _FUSED_AWAY:
            continue
        io_bytes = sum(
            aval_bytes(v.aval) for v in eqn.invars if _is_var(v)
        ) + sum(aval_bytes(v.aval) for v in eqn.outvars)
        total += trip * io_bytes
    return total


@ir_pass("I4", "traffic model: shape-derived bytes-moved estimate cross-"
              "checked against roofline.analysis.mpgemm_cost (finding when "
              "estimate exceeds the model by the configured factor)")
def check_traffic(entry: IREntry) -> Iterable[Finding]:
    meta = entry.meta
    if not all(k in meta for k in ("m_out", "k", "m_tokens")):
        return  # no analytic model for this entry's graph
    from repro.roofline.analysis import mpgemm_cost

    est = estimate_bytes(entry.jaxpr.jaxpr)
    _, model = mpgemm_cost(
        meta["m_out"], meta["k"], meta["m_tokens"], g=4,
        fused=bool(meta.get("fused", True)),
    )
    factor = float(meta.get("traffic_factor", DEFAULT_FACTOR))
    if model > 0 and est > factor * model:
        yield Finding(
            "I4", entry.path, 0, 0,
            f"traffic estimate {est / 1e6:.2f} MB exceeds {factor:g}x the "
            f"roofline model ({model / 1e6:.2f} MB) for "
            f"M={meta['m_tokens']}, K={meta['k']}, N={meta['m_out']} — the "
            f"graph materializes far more than the mpGeMM cost model "
            f"allows (estimate/model = {est / model:.1f}x)",
        )
