"""I1 — quantized-dtype flow: the LUT datapath stays in narrow int types.

T-MAC / LUT Tensor Core (PAPERS.md) and this repo's §3.3 fused kernel all
hinge on one invariant: values *derived from the packed ternary weights*
flow through {uint8, int8, int32} until the scale epilogue dequantizes
them. A graph that converts quantized values to float and then runs the
heavy math in float (a float dot_general over decoded trits) has silently
forfeited the paper's arithmetic — numerically identical, performance
class lost. That promotion is invisible to the AST but explicit in the
jaxpr.

Abstract interpretation: taint seeds are the uint8 leaves of the traced
inputs/consts (the packed trit-code segments). Taint propagates through
value-producing eqns, with two deliberate kills:

* the *dequant event* — a `mul` between a tainted float operand and an
  untainted float operand (the w_scale/a_scale epilogue): past the scale
  application the value is legitimately float;
* a `pallas_call` boundary — the kernel body has its own (AST R5 + test)
  coverage, and its outputs are post-epilogue by construction.

Index-like operands (gather/scatter indices, dynamic_slice starts) do not
propagate taint: using codes as LUT *indices* is the whole point.

Finding: a dot_general / conv whose floating-dtype operand is tainted —
quantized values were promoted to float BEFORE any scale was applied and
then fed the heavy op. (Integer dots over tainted int8/int32 operands are
the intended datapath and stay silent.)

Sub-jaxpr handling: jit/closed_call bodies are entered positionally;
scan/while bodies iterate taint to a fixpoint over the carry.
"""
from __future__ import annotations

from typing import Iterable

import jax.numpy as jnp

from ..core import Finding
from .core import CALL_LIKE, IREntry, ir_pass

_HEAVY = ("dot_general", "conv_general_dilated")

#: primitives whose trailing operands are indices, not values
_INDEX_OPERANDS = {
    "gather": 1,            # operands[1:] are indices
    "dynamic_slice": 1,     # operands[1:] are start indices
    "take_along_axis": 1,
    "argsort": 1,
}


def _is_float(aval) -> bool:
    dt = getattr(aval, "dtype", None)
    return dt is not None and jnp.issubdtype(dt, jnp.floating)


def _value_operands(eqn):
    """The invars that carry *values* (index operands stripped)."""
    name = eqn.primitive.name
    if name == "scatter" or name.startswith("scatter"):
        # (operand, indices, updates) — indices carry no value taint
        ops = list(eqn.invars)
        return [v for i, v in enumerate(ops) if i != 1]
    cut = _INDEX_OPERANDS.get(name)
    if cut is not None:
        return list(eqn.invars)[:cut]
    return list(eqn.invars)


def _sub_jaxpr(eqn):
    j = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
    return getattr(j, "jaxpr", j) if j is not None else None


def _analyze(jaxpr, in_taint, entry, findings, depth=0):
    """Propagate taint through one Jaxpr. -> per-outvar taint list."""
    taint: dict = {}

    def get(v):
        if not hasattr(v, "aval") or type(v).__name__ == "Literal":
            return False
        return taint.get(v, False)

    for var, t in zip(jaxpr.invars, in_taint):
        taint[var] = t
    for var in jaxpr.constvars:
        dt = getattr(var.aval, "dtype", None)
        taint[var] = dt is not None and dt == jnp.uint8
    if depth > 12:  # defensive: pathological nesting
        return [False] * len(jaxpr.outvars)

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            for ov in eqn.outvars:
                taint[ov] = False
            continue
        sub = _sub_jaxpr(eqn)
        if sub is not None and name in CALL_LIKE + (
                "custom_jvp_call", "custom_vjp_call", "remat", "checkpoint"):
            out_t = _analyze(sub, [get(v) for v in eqn.invars],
                             entry, findings, depth + 1)
            for ov, t in zip(eqn.outvars, out_t):
                taint[ov] = t
            continue
        if sub is not None and name in ("scan", "while"):
            # fixpoint over the carry: grow taint until stable
            in_t = [get(v) for v in eqn.invars]
            for _ in range(len(jaxpr.eqns) + 2):
                out_t = _analyze(sub, list(in_t[: len(sub.invars)]) + [False]
                                 * max(0, len(sub.invars) - len(in_t)),
                                 entry, findings, depth + 1)
                nc = int(eqn.params.get("num_consts", 0))
                grown = False
                # map body outputs back onto the carry slice of the inputs
                for i, t in enumerate(out_t):
                    j = nc + i
                    if j < len(in_t) and t and not in_t[j]:
                        in_t[j] = True
                        grown = True
                if not grown:
                    break
            for ov, t in zip(eqn.outvars, out_t):
                taint[ov] = t
            continue
        if name == "cond":
            branches = eqn.params.get("branches", ())
            outs = None
            for br in branches:
                bj = getattr(br, "jaxpr", br)
                t = _analyze(bj, [get(v) for v in eqn.invars[1:]],
                             entry, findings, depth + 1)
                outs = t if outs is None else [a or b
                                               for a, b in zip(outs, t)]
            for ov, t in zip(eqn.outvars, outs or []):
                taint[ov] = t
            continue

        vals = _value_operands(eqn)
        tainted_in = [v for v in vals if get(v)]
        if name in _HEAVY and any(
            get(v) and _is_float(v.aval) for v in vals
        ):
            off = next(v for v in vals if get(v) and _is_float(v.aval))
            findings.append(Finding(
                "I1", entry.path, 0, 0,
                f"{name} consumes a floating-dtype operand "
                f"({off.aval.dtype.name}{list(off.aval.shape)}) derived "
                f"from packed ternary weights with no scale applied — the "
                f"quantized datapath was promoted to float before the "
                f"dequant epilogue",
            ))
        out_tainted = bool(tainted_in)
        if out_tainted and name == "mul":
            # dequant kill: tainted float x untainted float scale
            a, b = (eqn.invars + [None, None])[:2]
            ta, tb = get(a), get(b)
            fa = a is not None and hasattr(a, "aval") and _is_float(a.aval)
            fb = b is not None and hasattr(b, "aval") and _is_float(b.aval)
            if fa and fb and (ta != tb):
                out_tainted = False
        for ov in eqn.outvars:
            taint[ov] = out_tainted
    return [get(v) for v in jaxpr.outvars]


@ir_pass("I1", "quantized-dtype flow: values derived from packed ternary "
              "weights stay integer until the scale epilogue; a float "
              "dot/conv over still-quantized values is a finding")
def check_dtype_flow(entry: IREntry) -> Iterable[Finding]:
    closed = entry.jaxpr
    jaxpr = closed.jaxpr
    seeds = [
        getattr(v.aval, "dtype", None) == jnp.uint8 for v in jaxpr.invars
    ]
    findings: list[Finding] = []
    _analyze(jaxpr, seeds, entry, findings)
    return findings
