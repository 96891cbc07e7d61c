"""repro.lint.ir core: IR entry model, pass registry, jaxpr walking.

The IR suite is the AST linter's complement: instead of parsing source it
*traces* a registry of hot-path entry points (kernels/ops.py mpGeMM impls,
`Engine.jit_entries()`, `ModelDrafter.jit_entries()`) to ClosedJaxprs with
`jax.make_jaxpr` — no compilation, no execution — and runs pluggable passes
over the equations. What the AST cannot see (a quantized value silently
promoted to f32 mid-graph, a dead intermediate surviving a fused-epilogue
refactor, a host callback smuggled into a decode step, a graph whose traffic
outgrew the roofline model, or *any* structural change to a serving graph)
is exactly what these passes check. Pass catalog: docs/static_analysis.md.

Findings reuse `lint.core.Finding` and the same exit-code contract
(0 clean / 1 findings / 2 usage). Source-comment suppressions make no sense
for traced IR, so the suppression contract moves to the registry: an entry
declares ``suppress={"I4": "<justification, ≥3 words>"}``; an
under-justified suppression is itself a finding (I0, unsuppressable) —
mirroring the AST side's R0.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterable, Iterator

from ..core import MIN_JUSTIFICATION_WORDS, Finding

#: pass id -> (one-line description, check callable)
_PASSES: dict[
    str, tuple[str, Callable[["IREntry"], Iterable[Finding]]]
] = {}


def ir_pass(pass_id: str, description: str):
    """Decorator registering ``check(entry) -> Iterable[Finding]``."""

    def deco(fn):
        _PASSES[pass_id] = (description, fn)
        fn.pass_id = pass_id
        fn.description = description
        return fn

    return deco


def registered_passes() -> dict[str, str]:
    return {pid: desc for pid, (desc, _) in sorted(_PASSES.items())}


@dataclasses.dataclass
class IREntry:
    """One traced entry point: a name, its ClosedJaxpr, and pass metadata.

    name      stable identifier ("mpgemm/vlut/M16", "engine/chunk_verify");
              doubles as the snapshot filename (with '/' -> '__').
    jaxpr     the ClosedJaxpr from jax.make_jaxpr.
    kind      "mpgemm" | "engine" | "drafter" — passes gate on it.
    meta      pass inputs: mpgemm entries carry m_out/k/m_tokens/g/fused for
              the I4 roofline cross-check and traffic_factor overrides.
    suppress  pass id -> justification (≥3 words); suppressed passes are
              skipped for this entry, bad justifications are I0 findings.
    """

    name: str
    jaxpr: Any
    kind: str = "mpgemm"
    meta: dict = dataclasses.field(default_factory=dict)
    suppress: dict = dataclasses.field(default_factory=dict)

    @property
    def path(self) -> str:
        """Pseudo-path used in Finding rows (there is no source file)."""
        return f"<jaxpr:{self.name}>"


# --------------------------------------------------------------------------
# jaxpr walking
# --------------------------------------------------------------------------
#: call primitives whose body is entered positionally (a nested `jax.jit`
#: traces as the `jit` primitive)
CALL_LIKE = ("jit", "closed_call", "core_call")


def subjaxprs(eqn) -> list:
    """The Jaxprs nested in one eqn's params (jit/scan/while/cond bodies).
    pallas_call kernels are deliberately EXCLUDED: their jaxpr has Mosaic
    ref/memory semantics the passes do not model — the AST R5 rules and the
    kernel tests own that boundary."""
    if eqn.primitive.name == "pallas_call":
        return []
    out = []
    for v in eqn.params.values():
        vals = v if isinstance(v, (list, tuple)) else (v,)
        for item in vals:
            j = getattr(item, "jaxpr", item)  # ClosedJaxpr -> Jaxpr
            if hasattr(j, "eqns") and hasattr(j, "invars"):
                out.append(j)
    return out


def all_eqns(jaxpr) -> Iterator[tuple[Any, int]]:
    """Depth-first (eqn, depth) over a Jaxpr and its nested call bodies."""

    def walk(j, depth):
        for eqn in j.eqns:
            yield eqn, depth
            for sub in subjaxprs(eqn):
                yield from walk(sub, depth + 1)

    yield from walk(jaxpr, 0)


def aval_bytes(aval) -> int:
    """Nominal byte size of an abstract value (0 for non-array avals)."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        try:
            n *= int(d)
        except TypeError:  # symbolic dim — don't guess
            return 0
    return n * dtype.itemsize


def fmt_aval(aval) -> str:
    dtype = getattr(aval, "dtype", None)
    shape = getattr(aval, "shape", None)
    if dtype is None:
        return str(aval)
    return f"{dtype.name}[{','.join(str(d) for d in (shape or ()))}]"


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------
def run_passes(
    entries: Iterable[IREntry],
    select: set[str] | None = None,
    **pass_kwargs,
) -> list[Finding]:
    """Run every registered pass over every entry -> sorted findings.

    Extra keyword args are forwarded to passes that accept them (the
    snapshot pass takes ``snapshot_dir``/``update_snapshots``); passes that
    do not declare the kwarg are called with the entry alone.
    """
    findings: list[Finding] = []
    for entry in entries:
        # registry-level suppression contract (I0 mirrors the AST R0)
        active_suppress: set[str] = set()
        for pid, justification in sorted(entry.suppress.items()):
            if len(str(justification).split()) < MIN_JUSTIFICATION_WORDS:
                findings.append(Finding(
                    "I0", entry.path, 0, 0,
                    f"suppression of {pid} lacks a justification "
                    f"(≥{MIN_JUSTIFICATION_WORDS} words)",
                ))
            else:
                active_suppress.add(pid)
        for pid, (_desc, check) in sorted(_PASSES.items()):
            if select is not None and pid not in select:
                continue
            if pid in active_suppress:
                continue
            kw = {
                k: v for k, v in pass_kwargs.items()
                if k in check.__code__.co_varnames[: check.__code__.co_argcount]
            }
            findings.extend(check(entry, **kw))
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings
