"""Ring-buffered span tracer emitting Chrome/Perfetto ``trace_event`` JSON.

Spans wrap the serving stack's host-side control flow — scheduler tick →
chunk_step / decode_step / verify → mpGeMM dispatch — with free-form ``args``
(the mpGeMM spans carry (M, N, K, impl, fusion, tile), so a slow tick is
attributable to the kernel shape it compiled/launched). Events land in a
bounded deque (oldest dropped, drop count kept), so an always-on tracer in a
long serve can never grow without bound.

Every span also enters a ``jax.profiler.TraceAnnotation`` of its own name,
so while a profiler trace runs (``jax.profiler.start_trace``) the same span
lands on the host plane of the ``.xplane.pb``, on the device trace's clock:
host phases and device operations line up in one Perfetto view. Outside a
profiler trace an annotation is host bookkeeping only; no span touches the
device.

Ring timestamps come from ``time.perf_counter()`` rebased to the tracer's
start, in microseconds (the trace_event unit). Output is the JSON *object*
format (``{"traceEvents": [...]}``) which both ``chrome://tracing`` and
https://ui.perfetto.dev load directly.

One semantic caveat, documented rather than hidden: the engine's compute runs
inside jit-compiled steps, so per-kernel spans cannot be recorded at
execution time from python. The mpGeMM spans are therefore **trace-time**
events — they fire when a step traces/compiles for a new shape and their
duration is the host-side dispatch (tracing) cost — while the per-tick step
spans carry the measured wall time of every execution; the device trace
holds the kernels themselves.
"""
from __future__ import annotations

import json
import time
from collections import deque
from typing import Callable

import jax


class _Span:
    """Mutable in-flight span; ``args`` may be extended before exit. After
    exit ``t0``/``t1`` (perf_counter seconds) bound it; ``done``, if given,
    is called with the closed span."""

    __slots__ = ("tracer", "name", "args", "done", "t0", "t1", "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: dict,
                 done: Callable[["_Span"], None] | None = None):
        self.tracer = tracer
        self.name = name
        self.args = args
        self.done = done
        self.t0 = self.t1 = 0.0
        self._ann = None

    def __enter__(self):
        if self.tracer.enabled:
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self.tracer.complete(self.name, self.t0, self.t1, args=self.args)
        if self.done is not None:
            self.done(self)
        return False

    @property
    def us(self) -> float:
        """Duration of the closed span in microseconds."""
        return (self.t1 - self.t0) * 1e6


class _NullSpan:
    """Shared no-op span: zero allocation on the disabled path. Its ``args``
    is a throwaway dict so `sp.args[...] = v` stays legal (and discarded)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    @property
    def args(self) -> dict:
        return {}


_NULL_SPAN = _NullSpan()


class Tracer:
    def __init__(self, capacity: int = 65536, enabled: bool = True,
                 pid: int = 0, tid: int = 0):
        self.enabled = enabled
        self.capacity = capacity
        self.pid = pid
        self.tid = tid
        self.events: deque[dict] = deque(maxlen=capacity)
        self.emitted = 0            # lifetime count (dropped = emitted - len)
        self._t0 = time.perf_counter()

    # -- recording -------------------------------------------------------
    def _ts(self, t: float) -> float:
        return (t - self._t0) * 1e6

    def span(self, name: str, **args):
        """Context manager recording a complete ('X') event on exit, and a
        profiler annotation of the same name while it is open."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, args)

    def complete(self, name: str, t0: float, t1: float | None = None,
                 args: dict | None = None) -> None:
        """Record a complete event for work measured externally
        ([t0, t1 or now] in perf_counter seconds)."""
        if not self.enabled:
            return
        t1 = time.perf_counter() if t1 is None else t1
        self.emitted += 1
        self.events.append({
            "name": name, "ph": "X", "pid": self.pid, "tid": self.tid,
            "ts": self._ts(t0), "dur": max((t1 - t0) * 1e6, 0.0),
            "args": dict(args or {}),
        })

    def instant(self, name: str, **args) -> None:
        if not self.enabled:
            return
        self.emitted += 1
        self.events.append({
            "name": name, "ph": "i", "s": "t", "pid": self.pid,
            "tid": self.tid, "ts": self._ts(time.perf_counter()),
            "args": args,
        })

    @property
    def dropped(self) -> int:
        return self.emitted - len(self.events)

    # -- export ----------------------------------------------------------
    def to_json(self) -> dict:
        return {
            "traceEvents": list(self.events),
            "displayTimeUnit": "ms",
            "otherData": {"dropped_events": self.dropped},
        }

    def write(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_json(), f)
        return path
