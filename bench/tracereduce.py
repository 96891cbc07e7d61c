"""Reduction of a profiler trace to the numbers the benchmark reports.

Input: the ``.xplane.pb`` JAX's profiler writes, or the same events saved as
JSON by :func:`save_events` (the small recorded trace the tests read).
Output (:func:`reduce_events`):

* ``window_s``: length of the host span ``bench.trace_window``, which the
  harness opens and closes at tick boundaries;
* ``busy_s``: union of the device operations' intervals inside the window,
  averaged over the chips;
* ``mpgemm``: every ternary mpGeMM kernel call in the window, with its
  device seconds and its operand shapes (for the roofline);
* ``breakdown``: the ten device operations that took most time, grouped by
  name, and the idle time between device operations grouped by the
  innermost host span that was open when the gap began.
"""
from __future__ import annotations

import gzip
import json
import re
from pathlib import Path

WINDOW = "bench.trace_window"
#: prefixes of the host spans the harness opens around each layer's calls
HOST_SPANS = ("bench.", "scheduler.", "engine.")
#: the Pallas kernels that compute a ternary mpGeMM, by kernel name
MPGEMM_KERNELS = ("ternary_decode_gemm_fused", "ternary_decode_gemm",
                  "vlut_lookup_gemm_fused", "vlut_lookup_gemm")
_SHAPE = re.compile(r"(u8|s8|bf16|f32|f16|s32)\[([0-9,]*)\]")
_BYTES = {"u8": 1, "s8": 1, "bf16": 2, "f16": 2, "f32": 4, "s32": 4}


def _events_from_xplane(path: Path) -> dict:
    """Host spans of every host thread, and the "XLA Ops" line of each TPU
    core. A TPU op event's name is its HLO instruction text."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    host, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPANS):
                        host.append([e.name, e.start_ns, e.duration_ns])
        elif plane.name.startswith("/device:TPU:"):
            devices[plane.name] = [
                [e.name, e.start_ns, e.duration_ns]
                for line in plane.lines if line.name == "XLA Ops"
                for e in line.events
            ]
    return {"host": host, "devices": [devices[k] for k in sorted(devices)]}


def load_events(trace_dir: Path) -> dict:
    """Host spans and device operations of the newest trace in trace_dir."""
    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return _events_from_xplane(files[-1])


def save_events(events: dict, path: Path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def read_saved(path: Path) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(events, lo, hi):
    for ev in events:
        s, e = max(ev[1], lo), min(ev[1] + ev[2], hi)
        if e > s:
            yield ev, s, e


_INSTR = re.compile(r"%([A-Za-z_\-]+)[.0-9]* = (\([^=]*?\)|\S+) ")
#: ops that enclose others on the same line (a scan's loop, a call): their
#: time is their children's, so they count neither as busy nor in the table
_ENCLOSING = ("while", "conditional", "call")


def _opname(hlo: str) -> tuple[str, str]:
    """(instruction name without its numeric suffix, result type without
    layouts) of one device op's HLO text."""
    m = _INSTR.match(hlo)
    if not m:
        return hlo.split(" ", 1)[0].lstrip("%"), ""
    return m.group(1), re.sub(r"\{[^}]*\}", "", m.group(2))


def _label(hlo: str) -> str:
    """A stable label for a device op: its instruction name (for a Pallas
    kernel, the kernel's name) and its result type."""
    name, result = _opname(hlo)
    return f"{name} {result}".strip()


def mpgemm_shape(hlo: str) -> dict | None:
    """Operand shapes of a fused decode/lookup kernel call, from its HLO
    text: packed weight (m, kg) u8 and activations (kg, g, n)."""
    hlo = re.sub(r"\{[^}]*\}", "", hlo)
    if "custom-call(" not in hlo:
        return None
    result, operands = hlo.split("custom-call(", 1)
    operands = operands.split(")", 1)[0]
    dims = lambda part: [(t, [int(x) for x in d.split(",") if x])
                         for t, d in _SHAPE.findall(part)]
    ops = dims(operands)
    packed = next((d for t, d in ops if t == "u8" and len(d) == 2), None)
    act = next(((t, d) for t, d in ops if len(d) == 3), None)
    out = next(iter(dims(result)), None)
    if packed is None or act is None or out is None:
        return None
    m, kg = packed
    kg2, g, n = act[1]
    if kg2 != kg:
        return None
    return {"m": m, "kg": kg, "g": g, "n": n, "act_bytes": _BYTES[act[0]],
            "out_bytes": _BYTES[out[0]]}


def reduce_events(ev: dict, chips: int = 1) -> dict:
    wins = [h for h in ev["host"] if h[0] == WINDOW]
    if not wins:
        raise ValueError(f"no host span {WINDOW!r} in the trace")
    lo, hi = wins[0][1], wins[0][1] + wins[0][2]
    devices = [[op for op in ops if _opname(op[0])[0] not in _ENCLOSING]
               for ops in ev["devices"][:chips]]
    if not devices or not any(devices):
        raise ValueError("no device operations in the trace")
    busy = []
    for ops in devices:
        busy.append(sum(e - s for s, e in _union(
            [(s, e) for _, s, e in _clip(ops, lo, hi)])))
    by_label: dict[str, float] = {}
    mpgemm = []
    for op, s, e in _clip(devices[0], lo, hi):
        label = _label(op[0])
        by_label[label] = by_label.get(label, 0.0) + (e - s) / 1e9
        if _opname(op[0])[0] in MPGEMM_KERNELS and e - s == op[2]:
            shape = mpgemm_shape(op[0])
            if shape is not None:
                mpgemm.append({"seconds": op[2] / 1e9, "shape": shape})
    # idle gaps on the first chip, each charged to the innermost benchmark
    # host span open at its start (spans of one thread nest, so a stack
    # swept along the gaps finds it)
    merged = _union([(s, e) for _, s, e in _clip(devices[0], lo, hi)])
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    host = sorted((h for h in ev["host"]
                   if h[0] != WINDOW and h[0].startswith(HOST_SPANS)),
                  key=lambda h: (h[1], -h[2]))
    gaps: dict[str, float] = {}
    stack: list = []
    i = 0
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        while i < len(host) and host[i][1] <= a:
            stack.append(host[i])
            i += 1
        while stack and stack[-1][1] + stack[-1][2] <= a:
            stack.pop()
        who = stack[-1][0] if stack else "no host span"
        gaps[who] = gaps.get(who, 0.0) + (b - a) / 1e9
    top = lambda d: [[k, v] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "mpgemm": mpgemm,
        "breakdown": {"device_ops": top(by_label), "idle_gaps": top(gaps)},
    }


def reduce_trace(trace_dir: Path, chips: int = 1) -> dict:
    return reduce_events(load_events(trace_dir), chips)
