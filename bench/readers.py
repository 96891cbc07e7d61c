"""Arithmetic shared by the per-layer metric readers in ``metrics/``.

Each reader takes the finished ``harness.Run`` and returns a number, or
None where the run holds nothing to read (the harness then leaves the
metric out of the result line)."""
from __future__ import annotations

import costs


def _spans(run, kind: str):
    return [s for s in run.spans if s["name"] == f"engine_step/{kind}"]


def step_ms(run, kind: str) -> float | None:
    """Mean wall time of the program's ``engine_step`` spans in the window."""
    spans = _spans(run, kind)
    if not spans:
        return None
    return sum(s["dur"] for s in spans) / len(spans) / 1e3


def chunk_fill_pct(run) -> float | None:
    """Real tokens over the rows the chunk steps computed, in per cent."""
    spans = _spans(run, "chunk")
    padded = sum(s["args"]["m_padded"] for s in spans)
    if not padded:
        return None
    return 100.0 * sum(s["args"]["m_real"] for s in spans) / padded


def mpgemm_roofline_pct(run) -> float | None:
    """Least time at the chip's peaks of the mpGeMM calls the traced steps
    made, over the device time those calls took, in per cent."""
    calls = (run.trace_data or {}).get("mpgemm", [])
    if not calls:
        return None
    pk = run.peaks
    least = sum(costs.least_time(*costs.mpgemm_cost(**c["shape"]),
                                 pk["int8_ops"], pk["hbm_bytes_per_s"])
                for c in calls)
    return 100.0 * least / sum(c["seconds"] for c in calls)


def idle_share_pct(run) -> float | None:
    """Share of the traced window in which no operation ran on the device."""
    td = run.trace_data
    if not td or not td["window_s"]:
        return None
    return 100.0 * (1.0 - td["busy_s"] / td["window_s"])
