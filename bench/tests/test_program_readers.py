"""The readers of the program's own step spans: ``engine.launch_gap_ms``
and ``step_mfu.program`` on synthetic windows, and the span counts they read
against the benchmark's outside view of the same steps."""
import pytest

import costs
import harness
import readers
import tiny

PEAKS = costs.peaks_for("TPU v5 lite")


def _run(spans, steps=()):
    run = harness.Run(tiny.cell(), 7, 1.0, True)
    run.peaks, run.spans, run.steps = PEAKS, list(spans), list(steps)
    return run


def _span(kind, dur_us, **args):
    return {"name": f"engine_step/{kind}", "ph": "X", "ts": 0.0,
            "dur": dur_us, "args": args}


def _read(name, run):
    return harness.load_reader(name)(run)


def test_launch_gap_is_the_median_over_chunk_steps():
    """Decode steps and a chunk step without a gap do not count; the one
    step whose gap held the profiler's stop does not move the median."""
    chunk = lambda **a: _span("chunk", 9e5, m_real=10, m_padded=64, **a)
    run = _run([chunk(),                                   # first: no gap
                chunk(launch_gap_us=4e4),
                _span("decode", 1e4, m_real=3, m_padded=4, launch_gap_us=1e6),
                chunk(launch_gap_us=6e4), chunk(launch_gap_us=5e4),
                chunk(launch_gap_us=2.3e6)])
    assert _read("engine.launch_gap_ms.offline", run) == pytest.approx(55.0)


def test_spans_without_the_counts_read_nothing():
    """A program whose step spans carry no such counts (an older one)
    gives a window the readers find nothing in."""
    run = _run([_span("chunk", 9e5, m_real=10, m_padded=64, prefills=1,
                      decodes=2)] * 3)
    assert _read("engine.launch_gap_ms.offline", run) is None
    assert _read("step_mfu.program.offline", run) is None
    assert _read("engine.launch_gap_ms.offline", _run([])) is None


def test_program_mfu_matches_the_benchmark_formula():
    """The same work and times read as spans and as the benchmark's step
    records give the same share."""
    work = [("chunk", 0.93, 700, 1.2e6, 12), ("chunk", 0.91, 650, 1.1e6, 14),
            ("decode", 0.05, 15, 3.0e4, 15)]
    spans = [_span(k, dt * 1e6, m_real=m, m_padded=4096, attn_keys=keys,
                   logit_rows=rows) for k, dt, m, keys, rows in work]
    records, t = [], 0.0
    for k, dt, m, keys, rows in work:
        records.append(harness.StepRecord(t, t + dt, k, m, keys, rows))
        t += dt + 0.05
    run = _run(spans, records)
    got = _read("step_mfu.program.offline", run)
    assert got == pytest.approx(readers.step_mfu_pct(run), rel=1e-12)
    assert 0.0 < got < 100.0


def test_span_counts_match_the_outside_view():
    """On the tiny engine serving a backlog, each step's span carries the
    work the benchmark reads from the engine's state before the step."""
    from repro import obs as obs_mod
    from repro.serve.engine import Request

    import numpy as np

    cell = tiny.cell()
    try:
        engine, sched = harness.build(cell, 11, trace=True, interpret=False)
        outside = []
        step = engine.step
        engine.step = lambda: (outside.append(harness._step_work(engine)),
                               step())
        rng = np.random.default_rng(3)
        sched.submit([Request(rid=i, prompt=rng.integers(0, 512, n).astype(
            np.int32), max_new_tokens=new) for i, (n, new) in enumerate(
                [(40, 3), (9, 5), (17, 2), (33, 6), (5, 4), (60, 2)])])
        while sched.queue or engine.has_work:
            sched.tick()
        inside = [(e["name"].split("/")[1], e["args"]["m_real"],
                   e["args"]["attn_keys"], e["args"]["logit_rows"])
                  for e in engine.obs.tracer.events
                  if e["name"].startswith("engine_step/")]
        assert len(inside) == len(outside) > 5
        assert inside == [(k, m, int(keys), rows)
                          for k, m, keys, rows in outside]
    finally:
        obs_mod.install(None)
