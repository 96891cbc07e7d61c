"""The readers of the program's own step spans and counters:
``engine.launch_gap_ms``, ``step_mfu.program`` and
``pager.prefix_hit_share`` on synthetic windows, and the span counts they
read against an outside view of the same steps."""
import pytest

import costs
import harness
import tiny

PEAKS = costs.peaks_for("TPU v5 lite")


def _run(spans):
    run = harness.Run(tiny.cell(), 7, 1.0, True)
    run.peaks, run.spans = PEAKS, list(spans)
    return run


def _outside_view(engine) -> tuple[str, int, float, int]:
    """What the next engine.step() will compute, read from the engine's
    state before the call: (kind, real tokens, keys attended summed over
    real tokens, rows whose logits are served)."""
    decoding = [len(r.prompt) + len(r.generated) - 1
                for s, r in engine.slot_req.items() if engine.active[s]]
    keys = float(sum(p + 1 for p in decoding))
    if engine.prefilling:
        chunk, real, rows = engine.prefill_chunk, len(decoding), len(decoding)
        for req in engine.prefilling.values():
            c = min(chunk, len(req.prompt) - req.prefill_pos)
            p0 = req.prefill_pos
            real += c
            keys += c * p0 + c * (c + 1) / 2
            rows += int(p0 + c == len(req.prompt))
        return "chunk", real, keys, rows
    return "decode", len(decoding), keys, len(decoding)


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
    """Each step's real-token ternary ops at the int8 peak, and its attention
    and served head rows at the bf16 peak, over the steps' summed time, with
    the tiny model's operations written out by hand."""
    work = [("chunk", 0.93, 700, 1.2e6, 12), ("chunk", 0.91, 650, 1.1e6, 14),
            ("decode", 0.05, 15, 3.0e4, 15)]
    spans = [_span(k, dt * 1e6, m_real=m, m_padded=4096, attn_keys=keys,
                   logit_rows=rows) for k, dt, m, keys, rows in work]
    # tiny: 2 layers of q, k, v, o (64x64, 32x64, 32x64, 64x64) and MLP
    # 3 x 64x128; 4 heads of 16; head 64 x 512
    mm = 2 * 2 * (2 * 64 * 64 + 2 * 32 * 64 + 3 * 64 * 128)
    at_peak = sum(m * mm / 393e12
                  + (4 * 4 * 16 * keys * 2 + rows * 2 * 64 * 512) / 197e12
                  for _, _, m, keys, rows in work)
    got = _read("step_mfu.program.offline", _run(spans))
    assert got == pytest.approx(100 * at_peak / (0.93 + 0.91 + 0.05), rel=1e-12)
    assert 0.0 < got < 100.0


def test_prefix_hit_share_reads_the_windows_counters():
    run = _run([])
    run.counts_open = {"prefill_tokens": 100, "prefix_hit_tokens": 5000,
                       "decode_tokens": 7}
    run.counts_close = {"prefill_tokens": 1100, "prefix_hit_tokens": 14000,
                        "decode_tokens": 300}
    assert _read("pager.prefix_hit_share.agent", run) == pytest.approx(90.0)
    run.counts_close = dict(run.counts_open, decode_tokens=400)
    assert _read("pager.prefix_hit_share.agent", run) is None


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
        engine.step = lambda: (outside.append(_outside_view(engine)),
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
