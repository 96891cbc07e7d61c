"""A window of requests with shared prefixes on the tiny engine: the pager
serves prompt tokens from shared pages, and ``tok_s`` counts them as
served, with their request's prefill."""
import types

import numpy as np
import pytest

import harness
import tiny


def test_prefix_hits_count_as_tokens_served():
    cell = tiny.cell(tiny.AGENT)
    counter = harness.CompileCounter()
    engine, sched = harness.set_up(cell, 2**31 + 21, trace=False)
    # set-up primed both prefixes into the pager's index
    assert engine.pager.shared_pages >= 2 * (32 // 16)
    run = harness.Run(cell, 2**31 + 21, 1.0, False)
    harness.serve_window(run, engine, sched, counter)
    delta = {k: run.counts_close[k] - run.counts_open[k]
             for k in run.counts_open}
    assert delta["prefix_hit_tokens"] > 0 and delta["decode_tokens"] > 0
    assert run.tokens_in_window == sum(delta.values())
    values, attempted, failed = harness.end_to_end(run, 0.0)
    assert values["tok_s"] == pytest.approx(
        sum(delta.values()) / (run.t_close - run.t_open))
    share = harness.load_reader("pager.prefix_hit_share.agent")(run)
    assert share == pytest.approx(100 * delta["prefix_hit_tokens"] / (
        delta["prefix_hit_tokens"] + delta["prefill_tokens"]))
    assert 50.0 < share < 100.0
    assert attempted > 0 and failed == 0


def test_shared_tokens_are_served_with_their_prefill():
    """A request that matched 4000 of its 10000 prompt tokens and has
    prefilled 1500 of the other 6000 has been served a quarter of its 4000
    shared tokens; a finished prefill, all of them."""
    req = types.SimpleNamespace(prompt=np.zeros(10000, np.int32),
                                prefill_pos=4000 + 1500)
    pager = types.SimpleNamespace(slot_shared=[0, 250, 0], page_size=16)
    engine = types.SimpleNamespace(
        pager=pager, prefilling={1: req}, prefill_tokens=1500,
        prefix_hit_tokens=4000 + 3000, decode_tokens=9)
    assert harness.token_counts(engine) == {
        "prefill_tokens": 1500, "prefix_hit_tokens": 3000 + 1000,
        "decode_tokens": 9}
    req.prefill_pos = 10000
    engine.prefilling = {}
    assert harness.token_counts(engine)["prefix_hit_tokens"] == 7000


def test_a_request_waiting_for_pages_has_not_failed():
    """The queue's head waiting for free pages has ``error`` set until it
    is admitted; only a request the scheduler refused has failed."""
    cell = tiny.cell(tiny.AGENT)
    run = harness.Run(cell, 1, 1.0, False)
    run.t_open, run.t_close, run.tokens_in_window = 10.0, 11.0, 500
    req = lambda **k: types.SimpleNamespace(**{
        "done": False, "error": "", "t_done": 0.0, **k})
    done = req(done=True, t_done=10.5)
    waiting = req(error="queued: waiting for free KV pages (...)")
    refused = req(error="request 7: prompt (300) + ... exceeds the model context")
    run.tracked = [harness.Tracked(r, 0.0, True) for r in (done, waiting, refused)]
    run.rejected = {id(refused)}
    values, attempted, failed = harness.end_to_end(run, 3.0)
    assert (attempted, failed) == (2, 1)
    assert values == {"setup_s": 3.0, "tok_s": 500.0}
