"""A whole run of a cell, minus the look for a chip, with the timed path
broken underneath: ``correct`` has to come out false for every fault a
serving cell can have, and true for the sound path."""
import time

import jax.numpy as jnp
import pytest

import costs
import harness
import tiny

SEED = 2**31 + 5


def _run(mix=None):
    cell = tiny.cell(mix)
    out = harness.run_cell(cell, SEED, 1.0, False, t_process=time.perf_counter(),
                           require_chip=False,
                           peaks=costs.peaks_for("TPU v5 lite"))
    assert out["attempted"] > 10 and out["failed"] == 0
    return out


def _wrap_build(monkeypatch, wrap):
    build = harness.build

    def patched(*a, **k):
        engine, sched = build(*a, **k)
        wrap(engine)
        return engine, sched

    monkeypatch.setattr(harness, "build", patched)


@pytest.mark.parametrize("mix, metric", [(tiny.OFFLINE, "tok_s"),
                                          (tiny.CHAT, "ttft_p95_ms")])
def test_sound_path_is_correct(mix, metric):
    out = _run(mix)
    assert out["correct"] is True
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {metric, "setup_s"}
    assert out["checks"]["checked_requests"]["value"] >= tiny.CHECK["min_requests"]


def test_token_altered_where_produced(monkeypatch):
    from repro.serve.engine import Engine

    sample = Engine._sample
    monkeypatch.setattr(Engine, "_sample", lambda self, logits: (
        sample(self, logits) + 1) % logits.shape[-1])
    out = _run()
    assert out["correct"] is False
    assert out["checks"]["widest_gap"]["value"] > out["checks"]["widest_gap"]["limit"]


def test_half_of_the_batch_left_out(monkeypatch):
    def wrap(engine):
        step = engine._chunk_verify

        def half(p, c, tokens, col):
            keep = (jnp.arange(tokens.shape[0]) % 2 == 0)[:, None]
            return step(p, c, jnp.where(keep, tokens, 0), col)

        engine._chunk_verify = half

    _wrap_build(monkeypatch, wrap)
    assert _run()["correct"] is False


def test_cache_state_left_behind(monkeypatch):
    import repro.serve.engine as eng

    rollback = eng.rollback_cache
    monkeypatch.setattr(eng, "rollback_cache",
                        lambda cache, idx: rollback(cache, jnp.maximum(idx - 1, 0)))
    assert _run()["correct"] is False


def test_too_few_requests_to_check(monkeypatch):
    cell = tiny.cell()
    cell.check["min_requests"] = 10_000
    monkeypatch.setattr(tiny, "cell", lambda mix=None: cell)
    out = _run()
    assert out["correct"] is False
    assert out["checks"]["checked_requests"]["value"] < 10_000
