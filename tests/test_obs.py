"""Observability layer: metrics registry, tracer, null path, engine wiring."""
import json

import jax
import numpy as np
import pytest

from repro import obs as obs_mod
from repro.obs import NULL_OBS, Obs, ObsConfig
from repro.obs.metrics import (
    M_BUCKETS,
    TTFT_BUCKETS,
    Counter,
    Histogram,
    MetricsRegistry,
)
from repro.obs.trace import _NULL_SPAN, Tracer


@pytest.fixture(autouse=True)
def _detach_obs():
    """Tests that construct enabled Obs instances must not leak them into the
    module-global kernel hook (ops/autotune read obs_mod.current())."""
    yield
    obs_mod.install(None)


class TestHistogram:
    def test_bucket_assignment(self):
        h = Histogram("h", buckets=(1.0, 2.0, 5.0))
        for v in (0.5, 1.0, 1.5, 2.0, 4.0, 100.0):
            h.observe(v)
        # edges are upper bounds (bisect_left: v == edge lands in its bucket)
        assert h.counts == [2, 2, 1, 1]
        assert h.cumulative() == [2, 4, 5, 6]
        assert h.count == 6 and h.sum == pytest.approx(109.0)

    def test_percentile_interpolation(self):
        h = Histogram("h", buckets=(1.0, 2.0, 4.0))
        for v in np.linspace(0.05, 0.95, 10):   # all mass in [0, 1)
            h.observe(float(v))
        # uniform mass assumption → p50 is mid-bucket
        assert h.percentile(0.5) == pytest.approx(0.5)
        assert h.percentile(1.0) == pytest.approx(1.0)

    def test_percentile_tail_and_empty(self):
        h = Histogram("h", buckets=(1.0, 2.0))
        assert h.percentile(0.5) == 0.0          # no observations yet
        h.observe(50.0)                          # +Inf tail
        # the histogram cannot see past its last edge — report it, not a lie
        assert h.percentile(0.99) == 2.0
        with pytest.raises(ValueError):
            h.percentile(1.5)

    def test_ladders_sorted(self):
        for ladder in (TTFT_BUCKETS, M_BUCKETS):
            assert list(ladder) == sorted(ladder)


class TestCounter:
    def test_monotone(self):
        c = Counter("c")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_sync_to_never_decreases(self):
        c = Counter("c")
        c.sync_to(10)
        c.sync_to(7)    # stale snapshot must not roll the export back
        assert c.value == 10
        c.sync_to(12)
        assert c.value == 12


class TestRegistry:
    def test_get_or_create_keyed_on_labels(self):
        r = MetricsRegistry()
        a = r.counter("x", labels={"impl": "vlut"})
        b = r.counter("x", labels={"impl": "vlut"})
        c = r.counter("x", labels={"impl": "xla"})
        assert a is b and a is not c
        assert r.find("x", {"impl": "xla"}) is c
        assert r.find("nope") is None

    def test_kind_conflict_raises(self):
        r = MetricsRegistry()
        r.counter("x")
        with pytest.raises(TypeError):
            r.gauge("x")

    def test_prometheus_exposition(self):
        r = MetricsRegistry()
        r.counter("repro:req_total", "requests", {"kind": "a"}).inc(3)
        h = r.histogram("repro:lat_seconds", "latency", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)
        text = r.to_prometheus()
        assert "# TYPE repro:req_total counter" in text
        assert 'repro:req_total{kind="a"} 3' in text
        # cumulative bucket semantics + the implicit +Inf bucket
        assert 'repro:lat_seconds_bucket{le="0.1"} 1' in text
        assert 'repro:lat_seconds_bucket{le="1"} 2' in text
        assert 'repro:lat_seconds_bucket{le="+Inf"} 3' in text
        assert "repro:lat_seconds_count 3" in text

    def test_json_roundtrip(self):
        r = MetricsRegistry()
        r.gauge("g").set(2.0)
        s = r.series("s", capacity=2)
        for v in (1.0, 2.0, 3.0):
            s.record(v)
        blob = json.loads(json.dumps(r.to_json()))
        by_name = {m["name"]: m for m in blob["metrics"]}
        assert by_name["g"]["value"] == 2.0
        # ring keeps the newest `capacity` samples; lifetime count is total
        assert by_name["s"]["samples"] == [2.0, 3.0]
        assert by_name["s"]["count"] == 3 and by_name["s"]["mean"] == 2.0


class TestTracer:
    def test_span_records_complete_event(self):
        tr = Tracer()
        with tr.span("work", m=4) as sp:
            sp.args["extra"] = 1
        ev = tr.events[-1]
        assert ev["name"] == "work" and ev["ph"] == "X"
        assert ev["args"] == {"m": 4, "extra": 1}
        assert ev["dur"] >= 0.0 and ev["ts"] >= 0.0

    def test_ring_drops_oldest(self):
        tr = Tracer(capacity=3)
        for i in range(5):
            tr.instant(f"e{i}")
        assert [e["name"] for e in tr.events] == ["e2", "e3", "e4"]
        assert tr.dropped == 2
        assert tr.to_json()["otherData"]["dropped_events"] == 2

    def test_trace_event_json_shape(self, tmp_path):
        """The export must be the trace_event object format Perfetto loads."""
        tr = Tracer()
        with tr.span("a"):
            pass
        tr.complete("b", tr._t0, tr._t0 + 1e-3, args={"k": 1})
        path = tr.write(str(tmp_path / "trace.json"))
        blob = json.loads(open(path).read())
        assert blob["displayTimeUnit"] == "ms"
        for ev in blob["traceEvents"]:
            assert {"name", "ph", "ts", "pid", "tid"} <= set(ev)
            assert ev["ph"] in ("X", "i")
            if ev["ph"] == "X":
                assert ev["dur"] >= 0.0
        assert blob["traceEvents"][1]["dur"] == pytest.approx(1000.0)

    def test_disabled_records_nothing(self):
        tr = Tracer(enabled=False)
        assert tr.span("x") is _NULL_SPAN
        tr.complete("x", 0.0)
        tr.instant("x")
        assert not tr.events and tr.emitted == 0


class TestNullPath:
    """obs=None / enabled=False must be free: no metric objects, no events,
    shared singletons on every span path."""

    def test_null_obs_is_inert(self):
        assert not NULL_OBS.enabled
        assert NULL_OBS.span("x") is _NULL_SPAN
        assert NULL_OBS.mpgemm_span(1, 2, 3, "xla", "fused") is _NULL_SPAN
        NULL_OBS.step_event("decode", 0.0, m_real=1, m_padded=1)
        NULL_OBS.observe_ttft(1.0)
        NULL_OBS.on_tick(None, queue_depth=0, completed=0, rejected=0)
        assert NULL_OBS.step_span("chunk") is _NULL_SPAN
        assert not NULL_OBS.registry.all()
        assert not NULL_OBS.tracer.events
        assert NULL_OBS.stats_line() == "obs disabled"
        assert NULL_OBS.finalize() == []

    def test_null_span_args_discarded(self):
        sp = _NULL_SPAN
        with sp:
            sp.args["k"] = "v"      # legal, discarded
        assert sp.args == {}

    def test_install_ignores_disabled(self):
        obs_mod.install(NULL_OBS)
        assert obs_mod.current() is None
        live = Obs(ObsConfig())
        obs_mod.install(live)
        assert obs_mod.current() is live
        obs_mod.install(None)
        assert obs_mod.current() is None


class TestObsFacade:
    def test_step_event(self):
        o = Obs(ObsConfig())
        t0 = o.now()
        o.step_event("chunk", t0, m_real=24, m_padded=32, prefills=3)
        h = o.registry.find("repro:engine_step_seconds", {"kind": "chunk"})
        assert h.count == 1
        assert list(o.s_eff_m.samples) == [24.0]
        assert o.h_eff_m.count == 1
        ev = o.tracer.events[-1]
        assert ev["name"] == "engine_step/chunk"
        assert ev["args"] == {"m_real": 24, "m_padded": 32, "prefills": 3}

    def test_mpgemm_span(self):
        o = Obs(ObsConfig())
        with o.mpgemm_span(16, 2048, 512, impl="xla", fusion="fused"):
            pass
        c = o.registry.find(
            "repro:mpgemm_dispatch_total", {"impl": "xla", "fusion": "fused"})
        assert c.value == 1
        ev = o.tracer.events[-1]
        assert ev["name"] == "mpgemm_dispatch"
        assert (ev["args"]["m"], ev["args"]["k"], ev["args"]["n"]) == (
            16, 2048, 512)

    @pytest.mark.parametrize("fusion,loads,reuse", [
        ("fused", 2 * 3, 4),          # nn·nk: held across the nm row tiles
        ("unfused", 4 * 2 * 3, 1),    # nm·nn·nk: fetched for every row tile
    ])
    def test_mpgemm_dispatch_act_tiles(self, fusion, loads, reuse, tmp_path):
        """A traced decode-kernel call reports its activation schedule: at
        the heuristic tiles (bm 128, bn 256, bkg 128) a 400 x 1500 weight
        (300 K-groups at g=5) over 300 tokens runs nm 4, nn 2, nk 3."""
        import jax.numpy as jnp
        import numpy as np

        from repro.core import pack_weight, ternary_quantize
        from repro.kernels import autotune, ternary_matmul
        from repro.kernels import ops as kernel_ops

        w = np.random.default_rng(0).standard_normal((400, 1500))
        tw = ternary_quantize(jnp.asarray(w, jnp.float32))
        pw = pack_weight(tw.values, tw.scale, "i1")
        x = jax.ShapeDtypeStruct((300, 1500), jnp.bfloat16)
        autotune.reset_default_cache(str(tmp_path / "tiles.json"))
        o = Obs(ObsConfig())
        obs_mod.install(o)
        try:
            with kernel_ops.dispatch_override(
                    impl="decode", fusion=fusion, interpret=True):
                jax.eval_shape(lambda x: ternary_matmul(pw, x), x)
        finally:
            obs_mod.install(None)
            autotune.reset_default_cache()
        (ev,) = [e for e in o.tracer.events if e["name"] == "mpgemm_dispatch"]
        assert ev["args"]["act_tile_loads"] == loads
        assert ev["args"]["act_reuse"] == reuse

    def test_step_span(self):
        """The step span is open for the whole step: its ring event keeps
        the engine_step/{kind} name and the args set inside it, and its
        close feeds the step histogram and the effective-M series."""
        o = Obs(ObsConfig())
        with o.step_span("chunk") as sp:
            with o.span("engine.chunk.prepare") as inner:
                pass
            sp.args.update(m_real=24, m_padded=32, prefills=3)
        h = o.registry.find("repro:engine_step_seconds", {"kind": "chunk"})
        assert h.count == 1 and h.sum == pytest.approx(sp.t1 - sp.t0)
        assert list(o.s_eff_m.samples) == [24.0]
        step_ev, inner_ev = o.tracer.events[-1], o.tracer.events[-2]
        assert step_ev["name"] == "engine_step/chunk"
        assert step_ev["args"] == {"m_real": 24, "m_padded": 32, "prefills": 3}
        assert inner_ev["name"] == "engine.chunk.prepare"
        assert sp.t0 <= inner.t0 <= inner.t1 <= sp.t1
        assert inner.us == pytest.approx(inner_ev["dur"])

    def test_gc_span_while_installed(self):
        """An installed instance gets an engine.gc span around each Python
        collection; detaching unregisters the collector hook."""
        import gc

        o = Obs(ObsConfig())
        obs_mod.install(o)
        assert obs_mod._gc_span in gc.callbacks
        gc.collect()
        ev = [e for e in o.tracer.events if e["name"] == "engine.gc"]
        assert ev and ev[-1]["args"]["generation"] == 2
        assert "collected" in ev[-1]["args"]
        obs_mod.install(None)
        assert obs_mod._gc_span not in gc.callbacks
        n = len(o.tracer.events)
        gc.collect()
        assert len(o.tracer.events) == n

    def test_finalize_writes_exports(self, tmp_path):
        o = Obs(ObsConfig(
            metrics_out=str(tmp_path / "m.json"),
            trace_out=str(tmp_path / "t.json"),
        ))
        o.observe_ttft(0.02)
        with o.span("x"):
            pass
        paths = o.finalize()
        assert len(paths) == 2
        m = json.loads(open(paths[0]).read())
        names = {x["name"] for x in m["metrics"]}
        assert "repro:time_to_first_token_seconds" in names
        t = json.loads(open(paths[1]).read())
        assert t["traceEvents"][0]["name"] == "x"


@pytest.mark.slow
class TestEngineIntegration:
    @pytest.fixture(scope="class")
    def served(self):
        from repro.configs import get_config
        from repro.models import init_lm, pack_params

        cfg = get_config("smollm-360m", smoke=True)
        params = pack_params(init_lm(jax.random.PRNGKey(0), cfg), cfg)
        return cfg, params

    def _requests(self, cfg, n, rng, max_new=6):
        from repro.serve import Request

        return [
            Request(
                rid=i,
                prompt=rng.integers(0, cfg.vocab, size=rng.integers(4, 20))
                .astype(np.int32),
                max_new_tokens=max_new,
            )
            for i in range(n)
        ]

    def test_gauges_track_engine_tick_by_tick(self, served, rng):
        from repro.serve import ContinuousBatchingScheduler, Engine

        cfg, params = served
        eng = Engine(params, cfg, max_slots=2, max_len=64,
                     obs=ObsConfig(), prefill_chunk=4)
        try:
            sched = ContinuousBatchingScheduler(eng)
            sched.submit(self._requests(cfg, 5, rng))
            o = eng.obs
            while sched.queue or eng.has_work:
                sched.tick()
                # on_tick runs at the END of tick(): gauges must equal the
                # engine's live state right now, every tick
                assert o.g_waiting.value == len(sched.queue)
                assert o.g_running.value == int(eng.active.sum())
                assert o.g_prefilling.value == len(eng.prefilling)
                assert o.g_slots_free.value == sum(eng.slot_free)
                assert o.c_completed.value == len(sched.completed)
            assert o.g_slots_free.value == eng.max_slots
        finally:
            obs_mod.install(None)

    def test_latency_and_trace_surface(self, served, rng):
        from repro.serve import ContinuousBatchingScheduler, Engine

        cfg, params = served
        n_req, max_new = 6, 6
        eng = Engine(params, cfg, max_slots=3, max_len=64, obs=ObsConfig())
        try:
            sched = ContinuousBatchingScheduler(eng)
            sched.submit(self._requests(cfg, n_req, rng, max_new=max_new))
            stats = sched.run_to_completion()
            o = eng.obs
            assert stats.completed == n_req
            # one TTFT per completed request; one TPOT per request that
            # produced >= 2 tokens (all of them here)
            assert o.h_ttft.count == n_req
            assert o.h_tpot.count == n_req
            assert o.h_ttft.percentile(0.95) >= o.h_ttft.percentile(0.5) > 0
            # counters mirrored from the engine's source-of-truth attributes
            assert o.c_prompt_tok.value == eng.prefill_tokens
            assert o.c_gen_tok.value == eng.decode_tokens
            # every decode step recorded its real parallel-token count
            assert o.s_eff_m.count > 0
            assert all(1 <= m <= eng.max_slots for m in o.s_eff_m.samples)
            names = {e["name"] for e in o.tracer.events}
            assert "scheduler_tick" in names
            assert "engine_step/decode" in names
            # mpGeMM dispatch spans fire at trace time with shape+impl args
            mp = [e for e in o.tracer.events if e["name"] == "mpgemm_dispatch"]
            assert mp
            assert {"m", "k", "n", "impl", "fusion"} <= set(mp[0]["args"])
        finally:
            obs_mod.install(None)

    def test_disabled_engine_records_nothing(self, served, rng):
        from repro.serve import ContinuousBatchingScheduler, Engine

        cfg, params = served
        eng = Engine(params, cfg, max_slots=2, max_len=64)   # obs=None
        assert eng.obs is NULL_OBS
        sched = ContinuousBatchingScheduler(eng)
        sched.submit(self._requests(cfg, 3, rng, max_new=3))
        stats = sched.run_to_completion()
        assert stats.completed == 3
        assert not eng.obs.registry.all()
        assert not eng.obs.tracer.events
        assert obs_mod.current() is None


# --------------------------------------------------------------------------
# Phase spans inside the engine and scheduler
# --------------------------------------------------------------------------
#: the spans one tick of a chunked, paged, unspeculated engine can open
PHASES = (
    "scheduler.admit", "scheduler.finish", "engine.pager.admit",
    "engine.pager.flush", "engine.pager.release",
    "engine.sync.last_token", "engine.chunk.prepare", "engine.chunk.launch",
    "engine.sync.tokens", "engine.chunk.commit", "engine.rollback",
    "engine.decode.prepare", "engine.decode.launch", "engine.decode.commit",
)
STEPS = ("engine_step/chunk", "engine_step/decode")


@pytest.fixture(scope="module")
def tiny():
    """A two-layer model at toy widths: what the spans record does not
    depend on the model's size."""
    from repro.configs.base import ModelConfig, uniform_layers
    from repro.models import init_lm, pack_params

    cfg = ModelConfig(name="tiny", n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=2, head_dim=16, d_ff=128, vocab=256,
                      layers=uniform_layers(2))
    return cfg, pack_params(init_lm(jax.random.PRNGKey(0), cfg), cfg)


def _engine(tiny, obs):
    from repro.serve import ContinuousBatchingScheduler, Engine
    from repro.serve.paging import PagedKVConfig

    cfg, params = tiny
    eng = Engine(params, cfg, max_slots=3, max_len=32, prefill_chunk=4,
                 paged_kv=PagedKVConfig(page_size=4), obs=obs)
    return eng, ContinuousBatchingScheduler(eng)


def _submit(sched, specs, rng):
    from repro.serve import Request

    reqs = [Request(rid=i, prompt=rng.integers(0, 256, n).astype(np.int32),
                    max_new_tokens=new) for i, (n, new) in enumerate(specs)]
    sched.submit(reqs)
    return reqs


def _drain(sched):
    while sched.queue or sched.engine.has_work:
        sched.tick()


class TestProgramSpans:
    def test_phase_spans_land_in_profiler_trace(self, tiny, rng, tmp_path):
        """Served under jax.profiler: every phase span is on the host plane
        of the .xplane.pb, inside its step (engine phases) or its tick
        (scheduler phases), as many times as the ring recorded it."""
        from jax.profiler import ProfileData

        eng, sched = _engine(tiny, ObsConfig())
        _submit(sched, [(6, 3), (3, 2), (9, 4)], rng)
        _drain(sched)                        # compiles outside the trace
        base = len(eng.obs.tracer.events)
        jax.profiler.start_trace(str(tmp_path))
        try:
            _submit(sched, [(6, 3), (3, 2), (9, 4), (5, 5)], rng)
            _drain(sched)
        finally:
            jax.profiler.stop_trace()
        ring = list(eng.obs.tracer.events)[base:]
        host = []
        (path,) = tmp_path.glob("**/*.xplane.pb")
        for plane in ProfileData.from_file(str(path)).planes:
            if plane.name.startswith("/host:CPU"):
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for line in plane.lines for e in line.events]
        count = lambda evs, name: sum(e[0] == name for e in evs)
        names = [e["name"] for e in ring]
        for name in PHASES + STEPS + ("scheduler_tick",):
            assert names.count(name) > 0, name
            assert count(host, name) == names.count(name), name

        def inside(ev, outer_names):
            return any(o[0] in outer_names and o[1] <= ev[1] and ev[2] <= o[2]
                       for o in host)

        for ev in host:
            if ev[0].startswith(("engine.sync", "engine.chunk",
                                 "engine.decode", "engine.rollback")):
                assert inside(ev, STEPS), ev
            elif ev[0] in STEPS + ("scheduler.admit", "scheduler.finish",
                                   "engine.pager.admit", "engine.pager.flush",
                                   "engine.pager.release"):
                assert inside(ev, ("scheduler_tick",)), ev

    def test_obs_off_opens_no_span(self, tiny, rng, monkeypatch):
        """With obs off nothing enters a profiler annotation (it would
        raise here) and the tracer records nothing."""
        import gc

        class Refused:
            def __init__(self, *a, **k):
                raise AssertionError("annotation opened with obs off")

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Refused)
        eng, sched = _engine(tiny, None)
        assert eng.obs is NULL_OBS
        reqs = _submit(sched, [(6, 3), (3, 2), (9, 4)], rng)
        _drain(sched)
        assert all(r.done for r in reqs)
        assert eng.last_sync_t is None
        assert not NULL_OBS.tracer.events and not NULL_OBS.registry.all()
        assert obs_mod._gc_span not in gc.callbacks

    def test_step_args_on_a_scripted_sequence(self, tiny, rng):
        """Slots 3, chunk 4. A: 6-token prompt, 3 new; B: 3-token prompt,
        2 new. Tick 1 chunks A[0:4] and all of B (B's first token); tick 2
        chunks A[4:6] with B's decode row riding (B done, A's first
        token); ticks 3 and 4 decode A alone."""
        eng, sched = _engine(tiny, ObsConfig())
        _submit(sched, [(6, 3), (3, 2)], rng)
        _drain(sched)
        steps = [e for e in eng.obs.tracer.events
                 if e["name"].startswith("engine_step/")]
        keys = ("m_real", "prefills", "decodes", "free_slots", "attn_keys",
                "logit_rows")
        got = [(e["name"],) + tuple(e["args"].get(k) for k in keys)
               for e in steps]
        # attn_keys: a token at position p attends p + 1 keys
        assert got == [
            ("engine_step/chunk", 7, 2, 0, 1, (1 + 2 + 3 + 4) + (1 + 2 + 3), 1),
            ("engine_step/chunk", 3, 1, 1, 1, (5 + 6) + 4, 2),
            ("engine_step/decode", 1, None, None, 2, 7, 1),
            ("engine_step/decode", 1, None, None, 2, 8, 1),
        ]
        assert "launch_gap_us" not in steps[0]["args"]
        for e in steps:
            a = e["args"]
            phases = [a["prepare_us"], a["sync_us"], a["commit_us"],
                      a["rollback_us"]]
            assert min(phases) >= 0.0
            assert sum(phases) <= e["dur"]
        for e in steps[1:]:
            assert e["args"]["launch_gap_us"] > 0.0
        assert steps[0]["args"]["rollback_us"] > 0.0      # chunk tail
        assert steps[2]["args"]["rollback_us"] == 0.0     # nothing prefills
        # the drained engine has no work, so the mark is cleared
        assert eng.last_sync_t is None
        # the phases' own ring events match the args
        prep = [e["dur"] for e in eng.obs.tracer.events
                if e["name"] in ("engine.chunk.prepare",
                                 "engine.decode.prepare")]
        assert prep == pytest.approx([e["args"]["prepare_us"] for e in steps])
