"""Runs one cell of the benchmark once: set-up, the measured window, the
comparison with the plain reference, and with ``trace`` the per-layer
readings.

A cell is found by its name in ``BENCHMARK.json``; everything that belongs
to it comes from files named after its parts:

* ``bench/configs/<config>.json``  the model's sizes, its ``kind`` (the
  module under ``bench/kinds/`` that builds the program's ModelConfig and
  counts its operations) and the reference module under
  ``bench/references/`` that computes it plainly;
* ``bench/traffic/<traffic>.json``  the mix's parameters (``traffic.py``);
* ``bench/cells/<workload>.json``   the engine's shape and page pool for
  this cell, the mix's per-cell overrides and the limits of the
  correctness check;
* ``bench/metrics/<metric>.py``     one reader per per-layer metric.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
import types
from pathlib import Path

import numpy as np

import costs

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: JAX's persistent compile cache: one fixed directory inside the checkout
COMPILE_CACHE = BENCH / ".cache" / "jax"
TRACE_DIR = BENCH / ".cache" / "trace"
#: the autotuner's tile cache, committed, so every machine runs the same tiles
TILES = BENCH / "tiles.json"


@dataclasses.dataclass
class Cell:
    name: str
    config: dict          # bench/configs/<config>.json
    kind: types.ModuleType  # bench/kinds/<config's kind>.py, loaded
    mix: dict             # traffic file merged with the cell's overrides
    engine: dict          # max_slots, max_len, prefill_chunk, page_size,
                          # optionally n_pages
    check: dict           # correctness sample size and limits
    chips: int = 1
    end_to_end: list = dataclasses.field(default_factory=list)
    per_layer: list = dataclasses.field(default_factory=list)
    trace_seconds: float = 2.0


def _read(path: Path) -> dict:
    return json.loads(path.read_text())


def _applies(metric: dict, workload: str, reported: set) -> bool:
    """A per-layer metric belongs to the cells it lists, or else to every
    cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric["moves"] in reported


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = _read(root / "BENCHMARK.json")
    wl = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cdir = root / "bench"
    config = _read(cdir / "configs" / f"{wl['config']}.json")
    cell = _read(cdir / "cells" / f"{workload}.json")
    mix = _read(cdir / "traffic" / f"{wl['traffic']}.json")
    mix.update(cell.get("traffic", {}))
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, workload, reported)]
    return Cell(
        name=workload, config=config, kind=costs.kind(config, cdir), mix=mix,
        engine=cell["engine"],
        check=cell["check"], chips=int(wl["chips"]), end_to_end=e2e,
        per_layer=per_layer, trace_seconds=float(cell.get("trace_seconds", 2.0)),
    )


def pin_environment() -> None:
    """Make every run use the same tiles and no tuning, whatever the machine
    has cached or set."""
    os.environ["REPRO_VLUT_AUTOTUNE_CACHE"] = str(TILES)
    for var in ("REPRO_VLUT_AUTOTUNE", "REPRO_VLUT_VMEM_BUDGET"):
        os.environ.pop(var, None)
    sys.path.insert(0, str(ROOT / "src"))


def use_compile_cache() -> None:
    import jax

    COMPILE_CACHE.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts JAX compilations (and traces) as they happen, the seconds spent
    compiling, and the persistent compile cache's hits and misses."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    EVENTS = (COMPILE, "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax

        self.count = 0
        self.compile_s = 0.0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **_):
        if event in self.EVENTS:
            self.count += 1
        if event == self.COMPILE:
            self.compile_s += duration

    def _on_event(self, event: str, **_):
        kind = event.rpartition("/jax/compilation_cache/cache_")[2]
        if kind in self.cache:
            self.cache[kind] += 1


@dataclasses.dataclass
class Tracked:
    """One request of the run and the benchmark's own timestamps for it."""
    req: object
    due: float                 # absolute perf_counter time it was due
    in_window: bool


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q of the
    sample at or below it."""
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]


class Run:
    """State of one run; readers of per-layer metrics get this object."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool):
        self.cell, self.seed, self.seconds, self.trace = cell, seed, seconds, trace
        self.tracked: list[Tracked] = []
        self.spans: list[dict] = []
        self.trace_data: dict | None = None
        self.peaks: dict = {}
        self.t_base = self.t_open = self.t_close = 0.0   # traffic starts,
                                   # the window opens, the window closes
        self.tokens_in_window = 0
        #: the engine's token counters as the window opens and closes
        self.counts_open: dict[str, int] = {}
        self.counts_close: dict[str, int] = {}
        self.lateness: list[float] = []
        #: id() of each request the engine refused (the scheduler's
        #: ``rejected``); a request queued for free pages has ``error`` set
        #: too, until it is admitted, and has not failed
        self.rejected: set[int] = set()

    @property
    def window_requests(self) -> list[Tracked]:
        return [t for t in self.tracked if t.in_window]


def _annotate(obj, attr: str, name: str):
    """Wrap obj.attr in a profiler host span."""
    import jax

    fn = getattr(obj, attr)

    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)

    setattr(obj, attr, wrapped)


def build(cell: Cell, seed: int, *, trace: bool, interpret: bool = False):
    """Weights on the device from the seed (one jitted call), the engine and
    its scheduler, exactly as served."""
    import jax

    from repro import obs as obs_mod
    from repro.models import init_lm, pack_params
    from repro.serve.engine import Engine
    from repro.serve.paging import PagedKVConfig
    from repro.serve.scheduler import ContinuousBatchingScheduler

    from seeds import model_key

    mc = cell.kind.model_config(cell.config)
    make_params = jax.jit(lambda k: pack_params(init_lm(k, mc), mc))
    params = make_params(model_key(seed))
    e = cell.engine
    engine = Engine(
        params, mc, max_slots=e["max_slots"], max_len=e["max_len"],
        prefill_chunk=e["prefill_chunk"], temperature=0.0,
        paged_kv=PagedKVConfig(page_size=e["page_size"],
                               n_pages=e.get("n_pages", 0)),
        mpgemm_interpret=True if interpret else None,
        obs=obs_mod.ObsConfig(trace_capacity=1 << 22) if trace else None,
    )
    return engine, ContinuousBatchingScheduler(engine)


def warm_up(cell: Cell, engine, sched) -> None:
    """Serve a few requests through both step shapes: chunk steps (a prompt
    longer than one chunk, with decode rows riding along) and plain decode
    steps. Every slot is used once, so no first use is left for the
    window."""
    from repro.serve.engine import Request

    e = cell.engine
    rng = np.random.default_rng(0)
    vocab = cell.config["vocab_size"]
    long = min(e["prefill_chunk"] + 1, e["max_len"] // 2)
    reqs = [Request(rid=-1 - i,
                    prompt=rng.integers(0, vocab, long if i == 0 else 2,
                                        dtype=np.int32),
                    max_new_tokens=3)
            for i in range(e["max_slots"])]
    sched.submit(reqs)
    while sched.queue or engine.has_work:
        sched.tick()
    engine.reset_stats()
    sched.completed.clear()


def prime(cell: Cell, seed: int, engine, sched) -> None:
    """Serve each shared prefix of the mix once (``traffic.prime_prompts``),
    so that the prefix index holds them before the traffic starts."""
    from repro.serve.engine import Request
    from traffic import prime_prompts

    prompts = prime_prompts(cell.mix, seed, cell.config["vocab_size"])
    if not prompts:
        return
    sched.submit([Request(rid=-1000 - i, prompt=p, max_new_tokens=1)
                  for i, p in enumerate(prompts)])
    while sched.queue or engine.has_work:
        sched.tick()
    engine.reset_stats()
    sched.completed.clear()


def set_up(cell: Cell, seed: int, *, trace: bool, interpret: bool = False,
           marks: dict | None = None):
    """The engine and its scheduler, built, warmed up and primed; the clock
    at the end of each phase goes into ``marks``."""
    marks = {} if marks is None else marks
    engine, sched = build(cell, seed, trace=trace, interpret=interpret)
    marks["built"] = time.perf_counter()
    warm_up(cell, engine, sched)
    marks["warmed up"] = time.perf_counter()
    prime(cell, seed, engine, sched)
    marks["primed"] = time.perf_counter()
    return engine, sched


def token_counts(engine) -> dict[str, float]:
    """Tokens served so far: prompt tokens prefilled, prompt tokens read
    from shared prefix pages, and tokens generated (the engine's counters).

    The engine counts a request's m shared prompt tokens when it admits it;
    here they are served with its prefill: each of its S - m prefilled
    tokens brings m / (S - m) of them, so all m are in by its first token.
    At ~5k shared tokens a request, a few admissions a window, the count
    at admission moved ``tok_s`` in steps of one admission."""
    pager, unserved = engine.pager, 0.0
    for slot, req in engine.prefilling.items():
        m, size = pager.slot_shared[slot] * pager.page_size, len(req.prompt)
        unserved += m * (size - req.prefill_pos) / (size - m)
    return {"prefill_tokens": engine.prefill_tokens,
            "prefix_hit_tokens": engine.prefix_hit_tokens - unserved,
            "decode_tokens": engine.decode_tokens}


def serve_window(run: Run, engine, sched, counter: CompileCounter) -> dict:
    """The lead-in, the measured window and the drain. Returns diagnostics."""
    import jax

    from repro.serve.engine import Request
    from traffic import generate

    cell, mix = run.cell, run.cell.mix
    arrivals = generate(mix, run.seed, run.seconds, cell.config["vocab_size"])
    reqs = [Request(rid=i, prompt=a.prompt, max_new_tokens=a.max_new_tokens)
            for i, a in enumerate(arrivals)]
    offline = mix["arrival"] == "backlog"
    lead_in = float(mix["lead_in_s"])
    drain_cap = float(mix.get("drain_cap_s", 0.0))
    t_base = run.t_base = time.perf_counter()
    run.tracked = [Tracked(r, t_base + a.due_s, a.in_window)
                   for r, a in zip(reqs, arrivals)]
    nxt = 0
    opened = closed = tracing = False
    compiles_open, compiles_window = 0, -1
    trace_stop = 0.0
    window_ann = None

    while True:
        now = time.perf_counter()
        while nxt < len(run.tracked) and run.tracked[nxt].due <= now:
            t = run.tracked[nxt]
            sched.submit([t.req])
            run.lateness.append(now - t.due)
            nxt += 1
        if not opened and now >= t_base + lead_in:
            opened, run.t_open = True, now
            run.counts_open = token_counts(engine)
            compiles_open = counter.count
            if run.trace:
                run.span_base = len(engine.obs.tracer.events)
                TRACE_DIR.mkdir(parents=True, exist_ok=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
                window_ann = jax.profiler.TraceAnnotation("bench.trace_window")
                window_ann.__enter__()
                tracing, trace_stop = True, time.perf_counter() + cell.trace_seconds
                now = time.perf_counter()
        if tracing and now >= trace_stop:
            jax.block_until_ready(engine.cache)
            window_ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing = False
            now = time.perf_counter()
        if opened and not closed and now >= run.t_open + run.seconds:
            jax.block_until_ready(engine.cache)
            closed, run.t_close = True, time.perf_counter()
            run.counts_close = token_counts(engine)
            run.tokens_in_window = sum(run.counts_close.values()) - sum(
                run.counts_open.values())
            compiles_window = counter.count - compiles_open
            if run.trace:
                run.spans = [
                    ev for ev in list(engine.obs.tracer.events)[run.span_base:]
                    if ev["name"].startswith("engine_step/")
                ]
            if offline:
                break
        if closed:
            refused = {id(r) for r in sched.rejected}
            pending = [t for t in run.window_requests if not t.req.done
                       and id(t.req) not in refused]
            if not pending or now > run.t_close + drain_cap:
                break
        if sched.queue or engine.has_work:
            sched.tick()
        elif nxt < len(run.tracked):
            wait = run.tracked[nxt].due - time.perf_counter()
            if wait > 2e-3:
                time.sleep(wait - 1e-3)
        else:
            break
    if tracing:
        jax.block_until_ready(engine.cache)
        window_ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
    run.rejected = {id(r) for r in sched.rejected}
    return {"compiles_in_window": compiles_window, "requests_offered": nxt}


def _sample_for_check(run: Run) -> list[Tracked]:
    """A sample drawn from the seed of the requests finished in the window,
    always with the longest among them, up to the cell's token budget and
    at least ``min_requests`` of them where the window finished as many."""
    done = [t for t in run.window_requests
            if t.req.done and len(t.req.generated) >= 1
            and (run.cell.mix["arrival"] != "backlog"
                 or run.t_open <= t.req.t_done <= run.t_close)]
    if not done:
        return []
    size = lambda t: len(t.req.prompt) + len(t.req.generated)
    longest = max(done, key=size)
    rng = np.random.default_rng([int(run.seed) & 0xFFFFFFFF, int(run.seed) >> 32, 2])
    rest = [done[i] for i in rng.permutation(len(done)) if done[i] is not longest]
    picked, served = [longest], len(longest.req.generated)
    budget, check = int(run.cell.check["tokens"]), run.cell.check
    for t in rest:
        if len(picked) >= int(check["max_requests"]) or (
                served >= budget and len(picked) >= int(check["min_requests"])):
            break
        picked.append(t)
        served += len(t.req.generated)
    return picked


def reference_module(cfg: dict):
    path = BENCH / "references" / f"{cfg['reference']}.py"
    spec = importlib.util.spec_from_file_location(f"ref_{cfg['reference']}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def served_gaps(cfg: dict, seed: int, items: list[tuple[np.ndarray, list[int]]],
                lowp=None, control: bool = False) -> list[np.ndarray]:
    """For each (prompt, served tokens): the gap by which each served token's
    reference logit lies below the reference's best at its position. With
    ``control`` the served tokens are ignored and the tokens that a
    ``lowp`` copy of the reference puts first are read instead."""
    from seeds import model_key

    mod = reference_module(cfg)
    seqs = []
    for prompt, gen in items:
        toks = np.concatenate([prompt, np.asarray(gen[:-1], np.int32)])
        pos = np.arange(len(prompt) - 1, len(prompt) - 1 + len(gen))
        seqs.append((toks, pos))
    ref = mod.Reference(cfg, model_key(seed)).logits(seqs)
    picks = ([np.asarray(g) for _, g in items] if not control else
             [l.argmax(-1) for l in
              mod.Reference(cfg, model_key(seed), lowp=lowp).logits(seqs)])
    return [r.max(-1) - np.take_along_axis(r, p[:, None], -1)[:, 0]
            for r, p in zip(ref, picks)]


def check_correct(run: Run) -> dict:
    """Compare what the timed path served with the plain reference."""
    picked = _sample_for_check(run)
    checks = {}
    short = sum(len(t.req.generated) != t.req.max_new_tokens for t in picked)
    checks["short_requests"] = {"value": short, "limit": 0}
    checks["checked_requests"] = {"value": len(picked),
                                  "limit": int(run.cell.check["min_requests"])}
    if picked:
        items = [(np.asarray(t.req.prompt, np.int32), list(t.req.generated))
                 for t in picked]
        gaps = served_gaps(run.cell.config, run.seed, items)
        widest = float(max(g.max() for g in gaps))
        checks["served_tokens"] = {"value": int(sum(len(g) for g in gaps)),
                                   "limit": int(run.cell.check["min_tokens"])}
        checks["widest_gap"] = {"value": widest,
                                "limit": float(run.cell.check["gap_limit"])}
    ok = (bool(picked) and short == 0
          and len(picked) >= checks["checked_requests"]["limit"]
          and checks["served_tokens"]["value"] >= checks["served_tokens"]["limit"]
          and checks["widest_gap"]["value"] <= checks["widest_gap"]["limit"])
    return {"correct": ok, "checks": checks}


def end_to_end(run: Run, setup_s: float) -> tuple[dict, int, int]:
    """The cell's end-to-end metrics, attempted and failed."""
    wr = run.window_requests
    refused = [t for t in wr if id(t.req) in run.rejected]
    values = {"setup_s": setup_s}
    if run.cell.mix["arrival"] == "backlog":
        finished = [t for t in wr
                    if t.req.done and run.t_open <= t.req.t_done <= run.t_close]
        values["tok_s"] = run.tokens_in_window / (run.t_close - run.t_open)
        return values, len(finished) + len(refused), len(refused)
    failed = [t for t in wr if not t.req.done]
    cap = max(time.perf_counter(), run.t_close)
    # a failed request misses every limit: it counts with what it waited
    ttft = [t.req.t_first_token - t.due if t.req.done else cap - t.due
            for t in wr]
    values["ttft_p95_ms"] = 1e3 * percentile(ttft, 0.95)
    return values, len(wr), len(failed)


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, interpret: bool = False,
             require_chip: bool = True, peaks: dict | None = None,
             log=None) -> dict:
    """One run of one cell. Returns the result line's object."""
    import jax

    log = log or (lambda s: print(s, file=sys.stderr, flush=True))
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if require_chip and (platform != "tpu" or len(devices) < cell.chips):
        raise SystemExit(
            f"needs {cell.chips} TPU chip(s); JAX sees {len(devices)} "
            f"{platform} device(s) ({kind})")
    peaks = peaks if peaks is not None else costs.peaks_for(kind)
    counter = CompileCounter()
    marks = {}
    engine, sched = set_up(cell, seed, trace=trace, interpret=interpret,
                           marks=marks)
    log("[setup] " + ", ".join(f"{k} at {t - t_process:.3f}s"
                               for k, t in marks.items())
        + f"; {counter.count} compilations and traces, "
        f"{counter.compile_s:.3f}s compiling, persistent cache "
        f"{counter.cache['hits']} hits {counter.cache['misses']} misses")
    if trace:
        for attr, name in (("step", "engine.step"),
                           ("_chunk_verify", "engine.chunk_verify dispatch"),
                           ("_decode", "engine.decode dispatch"),
                           ("_sample", "engine.sample"),
                           ("_flush_pager", "engine.flush_pager")):
            _annotate(engine, attr, name)
        _annotate(sched, "tick", "scheduler.tick")
    run = Run(cell, seed, seconds, trace)
    run.peaks = peaks
    diag = serve_window(run, engine, sched, counter)
    setup_s = run.t_base - t_process
    late = sorted(run.lateness)
    log(f"[window] {run.t_close - run.t_open:.3f}s, requests offered "
        f"{diag['requests_offered']}, due in window {len(run.window_requests)}, "
        f"compilations in window {diag['compiles_in_window']}, generator late "
        f"p50 {1e3 * late[len(late) // 2]:.3f} ms max {1e3 * late[-1]:.3f} ms")
    first = [t.req for t in run.tracked[:cell.engine["max_slots"]]]
    first_done = (f"{max(r.t_done for r in first) - run.t_base:.3f}s"
                  if all(r.done for r in first) else "not all")
    log(f"[window] token counts at open {run.counts_open}, at close "
        f"{run.counts_close}; the first {len(first)} requests done at "
        f"{first_done} of the lead-in's {float(cell.mix['lead_in_s'])}s")
    memory_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                      for d in devices[:cell.chips])
    device = {"platform": platform, "kind": kind, "count": cell.chips,
              "memory_peak_bytes": memory_peak}
    breakdown = None
    if trace:
        from tracereduce import reduce_trace

        run.trace_data = reduce_trace(TRACE_DIR, chips=cell.chips)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = run.trace_data["busy_s"]
        device["window_s"] = run.trace_data["window_s"]
        breakdown = run.trace_data["breakdown"]
    e2e, attempted, failed = end_to_end(run, setup_s)
    metrics = {}
    if trace:
        for m in cell.per_layer:
            value = load_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {k: {"value": v, "unit": units[k]} for k, v in e2e.items()
                   if k in units}
    # free the program before the reference runs: its peak is read already
    del engine, sched
    gc.collect()
    verdict = check_correct(run)
    for name, c in verdict["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    out = {"correct": verdict["correct"], "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = verdict["checks"]
    return out
