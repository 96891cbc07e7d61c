#!/usr/bin/env python3
"""Smoke run of the serving path on one TPU chip.

    python3 chip_smoke.py

Runs in one process, on the first TPU device, through the entry points a
user calls, with smollm-360m at full width (random weights from seed 0):

  (a) kernels  the fused decode and lookup mpGeMMs at the MLP down
               projection (960 x 2560) for N in {1, 8, 64, 512} tokens,
               against the dense oracle in kernels/ref.py;
  (b) serve    `repro.launch.serve.run`: 16 seeded requests, prompts of
               16-256 tokens, 32 new tokens each, 8 slots, max_len 1024,
               packed weights; once with whole-prompt prefill and a dense
               cache, once with --prefill-chunk 128 --page-size 16. Every
               request must complete, none rejected;
  (c) dispatch the compiled decode step of the dense engine contains
               `tpu_custom_call`, i.e. the default dispatch took Pallas;
  (d) logits   prefill logits of one prompt, the served engine (Pallas
               dispatch) against `Engine(mpgemm_impl="xla")`: within
               EXACT_RTOL when both are compiled without excess
               precision, and within LOGITS_RTOL as served.

Each phase prints one line with its wall and compile seconds. The tok/s
printed are a smoke figure from one short run, not a benchmark. A failed
phase makes the exit code non-zero. Without a TPU the script exits non-zero
before any phase; it never falls back to the CPU. The last line is
`{"ok": true, "device": {...}}` only when every phase passed.
"""
from __future__ import annotations

import json
import sys
import time
import traceback
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "smollm-360m"
TOKENS = (1, 8, 64, 512)
#: per-element bound on |kernel - oracle| relative to max |oracle|: one
#: activation that rounds the other way at a .5 tie moves one output by
#: w_scale * a_scale (about 2e-4 of the max here); a wrong lookup moves it
#: by O(1)
KERNEL_RTOL = 1e-3
#: bound on ||pallas - xla|| / ||xla|| over the prefill logits of one
#: prompt when XLA must round every bf16 intermediate: both paths then do
#: the same integer mpGeMMs on the same inputs (0 on a TPU v5e)
EXACT_OPTIONS = {"xla_allow_excess_precision": False}
EXACT_RTOL = 1e-6
#: the same bound for the programs as served. XLA may keep a bf16
#: intermediate in f32 in one program and round it in the other; the next
#: int8 activation quantization turns such a difference into a whole step
#: on some elements (0.029 on a TPU v5e). A wrong kernel gives O(1)
LOGITS_RTOL = 0.1
SERVE_ARGS = [
    "--arch", ARCH, "--requests", "16", "--slots", "8", "--max-len", "1024",
    "--max-new", "32", "--prompt-len", "256", "--min-prompt-len", "16",
]


class Phases:
    """Runs named phases, times them, and remembers which failed."""

    def __init__(self):
        import jax

        self.failed: list[str] = []
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def run(self, name: str, fn):
        t0, c0 = time.perf_counter(), self.compile_s
        try:
            detail = fn()
            status = "ok"
        except Exception as e:  # noqa: BLE001 — report, go on, exit non-zero
            traceback.print_exc()
            detail, status = f"{type(e).__name__}: {e}", "FAIL"
            self.failed.append(name)
        print(
            f"[{name}] {status} wall={time.perf_counter() - t0:.1f}s "
            f"compile={self.compile_s - c0:.1f}s {detail}",
            flush=True,
        )


def kernel_check() -> str:
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core import pack_weight, ternary_quantize
    from repro.kernels import ref_mpgemm, vlut_mpgemm

    cfg = get_config(ARCH)
    m, k = cfg.d_model, cfg.d_ff
    rng = np.random.default_rng(0)
    tw = ternary_quantize(
        jnp.asarray(rng.standard_normal((m, k)), jnp.float32), per_channel=True
    )
    pw = pack_weight(tw.values, tw.scale, mode="auto")
    worst = {}
    for impl in ("decode", "lookup"):
        for n in TOKENS:
            a = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
            out = np.asarray(vlut_mpgemm(pw, a, impl=impl, fusion="fused"))
            ref = np.asarray(ref_mpgemm(pw, a))
            err = float(np.max(np.abs(out - ref)) / np.max(np.abs(ref)))
            if not (out.shape == ref.shape and np.isfinite(out).all()
                    and err <= KERNEL_RTOL):
                raise AssertionError(
                    f"{impl} N={n}: shape {out.shape} vs {ref.shape}, "
                    f"max err / max|ref| = {err:.3g} > {KERNEL_RTOL}"
                )
            worst[impl] = max(worst.get(impl, 0.0), err)
    return (
        f"{m}x{k} g=5 N={list(TOKENS)} max err / max|ref|: "
        + " ".join(f"{i}={e:.3g}" for i, e in worst.items())
        + f" (bound {KERNEL_RTOL})"
    )


def serve_check(extra: list[str], out: dict, key: str) -> str:
    from repro.launch.serve import run

    engine, stats = run(SERVE_ARGS + extra)
    out[key] = engine
    if stats.completed != 16 or stats.rejected:
        raise AssertionError(
            f"completed {stats.completed}/16, rejected {stats.rejected}"
        )
    return (
        f"completed={stats.completed}/16 rejected={stats.rejected} "
        f"smoke tok/s={stats.throughput_tok_s:.1f} (not a benchmark)"
    )


def dispatch_check(engine) -> str:
    decode = engine.jit_entries()["decode"]
    hlo = decode.lower(
        engine.params, engine.cache, engine.last_token
    ).compile().as_text()
    n = hlo.count("tpu_custom_call")
    if not n:
        raise AssertionError("compiled decode step has no tpu_custom_call")
    return f"decode step holds {n} tpu_custom_call(s)"


def logits_check(engine) -> str:
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ops import dispatch_override
    from repro.models import init_cache
    from repro.serve import Engine

    cfg = engine.cfg
    xla = Engine(engine.params, cfg, max_slots=1, max_len=engine.max_len,
                 mpgemm_impl="xla")
    prompt = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab, (1, 128)), jnp.int32
    )
    cache = init_cache(cfg, 1, engine.max_len)

    def logits(eng, impl, options):
        # each engine's own prefill entry, traced under its own dispatch
        with dispatch_override(impl=impl):
            prefill = eng.jit_entries()["prefill1"].lower(
                eng.params, cache, prompt
            ).compile(compiler_options=options)
        return np.asarray(prefill(eng.params, cache, prompt)[0], np.float32)

    parts = []
    for options, bound in ((EXACT_OPTIONS, EXACT_RTOL), ({}, LOGITS_RTOL)):
        a = logits(engine, None, options)
        b = logits(xla, "xla", options)
        rel = float(np.linalg.norm(a - b) / np.linalg.norm(b))
        label = "exact compile" if options else "default compile"
        if not (np.isfinite(a).all() and a.shape == b.shape and rel <= bound):
            raise AssertionError(
                f"{label}: ||pallas - xla|| / ||xla|| = {rel:.3g} > {bound}"
            )
        parts.append(
            f"{label}: ||pallas - xla|| / ||xla|| = {rel:.3g} (bound {bound}),"
            f" argmax equal {bool(a.argmax() == b.argmax())}"
        )
    return "; ".join(parts)


def main() -> int:
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}", flush=True)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    print(f"device: {dev.device_kind} x{len(jax.devices())}", flush=True)

    phases = Phases()
    engines: dict = {}
    phases.run("a kernels", kernel_check)
    phases.run("b serve dense", lambda: serve_check([], engines, "dense"))
    phases.run("b serve chunked+paged", lambda: serve_check(
        ["--prefill-chunk", "128", "--page-size", "16"], engines, "paged"))
    if "dense" in engines:
        phases.run("c dispatch", lambda: dispatch_check(engines["dense"]))
        phases.run("d logits", lambda: logits_check(engines["dense"]))
    else:
        phases.failed += ["c dispatch", "d logits"]
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')}",
          flush=True)
    if phases.failed:
        print(f"chip_smoke: failed phases: {phases.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
