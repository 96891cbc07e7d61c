"""Tile-size autotuner for the Vec-LUT mpGeMM kernels (paper §4, measured).

The paper's §4 tile-size rules give a *feasible region* (N_tile a multiple of
the vector width, K_tile bounded so the streamed table fits the cache); the
best point inside it is hardware- and shape-dependent. This module:

  * enumerates legal (bm, bn, bkg) candidates under the VMEM-budget rule
    (`candidate_tiles`) — the TPU adaptation of 3^g · N_tile · K_tile/g < L1,
    extended with the fused kernels' float tile + scratch accumulator;
  * times each candidate on the *actual* kernel for a concrete
    (g, M, K-groups, N, backend, fusion) problem (`tune`);
  * persists winners in an on-disk JSON cache (`TileCache`, default
    ``~/.cache/repro/vlut_tiles.json``, override via
    ``REPRO_VLUT_AUTOTUNE_CACHE``) so a shape is timed once per host;
  * answers dispatch-time queries (`get_tiles`): cache hit → cached tiles,
    miss → the §4 heuristic (`heuristic_tiles`, what ops.select_tiles always
    returned) unless inline tuning is enabled (``REPRO_VLUT_AUTOTUNE=1`` or
    ``tune_if_missing=True``).

ops.py routes every kernel dispatch (and therefore `ternary_matmul`, the
model/serve-facing entry) through `get_tiles`; benchmarks/gemm_bench.py and
an explicit `tune` call are the usual cache writers.
"""
from __future__ import annotations

import dataclasses
import json
import os
import tempfile
import time
from typing import Callable, Iterable

_R = 3
#: default per-kernel VMEM budget: the scoped VMEM limit every Vec-LUT
#: pallas_call requests from Mosaic (``vmem_limit_bytes``), and the ceiling
#: `tile_vmem_bytes` is checked against when tiles are chosen. A TPU v5e
#: core has 128 MiB of VMEM; Mosaic's default scoped limit of 16 MiB is too
#: small for the g=5 lookup table at the smallest legal tile. This constant
#: is the SINGLE source of truth for every budget consumer — the kernels,
#: candidate enumeration here, `ops.select_tiles`, and the R5 lint rule all
#: resolve it through :func:`vmem_budget_bytes` so they can never drift.
VMEM_BUDGET_BYTES = 64 * 2**20

CACHE_ENV = "REPRO_VLUT_AUTOTUNE_CACHE"
TUNE_ENV = "REPRO_VLUT_AUTOTUNE"
#: env override for the VMEM budget (bytes) — hardware generations differ
#: in VMEM size; the kernels, the autotuner AND the R5 lint rule all read
#: this, so an override re-tunes and re-lints coherently
VMEM_BUDGET_ENV = "REPRO_VLUT_VMEM_BUDGET"


def vmem_budget_bytes() -> int:
    """The per-kernel VMEM working-set budget every consumer must use:
    ``REPRO_VLUT_VMEM_BUDGET`` when set (bytes), else VMEM_BUDGET_BYTES.
    A malformed or non-positive override falls back to the default rather
    than silently disabling the budget rule."""
    raw = os.environ.get(VMEM_BUDGET_ENV)
    if raw:
        try:
            v = int(raw)
        except ValueError:
            return VMEM_BUDGET_BYTES
        if v > 0:
            return v
    return VMEM_BUDGET_BYTES

# Every ladder entry is a legal Mosaic block by construction: bm a multiple
# of 8 sublanes, bn a multiple of 128 lanes, and bkg (the lane dim of the
# (bm, bkg) packed-weight block) a multiple of 128. Clamping to the problem
# keeps that: bm/bn clamp to the 8/128-padded extent, and bkg clamps to the
# whole K-group dim, which is legal at any size.
_BM_CANDIDATES = (64, 128, 256)
_BN_CANDIDATES = (128, 256, 512)
_BKG_CANDIDATES = (128, 256, 512)


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def tile_vmem_bytes(
    g: int, impl: str, bm: int, bn: int, bkg: int, *, fused: bool = True
) -> int:
    """Working-set bytes of one grid step (W + A + table + out + scratch).

    The lookup kernel's table dominates: it lives as the int32 matmul
    result, its int32 high part and the two transposed int8 halves (10 B an
    entry), beside the int8 one-hot (bm, bkg, 3^g) and the int32 per-k
    partial products (bkg, bm, bn)."""
    w = bm * bkg                                   # uint8 codes
    a = g * bkg * bn * (4 if fused else 1)         # f32 tile (fused) vs int8
    table = (
        (_R ** g) * bkg * (10 * bn + bm) + bkg * bm * bn * 4
        if impl == "lookup" else 0
    )
    out = bm * bn * 4
    acc = bm * bn * 4 if fused else 0
    scales = 4 * (bm + bn) if fused else 0
    return w + a + table + out + acc + scales


def heuristic_tiles(
    g: int,
    impl: str,
    vmem_budget: int | None = None,
    *,
    fused: bool = False,
) -> dict:
    """The static §4 rule (the cold-cache fallback): bn = minimal multiple
    of the 128-lane width that feeds the MXU (256 for decode — bigger N
    amortizes the decode), bkg = 128, the smallest legal K-group tile that
    is not the whole dimension (ops clamps it to the K-group count when that
    is smaller). bm halves until the working set (with the fused kernels'
    f32 activation tile and int32 scratch when ``fused=True``) fits the
    budget, down to the 8-row sublane floor. ``vmem_budget=None`` resolves
    through :func:`vmem_budget_bytes` (env-overridable)."""
    budget = vmem_budget if vmem_budget is not None else vmem_budget_bytes()
    t = dict(bm=128, bn=128 if impl == "lookup" else 256, bkg=128)
    while t["bm"] > 8 and tile_vmem_bytes(g, impl, **t, fused=fused) > budget:
        t["bm"] //= 2
    return t


def candidate_tiles(
    g: int,
    impl: str,
    m: int,
    kg: int,
    n: int,
    *,
    fused: bool = True,
    vmem_budget: int | None = None,
) -> list[dict]:
    """Legal (bm, bn, bkg) candidates for a concrete problem: every
    combination from the standard ladders that (a) stays within the VMEM
    budget and (b) isn't degenerate for the problem shape (tiles larger than
    the padded problem are clamped away as duplicates). Always non-empty —
    the §4 heuristic is appended as a safety net."""
    budget = vmem_budget if vmem_budget is not None else vmem_budget_bytes()
    m_cap = _round_up(max(m, 1), 8)
    n_cap = _round_up(max(n, 1), 128)
    out: list[dict] = []
    seen: set[tuple[int, int, int]] = set()
    for bm in _BM_CANDIDATES:
        bm = min(bm, m_cap)
        for bn in _BN_CANDIDATES:
            bn = min(bn, n_cap)
            for bkg in _BKG_CANDIDATES:
                bkg = min(bkg, max(kg, 1))
                key = (bm, bn, bkg)
                if key in seen:
                    continue
                if tile_vmem_bytes(g, impl, bm, bn, bkg, fused=fused) > budget:
                    continue
                seen.add(key)
                out.append(dict(bm=bm, bn=bn, bkg=bkg))
    if not out:
        out.append(heuristic_tiles(g, impl, budget, fused=fused))
    return out


# --------------------------------------------------------------------------
# persistent cache
# --------------------------------------------------------------------------
def cache_key(
    g: int, impl: str, m: int, kg: int, n: int, *, backend: str, fused: bool
) -> str:
    return f"{backend}|{impl}|{'fused' if fused else 'unfused'}|g{g}|m{m}|kg{kg}|n{n}"


class TileCache:
    """On-disk JSON map: cache_key → {bm, bn, bkg, seconds}."""

    def __init__(self, path: str | None = None):
        self.path = path or os.environ.get(CACHE_ENV) or os.path.join(
            os.path.expanduser("~"), ".cache", "repro", "vlut_tiles.json"
        )
        self._data: dict[str, dict] | None = None

    def _load(self) -> dict[str, dict]:
        if self._data is None:
            try:
                with open(self.path) as f:
                    self._data = json.load(f)
            except (OSError, ValueError):
                self._data = {}
        return self._data

    def get(self, key: str) -> dict | None:
        ent = self._load().get(key)
        if not ent:
            return None
        return {k: int(ent[k]) for k in ("bm", "bn", "bkg")}

    def put(self, key: str, tiles: dict, seconds: float | None = None) -> None:
        data = self._load()
        ent = {k: int(tiles[k]) for k in ("bm", "bn", "bkg")}
        if seconds is not None:
            ent["seconds"] = float(seconds)
        data[key] = ent
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        fd, tmp = tempfile.mkstemp(
            dir=os.path.dirname(self.path) or ".", suffix=".tmp"
        )
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(data, f, indent=0, sort_keys=True)
            os.replace(tmp, self.path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass


_default_cache: TileCache | None = None


def default_cache() -> TileCache:
    global _default_cache
    if _default_cache is None:
        _default_cache = TileCache()
    return _default_cache


def reset_default_cache(path: str | None = None) -> TileCache:
    """(Re)point the process-wide cache (tests / benchmark isolation)."""
    global _default_cache
    _default_cache = TileCache(path)
    return _default_cache


# --------------------------------------------------------------------------
# timing
# --------------------------------------------------------------------------
def _default_benchmark(
    g: int, impl: str, m: int, kg: int, n: int, *, fused: bool, interpret: bool
) -> Callable[[dict], float]:
    """Times the actual kernel on random data for one tile candidate."""
    import jax
    import numpy as np

    from . import ops  # local import: ops imports this module

    rng = np.random.default_rng(0)
    zero_code = (_R ** g - 1) // 2
    packed = jax.numpy.asarray(
        rng.integers(0, _R ** g, (m, kg)).astype(np.uint8)
    )
    a = jax.numpy.asarray(rng.standard_normal((kg * g, n)).astype(np.float32))

    def run(tiles: dict, repeats: int = 3) -> float:
        fn = lambda: ops.segment_mpgemm(  # noqa: E731
            packed, a, g, impl,
            fused=fused, interpret=interpret, tiles=tiles,
        )
        out = fn()
        jax.block_until_ready(out)                       # compile + warmup
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            times.append(time.perf_counter() - t0)
        times.sort()
        return times[len(times) // 2]

    return run


@dataclasses.dataclass
class TuneResult:
    tiles: dict
    seconds: float
    trials: list[tuple[dict, float]]


def tune(
    g: int,
    impl: str,
    m: int,
    kg: int,
    n: int,
    *,
    fused: bool = True,
    backend: str | None = None,
    interpret: bool = False,
    cache: TileCache | None = None,
    benchmark: Callable[[dict], float] | None = None,
    candidates: Iterable[dict] | None = None,
    vmem_budget: int | None = None,
) -> TuneResult:
    """Time every legal candidate, persist the winner, return it."""
    import jax

    backend = backend or ("interpret" if interpret else jax.default_backend())
    cache = cache or default_cache()
    budget = vmem_budget if vmem_budget is not None else vmem_budget_bytes()
    cands = list(
        candidates
        if candidates is not None
        else candidate_tiles(
            g, impl, m, kg, n, fused=fused, vmem_budget=budget
        )
    )
    bench = benchmark or _default_benchmark(
        g, impl, m, kg, n, fused=fused, interpret=interpret
    )
    trials: list[tuple[dict, float]] = []
    refused: list[tuple[dict, Exception]] = []
    for t in cands:
        try:
            trials.append((t, float(bench(t))))
        except Exception as e:  # noqa: BLE001 — a refused candidate loses
            if backend == "interpret":
                raise  # the interpreter enforces no tiling rules: a bug
            refused.append((t, e))
    if not trials:
        t, e = refused[-1]
        raise RuntimeError(
            f"every tile candidate for {impl} g={g} (m={m}, kg={kg}, n={n}) "
            f"was refused on {backend}; last {t}: {e}"
        ) from e
    best, best_s = min(trials, key=lambda kv: kv[1])
    key = cache_key(g, impl, m, kg, n, backend=backend, fused=fused)
    cache.put(key, best, best_s)
    # observability: feed the measured winner into the installed metrics
    # registry (per-(shape, impl) timing series + achieved GB/s / GFLOP/s
    # gauges) so serve-time tuning shows up in the metrics dump
    from repro import obs as obs_mod

    o = obs_mod.current()
    if o is not None:
        o.record_kernel_sample(
            g=g, impl=impl, m=m, kg=kg, n=n, fused=fused, seconds=best_s
        )
    return TuneResult(tiles=best, seconds=best_s, trials=trials)


def get_tiles(
    g: int,
    impl: str,
    m: int,
    kg: int,
    n: int,
    *,
    fused: bool = True,
    backend: str | None = None,
    interpret: bool = False,
    cache: TileCache | None = None,
    tune_if_missing: bool | None = None,
    benchmark: Callable[[dict], float] | None = None,
) -> dict:
    """Dispatch-time tile query: cached winner if present; otherwise tune
    inline when enabled (REPRO_VLUT_AUTOTUNE=1 / tune_if_missing=True) or
    fall back to the §4 heuristic (cold cache, e.g. first trace on CI)."""
    import jax

    backend = backend or ("interpret" if interpret else jax.default_backend())
    cache = cache or default_cache()
    key = cache_key(g, impl, m, kg, n, backend=backend, fused=fused)
    hit = cache.get(key)
    if hit is not None:
        return hit
    if tune_if_missing is None:
        # Env-triggered inline tuning never targets the interpreter: its
        # timings don't transfer to hardware and a single candidate can take
        # minutes. Explicit tune()/tune_if_missing=True still may.
        tune_if_missing = (
            os.environ.get(TUNE_ENV, "0") == "1" and backend != "interpret"
        )
    if tune_if_missing:
        return tune(
            g, impl, m, kg, n,
            fused=fused, backend=backend, interpret=interpret,
            cache=cache, benchmark=benchmark,
        ).tiles
    return heuristic_tiles(g, impl, fused=fused)
