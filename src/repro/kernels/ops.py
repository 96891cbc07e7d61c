"""Public jit'd wrappers around the Vec-LUT TPU kernels.

The hot path is **single-pass** (paper §3.3 "fused activation and output
transformation"): float activations go straight into the Pallas kernel, which
quantizes each (bkg, bn) tile against the per-token scale in VMEM (prologue;
the decode kernel does so once per call and keeps the int8 token tile for
every weight-row tile), de-interleaves in VMEM from the free (K//g, g, N)
row-major view, and applies the w_scale × a_scale dequant epilogue on the
last K grid step — emitting f32/bf16 directly. The only HBM tensors are the
packed weights, the float activation, and the float output: no int8
activation buffer, no de-interleave rematerialization, no int32 output
round-trip.

Responsibilities:
  * per-token activation scale (one cheap reduction; shared with the QAT
    path via core.quantize.act_token_scale) + padding to block multiples
    (padded K-groups carry the all-zero-trit code so they contribute 0;
    padded tokens carry a_scale = 1, padded rows w_scale = 0);
  * tile-size selection through kernels/autotune.py (measured, disk-cached;
    the static §4 heuristic `select_tiles` is the cold-cache fallback);
  * backend dispatch: fused Pallas kernels on TPU (or interpret=True for CPU
    validation), and a shardable pure-XLA streamed-decode path used by the
    multi-device dry-run (pjit-friendly, identical semantics);
  * the `fusion="unfused"` ablation path: the original three-pass pipeline
    (XLA quantize → de-interleave/pad → int kernel → dequant), kept for
    benchmarks/gemm_bench.py --fusion and as a parity oracle.

The packed-serving path is inference-only by design (training runs the QAT
fake-quant dense path; see repro/models/common.py), so no custom_vjp here.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro import obs as obs_mod
from repro.core.packing import PackedWeight
from repro.core.quantize import act_quant_tokens, act_token_scale
from . import autotune
from .ternary_decode_gemm import (
    grid_counts, ternary_decode_gemm, ternary_decode_gemm_fused,
)
from .vlut_lookup_gemm import vlut_lookup_gemm, vlut_lookup_gemm_fused

_R = 3

Impl = Literal["decode", "lookup", "xla"]
Fusion = Literal["fused", "unfused"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def select_tiles(g: int, impl: Impl, vmem_budget: int | None = None):
    """Static §4 tile heuristic (delegates to autotune.heuristic_tiles).

    Kept public as the autotuner's cold-cache fallback; measured winners come
    from kernels/autotune.get_tiles / tune. The default budget resolves
    through `autotune.vmem_budget_bytes()` (env-overridable) — the same
    source the R5 lint rule reads, so dispatch and lint can never drift.
    """
    return autotune.heuristic_tiles(g, impl, vmem_budget)


# --------------------------------------------------------------------------
# dispatch configuration (the serve/model-facing routing knobs)
# --------------------------------------------------------------------------
@dataclasses.dataclass
class DispatchConfig:
    """Process-wide defaults for `ternary_matmul` routing. `impl=None` picks
    the backend default (fused Pallas decode on TPU, streamed XLA elsewhere)."""
    impl: Impl | None = None
    fusion: Fusion = "fused"
    interpret: bool = False


_dispatch = DispatchConfig()


def dispatch_config() -> DispatchConfig:
    return _dispatch


_DISPATCH_FIELDS = tuple(f.name for f in dataclasses.fields(DispatchConfig))


def configure_dispatch(**kw) -> DispatchConfig:
    """Set process-wide dispatch defaults (serve entrypoints call this).
    None values are ignored; unknown knobs raise."""
    for k, v in kw.items():
        if k not in _DISPATCH_FIELDS:
            raise TypeError(f"unknown dispatch knob {k!r}; have {_DISPATCH_FIELDS}")
        if v is not None:
            setattr(_dispatch, k, v)
    return _dispatch


@contextlib.contextmanager
def dispatch_override(**kw):
    """Temporarily override dispatch defaults (None values are ignored)."""
    saved = {f: getattr(_dispatch, f) for f in _DISPATCH_FIELDS}
    try:
        configure_dispatch(**kw)
        yield _dispatch
    finally:
        for f, v in saved.items():
            setattr(_dispatch, f, v)


# --------------------------------------------------------------------------
# layout / padding helpers
# --------------------------------------------------------------------------
def _deinterleave(a_q: jax.Array, g: int) -> jax.Array:
    """(K, N) → (g, K//g, N): A_r[j, k, :] = A[k*g+j, :] (§3.3 layout).

    Only the *unfused* ablation path materializes this — the fused kernels
    consume the zero-copy (K//g, g, N) view and transpose in VMEM."""
    K, N = a_q.shape
    return a_q.reshape(K // g, g, N).transpose(1, 0, 2)


def _pad_to(x: jax.Array, axis: int, mult: int, value=0) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _resolve_tiles(
    g: int, impl: Impl, m: int, kg: int, n: int,
    *, fused: bool, interpret: bool, tiles: dict | None,
) -> dict:
    """Per-segment tile resolution: explicit override > autotune cache >
    §4 heuristic (see kernels/autotune.py). bkg is clamped to the K-group
    count: a K tile spanning the whole dimension is a legal block at any
    size and needs no padding.

    A fully-specified override skips the autotuner entirely — essential for
    the autotuner's own timing benchmark (segment_mpgemm), which would
    otherwise re-enter tune() for the very key it is measuring."""
    if tiles and all(k in tiles for k in ("bm", "bn", "bkg")):
        t = dict(tiles)
    else:
        t = dict(autotune.get_tiles(
            g, impl, m, kg, n, fused=fused, interpret=interpret))
        t.update(tiles or {})
    t["bkg"] = min(t["bkg"], kg)
    return t


# --------------------------------------------------------------------------
# per-segment kernels (one homogeneous g)
# --------------------------------------------------------------------------
def _segment_gemm_int(
    packed: jax.Array,
    a_q_seg: jax.Array,
    g: int,
    impl: Impl,
    interpret: bool,
    tiles: dict | None,
) -> jax.Array:
    """Unfused integer segment: packed (M, KG) uint8 × a_q_seg (K, N) int8
    → (M, N) int32, dispatched to the chosen kernel."""
    m, kg = packed.shape
    n = a_q_seg.shape[1]
    if impl == "xla":
        # Shardable streamed decode: scan over K-chunks so the transient
        # dense tile stays small (the dry-run / pjit path).
        return _xla_streamed_decode(packed, a_q_seg, g)

    t = _resolve_tiles(g, impl, m, kg, n, fused=False, interpret=interpret, tiles=tiles)
    zero_code = (_R ** g - 1) // 2
    packed_p = _pad_to(_pad_to(packed, 1, t["bkg"], value=zero_code), 0, 8)
    a_r = _deinterleave(a_q_seg, g)
    a_r = _pad_to(_pad_to(a_r, 1, t["bkg"]), 2, 128)
    fn = ternary_decode_gemm if impl == "decode" else vlut_lookup_gemm
    out = fn(packed_p, a_r, g=g, interpret=interpret, **t)
    return out[:m, :n]


def _segment_gemm_fused(
    packed: jax.Array,
    a_seg: jax.Array,
    a_scale: jax.Array,
    w_scale: jax.Array,
    g: int,
    impl: Impl,
    interpret: bool,
    tiles: dict | None,
    out_dtype,
) -> jax.Array:
    """Single-pass fused segment: packed (M, KG) uint8 × a_seg (K, N) float
    → (M, N) out_dtype, with quantization + de-interleave + dequantization
    inside the kernel. a_scale: (N,) f32 per-token; w_scale: (M,) f32."""
    m, kg = packed.shape
    n = a_seg.shape[1]
    t = _resolve_tiles(g, impl, m, kg, n, fused=True, interpret=interpret, tiles=tiles)
    zero_code = (_R ** g - 1) // 2
    packed_p = _pad_to(_pad_to(packed, 1, t["bkg"], value=zero_code), 0, 8)
    a3 = a_seg.reshape(kg, g, n)                   # free row-major view of (K, N)
    a3 = _pad_to(_pad_to(a3, 0, t["bkg"]), 2, 128)
    a_scale_p = _pad_to(a_scale[None, :], 1, 128, value=1.0)
    w_scale_p = _pad_to(w_scale[:, None], 0, 8, value=0.0)
    fn = ternary_decode_gemm_fused if impl == "decode" else vlut_lookup_gemm_fused
    out = fn(
        packed_p, a3, a_scale_p, w_scale_p,
        g=g, out_dtype=out_dtype, interpret=interpret, **t,
    )
    return out[:m, :n]


def _xla_streamed_decode(
    packed: jax.Array, a_q_seg: jax.Array, g: int, k_chunk_groups: int = 512
) -> jax.Array:
    """Pure-XLA streamed decode+dot: functionally the Pallas decode kernel,
    expressed as a scan over K-group chunks (keeps the transient decoded tile
    ≤ M×(k_chunk·g) int8). pjit-shardable: M shards freely; K sharding gives
    row-parallel partial sums (psum inserted by SPMD)."""
    m, kg = packed.shape
    n = a_q_seg.shape[1]
    if kg <= k_chunk_groups:
        return _decode_dot(packed, a_q_seg, g)
    zero_code = (_R ** g - 1) // 2
    packed_p = _pad_to(packed, 1, k_chunk_groups, value=zero_code)
    a_p = _pad_to(a_q_seg, 0, k_chunk_groups * g)
    nc = packed_p.shape[1] // k_chunk_groups
    w_c = packed_p.reshape(m, nc, k_chunk_groups).transpose(1, 0, 2)
    a_c = a_p.reshape(nc, k_chunk_groups * g, n)

    def step(acc, xs):
        wc, ac = xs
        return acc + _decode_dot(wc, ac, g), None

    out, _ = jax.lax.scan(step, jnp.zeros((m, n), jnp.int32), (w_c, a_c))
    return out


def _decode_dot(packed: jax.Array, a_q: jax.Array, g: int) -> jax.Array:
    """Decode to a dense int8 tile, then one dot. A per-trit-position dot
    (the Pallas decode kernel's structure) is ~1.3× faster on pre-quantized
    int8 inputs, but in the *fused* graph its g operand reads make XLA
    re-fuse (recompute) the activation quantization per trit position —
    measured net loss; the single-consumer form keeps quantize computed
    once."""
    codes = packed.astype(jnp.int32)                                 # (M, KG)
    trits = (codes[..., None] // (_R ** jnp.arange(g, dtype=jnp.int32))) % _R - 1
    w_t = trits.reshape(packed.shape[0], packed.shape[1] * g).astype(jnp.int8)
    return jax.lax.dot_general(
        w_t, a_q, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def _segments(pw: PackedWeight):
    """[(packed, col_start, col_stop, g)] for the non-empty segments."""
    segs = []
    if pw.packed5.shape[-1]:
        segs.append((pw.packed5, 0, pw.k5, 5))
    if pw.packed4.shape[-1]:
        segs.append((pw.packed4, pw.k5, pw.k5 + pw.k4, 4))
    return segs


def _w_scale(pw: PackedWeight) -> jax.Array:
    return (
        pw.scale if pw.scale.shape[-1] == pw.M
        else jnp.broadcast_to(pw.scale, (pw.M,))
    )


# --------------------------------------------------------------------------
# public mpGeMM entry points
# --------------------------------------------------------------------------
@functools.partial(
    jax.jit, static_argnames=("impl", "interpret", "out_dtype", "fusion")
)
def vlut_mpgemm(
    pw: PackedWeight,
    a: jax.Array,
    *,
    impl: Impl = "decode",
    interpret: bool = False,
    out_dtype=jnp.float32,
    fusion: Fusion = "fused",
) -> jax.Array:
    """Kernel-backed mpGeMM. a: (K, N) float, token-contiguous → (M, N).

    fusion="fused" (default) runs the single-pass kernel; "unfused" runs the
    original multi-pass pipeline, whose stage boundaries are real HBM
    materializations for the Pallas impls (XLA quantize → pallas_call →
    XLA dequant). The two are numerically identical up to f32 summation
    order when the weight has both a g=5 and a g=4 segment, bit-identical
    otherwise. For impl="xla" there is no Pallas stage and XLA fuses freely
    inside one jit (optimization_barrier is elided on CPU), so both fusion
    arms compile to the same graph here — the benchmark's unfused-xla
    ablation arm stages separate dispatches instead (gemm_bench.py).
    """
    n = a.shape[1]
    segs = _segments(pw)
    if fusion == "fused" and impl != "xla":
        a_f = a if jnp.issubdtype(a.dtype, jnp.floating) else a.astype(jnp.float32)
        a_scale = act_token_scale(a_f)                               # (N,)
        w_scale = _w_scale(pw)
        seg_dtype = out_dtype if len(segs) == 1 else jnp.float32
        parts = [
            _segment_gemm_fused(
                packed, a_f[lo:hi], a_scale, w_scale, g, impl, interpret,
                None, seg_dtype,
            )
            for packed, lo, hi, g in segs
        ]
        if not parts:
            return jnp.zeros((pw.M, n), out_dtype)
        out = parts[0] if len(parts) == 1 else sum(parts).astype(out_dtype)
        return out

    # fusion="unfused" (or impl="xla"): the original three-pass pipeline —
    # materialized int8 activations, de-interleave layout pass (Pallas impls),
    # int32 kernel output, separate dequant. For the Pallas kernels each
    # stage boundary is a real HBM materialization (pallas_call in/out); for
    # impl="xla" inside one jit XLA fuses freely, so the *benchmark* stages
    # the unfused ablation as separate dispatches (see gemm_bench.py).
    a_q, a_scale = act_quant_tokens(a)
    out = jnp.zeros((pw.M, n), jnp.int32)
    for packed, lo, hi, g in segs:
        out = out + _segment_gemm_int(packed, a_q[lo:hi], g, impl, interpret, None)
    w_scale = _w_scale(pw)
    return (
        out.astype(jnp.float32) * w_scale[:, None] * a_scale[None, :]
    ).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("g", "impl", "fused", "interpret", "tiles_t", "out_dtype"),
)
def _segment_mpgemm_jit(
    packed, a, *, g, impl, fused, interpret, tiles_t, out_dtype
):
    tiles = dict(tiles_t) if tiles_t else None
    a_scale = act_token_scale(a)
    m = packed.shape[0]
    if fused and impl != "xla":
        w_scale = jnp.ones((m,), jnp.float32)
        return _segment_gemm_fused(
            packed, a, a_scale, w_scale, g, impl, interpret, tiles, out_dtype
        )
    a_q, a_scale = act_quant_tokens(a)
    out = _segment_gemm_int(packed, a_q, g, impl, interpret, tiles)
    return (out.astype(jnp.float32) * a_scale[None, :]).astype(out_dtype)


def segment_mpgemm(
    packed: jax.Array,
    a: jax.Array,
    g: int,
    impl: Impl,
    *,
    fused: bool = True,
    interpret: bool = False,
    tiles: dict | None = None,
    out_dtype=jnp.float32,
) -> jax.Array:
    """One homogeneous-g mpGeMM with unit weight scale — the autotuner's
    timing target (explicit `tiles` override, fused/unfused selectable)."""
    tiles_t = tuple(sorted(tiles.items())) if tiles else None
    return _segment_mpgemm_jit(
        packed, a, g=g, impl=impl, fused=fused, interpret=interpret,
        tiles_t=tiles_t, out_dtype=out_dtype,
    )


def _peek_tiles(pw: PackedWeight, n_tokens: int, impl: str, fusion: str,
                interpret: bool):
    """Best-effort cached-tile lookup for the dispatch trace annotation (the
    first segment's tiles; 'heuristic' when the autotuner has no measured
    winner). Never tunes — this runs on the dispatch path."""
    if impl == "xla":
        return None
    segs = _segments(pw)
    if not segs:
        return None
    packed, _, _, g = segs[0]
    backend = "interpret" if interpret else jax.default_backend()
    hit = autotune.default_cache().get(autotune.cache_key(
        g, impl, packed.shape[0], packed.shape[1], n_tokens,
        backend=backend, fused=fusion == "fused",
    ))
    return hit if hit is not None else "heuristic"


def _act_tiles(pw: PackedWeight, n_tokens: int, impl: str, fusion: str,
               interpret: bool) -> dict:
    """Activation tiles the kernel calls fetch from HBM (`act_tile_loads`,
    summed over segments) and the weight-row tiles that share each fetch
    (`act_reuse`), with the tiles dispatch resolves (cache or heuristic;
    never tunes). The fused decode kernel holds its activation tile across
    the row tiles (nn·nk loads, reuse nm); the other kernels fetch it again
    for every row tile (nm·nn·nk loads, reuse 1) unless there is only one
    activation tile. Empty for impl="xla"."""
    if impl == "xla":
        return {}
    loads, reuse = 0, []
    for packed, _, _, g in _segments(pw):
        m, kg = packed.shape
        t = autotune.get_tiles(
            g, impl, m, kg, n_tokens, fused=fusion == "fused",
            interpret=interpret, tune_if_missing=False,
        )
        nm, nn, nk = grid_counts(m, kg, n_tokens, t["bm"], t["bn"], t["bkg"])
        stationary = (impl == "decode" and fusion == "fused") or nn * nk == 1
        loads += nn * nk * (1 if stationary else nm)
        reuse.append(nm if stationary else 1)
    if not reuse:
        return {}
    return {"act_tile_loads": loads, "act_reuse": min(reuse)}


def ternary_matmul(
    pw: PackedWeight,
    x: jax.Array,
    impl: Impl | None = None,
    fusion: Fusion | None = None,
) -> jax.Array:
    """Model-facing packed linear:  y(..., M) = x(..., K) · Wᵀ.

    Fuses the token-first layout transformation (flatten tokens → transpose
    to token-minor) around the kernel, per paper §3.3. Routing comes from the
    process DispatchConfig (see `configure_dispatch`/`dispatch_override`):
    by default the fused single-pass Pallas kernel on TPU (tiles from the
    autotuner) and the shardable XLA streamed-decode elsewhere (incl. the
    multi-pod dry-run). serve/engine.py prefill and decode land here for
    every BitLinear.
    """
    cfg = _dispatch
    if impl is None:
        impl = cfg.impl if cfg.impl is not None else (
            "decode" if (on_tpu() or cfg.interpret) else "xla"
        )
    fusion = fusion if fusion is not None else cfg.fusion
    lead = x.shape[:-1]
    a = x.reshape(-1, x.shape[-1]).T                                 # (K, N) token-minor
    # observability hook: inside a jit this python body runs at *trace* time
    # only, so the span fires once per compiled shape (duration = host-side
    # dispatch/trace cost) with the (M, N, K, impl, fusion, tile) args that
    # make slow ticks attributable to kernel shape choices. Eager calls get
    # a true per-call span. See repro.obs / docs/observability.md.
    o = obs_mod.current()
    if o is not None:
        span = o.mpgemm_span(
            m_tokens=a.shape[1], k=a.shape[0], n_out=pw.M, impl=impl,
            fusion=fusion,
            tiles=_peek_tiles(pw, a.shape[1], impl, fusion, cfg.interpret),
            **_act_tiles(pw, a.shape[1], impl, fusion, cfg.interpret),
        )
    else:
        span = contextlib.nullcontext()
    with span:
        out = vlut_mpgemm(
            pw, a, impl=impl, interpret=cfg.interpret, out_dtype=x.dtype,
            fusion=fusion,
        )                                                            # (M, N)
    return out.T.reshape(*lead, pw.M)
