"""Pallas TPU kernel: streamed ternary-decode mpGeMM (beyond-paper variant).

TPU-native realization of Vec-LUT's memory-system insight (DESIGN.md §2):
weights stay in HBM at 1.6/2.0 bits/weight as trit codes; each grid step
streams a packed tile into VMEM, decodes it to {-1,0,1} int8 *in VMEM* (three
VPU ops per trit position), and feeds the MXU with an int8×int8→int32 dot.
No dequantized weight tensor ever exists in HBM — the analogue of the paper's
"streamed precompute-lookup entirely in cache", with the MXU replacing the
table since TPU matmul is cheaper than cross-sublane gathers.

Two entry points:
  * `ternary_decode_gemm` — integer-only (pre-quantized int8 A_r in, int32
    out); the unfused pipeline, kept for ablation and oracle checks.
  * `ternary_decode_gemm_fused` — single-pass (paper §3.3 adapted): float A
    in the free (KG, g, N) view, a quantization prologue in VMEM, int32 VMEM
    scratch accumulation, and the w_scale × a_scale dequant epilogue fused
    into the last K step → f32/bf16 straight to HBM.

Layout contract (Vector-LUT-centric, paper §3.3 adapted):
  * unfused: activation A pre-deinterleaved to A_r (g, K//g, N) in XLA;
    fused: A passed as the (K//g, g, N) row-major *view* (zero-copy) and
    de-interleaved per tile in VMEM.
  * packed weights W (M, K//g) uint8 — tile-contiguous via BlockSpec.
  * output O (M, N), token-contiguous.

Per block (bm, bn, bkg):  O[i,j] += sum_j trit_j(W[i,k]) @ A_r[j,k,n]
— g small matmuls of (bm × bkg) @ (bkg × bn), int32 accumulation across the
innermost (K) grid axis.

Fused schedule — the activation tile is stationary. As the paper builds the
token-side table once across the parallel tokens and streams the weight
indices past it, the fused kernel quantizes each token tile once and streams
every weight-row tile past it. The grid is (nn, nm, nk): token tile j outer,
weight-row tile i in the middle, K innermost. The activation block index is
(k, 0, j) while i == 0 and stays at (nk - 1, 0, j) for i > 0, so Pallas
starts no activation DMA after the first row tile: each activation tile is
fetched once per call (nn·nk loads, not nm·nn·nk). While i == 0 the prologue
quantizes and de-interleaves the fetched tile into a persistent int8 VMEM
scratch (nk, g, bkg, bn) — the whole K extent of token tile j — and every
step, for every i, feeds its g int8 dots from that scratch. The scratch
carries state across i, so the i and k axes are sequential ("arbitrary");
j is independent ("parallel").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .autotune import vmem_budget_bytes
from .vlut_lookup_gemm import compiler_params

_R = 3


def _decode_block_int(codes, a_r, *, g: int):
    """codes (bm, bkg) i32, a_r (g, bkg, bn) int8 → (bm, bn) int32."""
    acc = jnp.zeros((codes.shape[0], a_r.shape[2]), jnp.int32)
    for j in range(g):                                     # static unroll
        trit = (codes // (_R ** j)) % _R - 1               # VPU decode, {-1,0,1}
        acc = acc + jax.lax.dot_general(
            trit.astype(jnp.int8),
            a_r[j],                                        # (bkg, bn) int8
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
    return acc


def _decode_gemm_kernel(w_ref, a_ref, o_ref, *, g: int, nk: int):
    """One (bm, bn) output tile, one K-tile step.

    w_ref: (bm, bkg) uint8; a_ref: (g, bkg, bn) int8; o_ref: (bm, bn) int32.
    """
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    codes = w_ref[...].astype(jnp.int32)                   # (bm, bkg)
    o_ref[...] += _decode_block_int(codes, a_ref[...], g=g)


def _decode_gemm_fused_kernel(
    w_ref, a_ref, as_ref, ws_ref, o_ref, acc_ref, aq_ref, *, g: int, nk: int
):
    """Single-pass tile: quantize prologue (first row tile only) → decode+dot
    → dequant epilogue. Grid (j, i, k) = (token tile, row tile, K tile).

    w_ref: (bm, bkg) uint8; a_ref: (bkg, g, bn) float; as_ref: (1, bn) f32;
    ws_ref: (bm, 1) f32; o_ref: (bm, bn) f32/bf16; acc_ref: (bm, bn) int32
    scratch persisting across the sequential K grid; aq_ref: (nk, g, bkg, bn)
    int8 scratch holding token tile j quantized and de-interleaved, written
    while i == 0 and read by every row tile.
    """
    i, k_step = pl.program_id(1), pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i == 0)
    def _quantize():
        a = a_ref[...].astype(jnp.float32) / as_ref[...][None]      # (bkg, g, bn)
        a_q = jnp.clip(jnp.round(a), -127, 127).astype(jnp.int8)
        aq_ref[k_step] = a_q.transpose(1, 0, 2)                     # (g, bkg, bn)

    codes = w_ref[...].astype(jnp.int32)
    acc_ref[...] += _decode_block_int(codes, aq_ref[k_step], g=g)

    @pl.when(k_step == nk - 1)
    def _finish():
        out = acc_ref[...].astype(jnp.float32) * ws_ref[...] * as_ref[...]
        o_ref[...] = out.astype(o_ref.dtype)


def grid_counts(m: int, kg: int, n: int, bm: int, bn: int, bkg: int):
    """(nm, nn, nk): row, token and K tiles of a kernel call on operands
    (m, kg) and (kg, g, n), each block clamped to its dimension. Padding m
    to 8, n to 128 and kg to bkg, as ops does, leaves the counts as they are
    for 8- and 128-aligned bm and bn."""
    bm, bn, bkg = min(bm, m), min(bn, n), min(bkg, kg)
    return pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(kg, bkg)


@functools.partial(
    jax.jit, static_argnames=("g", "bm", "bn", "bkg", "interpret")
)
def ternary_decode_gemm(
    packed: jax.Array,
    a_r: jax.Array,
    *,
    g: int,
    bm: int = 128,
    bn: int = 256,
    bkg: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """packed: (M, KG) uint8; a_r: (g, KG, N) int8 → (M, N) int32.

    Block sizes follow the TPU-adapted §4 rules: bn multiple of 128 lanes
    (N_tile rule), bm multiple of 8 sublanes, bkg sized so the A tile
    (g·bkg·bn int8) + W tile stay within the VMEM budget (K_tile rule) —
    kernels/autotune.py enumerates and times the candidates. Shapes not
    divisible by blocks are padded by Pallas (zero padding is exact here:
    code 0 decodes to all -1 trits but the padded A rows are 0).
    """
    m, kg = packed.shape
    g_, kg_, n = a_r.shape
    assert g_ == g and kg_ == kg, (packed.shape, a_r.shape, g)
    bm = min(bm, m)
    bn = min(bn, n)
    bkg = min(bkg, kg)
    nm, nn, nk = pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(kg, bkg)

    return pl.pallas_call(
        functools.partial(_decode_gemm_kernel, g=g, nk=nk),
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bkg), lambda i, j, k: (i, k)),
            pl.BlockSpec((g, bkg, bn), lambda i, j, k: (0, k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        compiler_params=compiler_params(),
        interpret=interpret,
        name="ternary_decode_gemm",
    )(packed, a_r)


@functools.partial(
    jax.jit, static_argnames=("g", "bm", "bn", "bkg", "out_dtype", "interpret")
)
def ternary_decode_gemm_fused(
    packed: jax.Array,
    a: jax.Array,
    a_scale: jax.Array,
    w_scale: jax.Array,
    *,
    g: int,
    bm: int = 128,
    bn: int = 256,
    bkg: int = 128,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Single-pass fused decode mpGeMM.

    packed: (M, KG) uint8; a: (KG, g, N) float (free view of (K, N));
    a_scale: (1, N) f32; w_scale: (M, 1) f32 → (M, N) out_dtype.

    Padded tokens must carry a_scale = 1, padded rows w_scale = 0 (see
    vlut_lookup_gemm_fused).
    """
    m, kg = packed.shape
    kg_, g_, n = a.shape
    assert g_ == g and kg_ == kg, (packed.shape, a.shape, g)
    assert a_scale.shape == (1, n) and w_scale.shape == (m, 1), (
        a_scale.shape, w_scale.shape)
    bm = min(bm, m)
    bn = min(bn, n)
    bkg = min(bkg, kg)
    nm, nn, nk = grid_counts(m, kg, n, bm, bn, bkg)

    return pl.pallas_call(
        functools.partial(_decode_gemm_fused_kernel, g=g, nk=nk),
        grid=(nn, nm, nk),
        in_specs=[
            pl.BlockSpec((bm, bkg), lambda j, i, k: (i, k)),
            # K tile k while i == 0; parked on the last K tile for i > 0 (the
            # factor (i + nm - 1) // nm is 0 at i == 0 and 1 after), so the
            # block index does not change and no DMA starts
            pl.BlockSpec(
                (bkg, g, bn),
                lambda j, i, k, nk=nk, nm=nm: (
                    k + (nk - 1 - k) * ((i + nm - 1) // nm), 0, j),
            ),
            pl.BlockSpec((1, bn), lambda j, i, k: (0, j)),
            pl.BlockSpec((bm, 1), lambda j, i, k: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda j, i, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.int32),
            pltpu.VMEM((nk, g, bkg, bn), jnp.int8),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_budget_bytes(),
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="ternary_decode_gemm_fused",
    )(packed, a, a_scale, w_scale)
