"""Pallas TPU kernel: paper-faithful streamed vector-LUT mpGeMM.

Implements the Vec-LUT pipeline (paper Alg. 1 + §3.4) per VMEM tile:

  1. *LUT precompute in VMEM*: the unified sub-table tile
     T (3^g, bkg, bn) int32 = S(3^g, g) ⨯ A_r(g, bkg, bn), computed with one
     MXU contraction against the compile-time sign-enumeration matrix S
     (the TPU replacement for topological precompute — DESIGN.md §2).
     T lives only in this grid step's VMEM: this is the paper's
     "streamed precomputing-lookup execution" with VMEM as the cache.
  2. *1→N vector lookup & accumulate*: every packed byte W[m, k] selects a
     row T[idx, k, :] — a vector of bn token results — accumulated into the
     revisited output block.

Two entry points share that core:

  * `vlut_lookup_gemm` — the integer-only kernel: pre-quantized int8
    activations in the de-interleaved (g, KG, N) layout → int32 output. The
    *unfused* pipeline (ops.py quantizes / de-interleaves / dequantizes in
    XLA around it, three extra HBM round-trips) — kept for the fusion
    ablation and as the bit-exact integer oracle target.
  * `vlut_lookup_gemm_fused` — the single-pass kernel (paper §3.3 "fused
    activation and output transformation"): activations enter as *float* in
    the free (KG, g, N) row-major view, each grid step quantizes its
    (bkg, g, bn) tile against the per-token scale *in VMEM* (prologue) and
    the final K step applies the w_scale × a_scale dequant epilogue from an
    int32 VMEM scratch accumulator, emitting f32/bf16 directly. No int8
    activation buffer, no de-interleave rematerialization, and no int32
    output ever touch HBM.

Two lookup strategies (both faithful to "one 1→N lookup per index"):
  * 'onehot' (default): the gather is expressed as a one-hot batched matmul
    onehot(W)(bm, bkg, 3^g) ⨯ T(3^g, bkg, bn) on the MXU — TPU has no
    cross-sublane vector gather, and one-hot contraction is the idiomatic
    Mosaic lowering of a row-select.
  * 'serial': literal row gather via a fori_loop of dynamic slices — the
    closest transliteration of the CPU kernel's inner loop; sublane-serial
    on real hardware (kept for fidelity comparison + ablation).

VMEM budget per §4's K_tile rule (adapted): the table T dominates the
working set — kernels/autotune.py models it (`tile_vmem_bytes`) and
enumerates legal (bm, bn, bkg) candidates under the budget, which is also
the scoped VMEM limit the kernels request (ops.select_tiles is the
cold-cache heuristic).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .autotune import vmem_budget_bytes

_R = 3


def compiler_params() -> pltpu.CompilerParams:
    """Scoped VMEM limit = the autotuner's budget, which candidate tiles
    are checked against (Mosaic's 16 MiB default cannot hold the g=5 table
    even at the smallest legal tile)."""
    return pltpu.CompilerParams(vmem_limit_bytes=vmem_budget_bytes())


def _lut_block_int(codes, a_r, *, g: int, lookup: str):
    """Shared LUT core: codes (bm, bkg) i32, a_r (g, bkg, bn) i8 → (bm, bn) i32."""
    bm, bkg = codes.shape
    bn = a_r.shape[2]
    n_entries = _R ** g

    # --- 1. streamed LUT precompute (unified across the bn tokens) --------
    # Sign-enumeration matrix S[e, j] = trit_j(e) - 1, built in-kernel from
    # iota (Pallas kernels cannot capture host constants).
    e_iota = jax.lax.broadcasted_iota(jnp.int32, (n_entries, 1), 0)
    s = jnp.concatenate(
        [(e_iota // (_R ** j)) % _R - 1 for j in range(g)], axis=1
    ).astype(jnp.int8)                                              # (3^g, g)
    # T[e, k, n] = sum_j S[e, j] * A_r[j, k, n]
    t = jax.lax.dot_general(
        s, a_r,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )                                                               # (3^g, bkg, bn)

    # --- 2. 1→N vector lookup + accumulate --------------------------------
    if lookup == "onehot":
        # onehot[m, k, e] ⨯ T[e, k, n] → batched over k: (bkg, bm, bn).
        # Mosaic has no mixed int8×int16 matmul, so T (|T| ≤ 127·g < 2^10)
        # is split exactly into int8 halves T = 128·hi + lo, hi ∈ [-5, 4],
        # lo ∈ [0, 127]: two native int8×int8→int32 MXU contractions.
        eye = jax.lax.broadcasted_iota(jnp.int32, (bm, bkg, n_entries), 2)
        onehot = (eye == codes[:, :, None]).astype(jnp.int8).transpose(1, 0, 2)
        hi = jnp.floor_divide(t, 128)

        def lookup_sum(half):                                       # → (bm, bn)
            part = jax.lax.dot_general(
                onehot,                                             # (bkg, bm, 3^g)
                half.astype(jnp.int8).transpose(1, 0, 2),           # (bkg, 3^g, bn)
                dimension_numbers=(((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.int32,
            )                                                       # (bkg, bm, bn)
            return jnp.sum(part, axis=0)

        return 128 * lookup_sum(hi) + lookup_sum(t - 128 * hi)
    # 'serial' — literal per-(m,k) row gather
    def body_k(k, acc):
        t_k = jax.lax.dynamic_slice(t, (0, k, 0), (n_entries, 1, bn))[:, 0, :]
        rows = jnp.take(t_k, codes[:, k], axis=0)                   # (bm, bn) 1→N
        return acc + rows.astype(jnp.int32)

    return jax.lax.fori_loop(0, bkg, body_k, jnp.zeros((bm, bn), jnp.int32))


def _vlut_kernel(w_ref, a_ref, o_ref, *, g: int, lookup: str):
    """w_ref: (bm, bkg) uint8; a_ref: (g, bkg, bn) int8; o_ref: (bm, bn) i32."""
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    codes = w_ref[...].astype(jnp.int32)                            # (bm, bkg)
    o_ref[...] += _lut_block_int(codes, a_ref[...], g=g, lookup=lookup)


def _vlut_fused_kernel(
    w_ref, a_ref, as_ref, ws_ref, o_ref, acc_ref, *, g: int, lookup: str, nk: int
):
    """Single-pass tile: quantize prologue → LUT core → dequant epilogue.

    w_ref: (bm, bkg) uint8; a_ref: (bkg, g, bn) float; as_ref: (1, bn) f32
    per-token scale; ws_ref: (bm, 1) f32 per-channel scale; o_ref: (bm, bn)
    f32/bf16; acc_ref: (bm, bn) int32 VMEM scratch (persists across the
    sequential K grid).
    """
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # --- prologue: fused per-token quantization + de-interleave -----------
    # A arrives as the free row-major view (KG, g, N); the (bkg, g, bn) tile
    # is quantized against the per-token scale and transposed to the
    # token-minor (g, bkg, bn) layout entirely in VMEM (§3.3).
    a = a_ref[...].astype(jnp.float32) / as_ref[...][None]          # (bkg, g, bn)
    a_q = jnp.clip(jnp.round(a), -127, 127).astype(jnp.int8)
    a_r = a_q.transpose(1, 0, 2)                                    # (g, bkg, bn)

    codes = w_ref[...].astype(jnp.int32)
    acc_ref[...] += _lut_block_int(codes, a_r, g=g, lookup=lookup)

    # --- epilogue: fused scale application on the last K step -------------
    @pl.when(k_step == nk - 1)
    def _finish():
        out = acc_ref[...].astype(jnp.float32) * ws_ref[...] * as_ref[...]
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("g", "bm", "bn", "bkg", "lookup", "interpret")
)
def vlut_lookup_gemm(
    packed: jax.Array,
    a_r: jax.Array,
    *,
    g: int,
    bm: int = 128,
    bn: int = 128,
    bkg: int = 128,
    lookup: str = "onehot",
    interpret: bool = False,
) -> jax.Array:
    """packed: (M, KG) uint8; a_r: (g, KG, N) int8 → (M, N) int32.

    Callers (ops.py) must pre-pad M/N/KG to block multiples — padded K-groups
    must carry the all-zero-trit code so they contribute 0.
    """
    m, kg = packed.shape
    g_, kg_, n = a_r.shape
    assert g_ == g and kg_ == kg, (packed.shape, a_r.shape, g)
    bm = min(bm, m)
    bn = min(bn, n)
    bkg = min(bkg, kg)
    nm, nn, nk = pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(kg, bkg)

    return pl.pallas_call(
        functools.partial(_vlut_kernel, g=g, lookup=lookup),
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bkg), lambda i, j, k: (i, k)),
            pl.BlockSpec((g, bkg, bn), lambda i, j, k: (0, k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        compiler_params=compiler_params(),
        interpret=interpret,
        name="vlut_lookup_gemm",
    )(packed, a_r)


@functools.partial(
    jax.jit,
    static_argnames=("g", "bm", "bn", "bkg", "lookup", "out_dtype", "interpret"),
)
def vlut_lookup_gemm_fused(
    packed: jax.Array,
    a: jax.Array,
    a_scale: jax.Array,
    w_scale: jax.Array,
    *,
    g: int,
    bm: int = 128,
    bn: int = 128,
    bkg: int = 128,
    lookup: str = "onehot",
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Single-pass fused lookup mpGeMM.

    packed: (M, KG) uint8; a: (KG, g, N) float (the free row-major view of
    the (K, N) activation); a_scale: (1, N) f32; w_scale: (M, 1) f32
    → (M, N) out_dtype = (W ⨯ quant(A)) · w_scale · a_scale.

    Same padding contract as the unfused kernel; additionally padded tokens
    must carry a_scale = 1 (their activations are 0 so any nonzero scale is
    exact) and padded rows w_scale = 0.
    """
    m, kg = packed.shape
    kg_, g_, n = a.shape
    assert g_ == g and kg_ == kg, (packed.shape, a.shape, g)
    assert a_scale.shape == (1, n) and w_scale.shape == (m, 1), (
        a_scale.shape, w_scale.shape)
    bm = min(bm, m)
    bn = min(bn, n)
    bkg = min(bkg, kg)
    nm, nn, nk = pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(kg, bkg)

    return pl.pallas_call(
        functools.partial(_vlut_fused_kernel, g=g, lookup=lookup, nk=nk),
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bkg), lambda i, j, k: (i, k)),
            pl.BlockSpec((bkg, g, bn), lambda i, j, k: (k, 0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=compiler_params(),
        interpret=interpret,
        name="vlut_lookup_gemm_fused",
    )(packed, a, a_scale, w_scale)
