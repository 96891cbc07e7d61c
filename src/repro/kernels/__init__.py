"""repro.kernels — Pallas TPU kernels for the Vec-LUT mpGeMM hot spot.

The hot path is the **fused single-pass pipeline** (paper §3.3): float
activations stream into the kernel, which quantizes each tile against the
per-token scale in VMEM and de-interleaves it there (the decode kernel once
per call, keeping the int8 token tile for every weight-row tile), and the
w_scale × a_scale dequant epilogue runs on the last K step — no int8
activation buffer, de-interleave rematerialization, or int32 output ever
round-trips through HBM. Tile sizes come from the measured autotuner with
the static §4 heuristic as the cold-cache fallback.

  vlut_lookup_gemm.py   — paper-faithful streamed vector-LUT (VMEM table +
                          1→N lookup): `vlut_lookup_gemm` (integer/unfused)
                          and `vlut_lookup_gemm_fused` (single-pass).
  ternary_decode_gemm.py— beyond-paper TPU-native streamed decode + MXU dot
                          (same ≤2-bit HBM format, same layout rules):
                          `ternary_decode_gemm` / `ternary_decode_gemm_fused`.
  autotune.py           — §4 tile-size rules made empirical: candidate
                          enumeration under the VMEM budget, per-(g, M, K,
                          N, backend) timing, persistent on-disk cache.
  flash_attention.py    — IO-aware attention (VMEM-resident scores) for the
                          train/prefill memory term (EXPERIMENTS §Perf).
  ops.py                — jit wrappers: fused/unfused dispatch, padding,
                          autotuned tile selection, scales, and the
                          DispatchConfig that serve/engine.py routes through.
  ref.py                — pure-jnp oracles (dense int32 ternary matmul).
"""
from . import autotune
from .flash_attention import flash_attention, flash_attention_bsnd
from .ops import (
    configure_dispatch,
    dispatch_override,
    segment_mpgemm,
    select_tiles,
    ternary_matmul,
    vlut_mpgemm,
)
from .ref import ref_mpgemm, ref_mpgemm_int, ref_segment_gemm_int
from .ternary_decode_gemm import ternary_decode_gemm, ternary_decode_gemm_fused
from .vlut_lookup_gemm import vlut_lookup_gemm, vlut_lookup_gemm_fused

__all__ = [
    "autotune",
    "flash_attention", "flash_attention_bsnd",
    "configure_dispatch", "dispatch_override", "segment_mpgemm",
    "select_tiles", "ternary_matmul", "vlut_mpgemm",
    "ref_mpgemm", "ref_mpgemm_int", "ref_segment_gemm_int",
    "ternary_decode_gemm", "ternary_decode_gemm_fused",
    "vlut_lookup_gemm", "vlut_lookup_gemm_fused",
]
