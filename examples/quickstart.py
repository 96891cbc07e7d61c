"""Quickstart: the Vec-LUT mpGeMM public API in 40 lines.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pack_weight, ternary_quantize, vlut_gemm, mad_gemm_int8
from repro.kernels import vlut_mpgemm, ref_mpgemm

# 1. Quantize a weight matrix to ternary (BitNet b1.58 absmean recipe) and
#    pack it at 1.6 bits/weight (g=5 trit groups → one uint8 index each).
rng = np.random.default_rng(0)
W = jnp.asarray(rng.standard_normal((1024, 4096)), jnp.float32)   # (M, K)
tern = ternary_quantize(W)
packed = pack_weight(tern.values, tern.scale, mode="auto")  # K=4096 → 816 g=5 + 4 g=4 groups
print(f"packed: {packed.bits_per_weight:.3f} bits/weight "
      f"({packed.M}x{packed.K} -> {packed.packed5.nbytes + packed.packed4.nbytes} bytes)")

# 2. Multiply against N parallel tokens with the vector-LUT algorithm
#    (paper Alg. 1: one unified table, one 1→N lookup per weight byte).
A = jnp.asarray(rng.standard_normal((4096, 64)), jnp.float32)     # (K, N)
out = vlut_gemm(packed, A)                                        # (M, N) f32
print("vlut_gemm:", out.shape, out.dtype)

# 3. Same thing through the fused Pallas kernel — compiled on a TPU, run by
#    the Pallas interpreter elsewhere — bit-identical integer results.
on_tpu = jax.default_backend() == "tpu"
out_kernel = vlut_mpgemm(packed, A, impl="decode", interpret=not on_tpu)
ref = ref_mpgemm(packed, A)
print("kernel max |err| vs oracle:", float(jnp.max(jnp.abs(out_kernel - ref))))

# 4. Baseline comparison (MAD int8 à la bitnet.cpp I2_S).
print("mad max |err| vs oracle:", float(jnp.max(jnp.abs(mad_gemm_int8(packed, A) - ref))))
