"""Pallas kernel validation (interpret=True on CPU): shape/dtype/g sweeps,
bit-exact against the pure-jnp oracle in kernels/ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import pack_ternary, pack_weight, ternary_quantize
from repro.kernels import (
    autotune,
    ref_mpgemm,
    ref_segment_gemm_int,
    select_tiles,
    ternary_decode_gemm,
    ternary_matmul,
    vlut_lookup_gemm,
    vlut_mpgemm,
)


def _mk_int(m, k, n, g, seed=0):
    rng = np.random.default_rng(seed)
    w = rng.integers(-1, 2, (m, k)).astype(np.int8)
    a_q = rng.integers(-127, 128, (k, n)).astype(np.int8)
    packed = pack_ternary(jnp.asarray(w), g)
    a_r = jnp.asarray(a_q).reshape(k // g, g, n).transpose(1, 0, 2)
    ref = np.asarray(ref_segment_gemm_int(packed, jnp.asarray(a_q), g))
    return packed, a_r, ref


SHAPES = [(8, 1, 8), (16, 4, 32), (64, 16, 128), (128, 40, 256), (256, 7, 64)]


class TestDecodeKernel:
    @pytest.mark.parametrize("g", [4, 5])
    @pytest.mark.parametrize("m,kg,n", SHAPES)
    def test_exact_vs_ref(self, g, m, kg, n):
        packed, a_r, ref = _mk_int(m, kg * g, n, g, seed=kg)
        out = np.asarray(
            ternary_decode_gemm(packed, a_r, g=g, interpret=True, bm=32, bn=64, bkg=8)
        )
        assert np.array_equal(out, ref)


class TestLookupKernel:
    @pytest.mark.parametrize("g", [4, 5])
    @pytest.mark.parametrize("lookup", ["onehot", "serial"])
    @pytest.mark.parametrize("m,kg,n", [(16, 4, 32), (64, 16, 128)])
    def test_exact_vs_ref(self, g, lookup, m, kg, n):
        packed, a_r, ref = _mk_int(m, kg * g, n, g, seed=m)
        out = np.asarray(
            vlut_lookup_gemm(
                packed, a_r, g=g, lookup=lookup, interpret=True, bm=16, bn=32, bkg=4
            )
        )
        assert np.array_equal(out, ref)


class TestOpsWrapper:
    # slow: every drawn (m, k, n) shape is a fresh interpreted-Pallas compile
    @pytest.mark.slow
    @given(
        st.integers(1, 48),
        st.integers(12, 96),
        st.integers(1, 48),
        st.sampled_from(["xla", "decode", "lookup"]),
        st.integers(0, 2**31 - 1),
    )
    @settings(max_examples=12, deadline=None)
    def test_all_impls_match_ref(self, m, k, n, impl, seed):
        if k in (6, 7, 11):
            k = 13
        rng = np.random.default_rng(seed)
        w = rng.standard_normal((m, k)).astype(np.float32)
        a = rng.standard_normal((k, n)).astype(np.float32)
        tw = ternary_quantize(jnp.asarray(w))
        pw = pack_weight(tw.values, tw.scale, "auto")
        out = np.asarray(vlut_mpgemm(pw, jnp.asarray(a), impl=impl, interpret=True))
        want = np.asarray(ref_mpgemm(pw, jnp.asarray(a)))
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)

    def test_ternary_matmul_leading_dims(self):
        rng = np.random.default_rng(3)
        k, m = 45, 32
        w = rng.standard_normal((m, k)).astype(np.float32)
        tw = ternary_quantize(jnp.asarray(w))
        pw = pack_weight(tw.values, tw.scale, "auto")
        x = rng.standard_normal((2, 3, 4, k)).astype(np.float32)
        y = np.asarray(ternary_matmul(pw, jnp.asarray(x)))
        assert y.shape == (2, 3, 4, m)
        want = np.asarray(
            ref_mpgemm(pw, jnp.asarray(x.reshape(-1, k).T))
        ).T.reshape(2, 3, 4, m)
        np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-4)

    def test_select_tiles_vmem_budget(self):
        """§4 K_tile rule adapted: the tile, streamed table included, must
        fit the VMEM budget, and be a legal Mosaic block."""
        for g in (4, 5):
            t = select_tiles(g, "lookup")
            need = autotune.tile_vmem_bytes(g, "lookup", **t, fused=False)
            assert need <= autotune.vmem_budget_bytes()
            assert t["bn"] % 128 == 0 and t["bkg"] % 128 == 0


class TestDtypeEdges:
    def test_extreme_activations(self):
        """Saturated int8 activations: accumulation must not overflow."""
        g, m, kg, n = 5, 8, 64, 16
        k = kg * g
        w = np.ones((m, k), np.int8)  # all +1 → worst-case accumulation
        a_q = np.full((k, n), 127, np.int8)
        packed = pack_ternary(jnp.asarray(w), g)
        a_r = jnp.asarray(a_q).reshape(kg, g, n).transpose(1, 0, 2)
        ref = np.asarray(ref_segment_gemm_int(packed, jnp.asarray(a_q), g))
        assert ref.max() == 127 * k  # int32 exact
        out = np.asarray(ternary_decode_gemm(packed, a_r, g=g, interpret=True))
        assert np.array_equal(out, ref)
