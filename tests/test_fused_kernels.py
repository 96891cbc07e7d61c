"""Fused single-pass kernel validation (interpret=True on CPU).

The fused pipeline (quantize + de-interleave prologue, int32 VMEM
accumulation, scale epilogue) must reproduce the reference oracle across
padding edges (odd M/K/N), both kernels, mixed g=5/g=4 segments, and must
match the unfused three-pass pipeline bit-for-bit on single-segment weights
(same quantizer, same int path, same f32 scale application order)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import act_quant_tokens, pack_ternary, pack_weight, ternary_quantize
from repro.kernels import (
    ref_mpgemm,
    ref_segment_gemm_int,
    ternary_decode_gemm_fused,
    ternary_matmul,
    vlut_lookup_gemm_fused,
    vlut_mpgemm,
)
from repro.kernels import ops as kernel_ops


def _mk(m, k, n, seed=0):
    rng = np.random.default_rng(seed)
    w = rng.standard_normal((m, k)).astype(np.float32)
    a = rng.standard_normal((k, n)).astype(np.float32)
    tw = ternary_quantize(jnp.asarray(w))
    return tw, jnp.asarray(a)


_ref_jit = jax.jit(ref_mpgemm)

# odd M/K/N on purpose: every axis exercises the padding edge
ODD_SHAPES = [(8, 13, 3), (5, 20, 1), (33, 45, 17), (64, 97, 130), (127, 24, 7)]


# Direct-kernel cases: (impl, g, m, kg, n, activation dtype). The first
# four keep a 2 x 1 x 2 (row x token x K) grid at bm=8, bn=32, bkg=4; the
# "grid" cases run a 3 x 3 x 3 grid, so the decode kernel's activation tile
# is held across three row tiles for each of three token tiles.
DIRECT_CASES = [
    pytest.param(impl, g, 16, 8, 32, jnp.float32, id=f"{impl}-{g}")
    for impl in ("decode", "lookup") for g in (4, 5)
] + [
    pytest.param("decode", g, 24, 12, 96, dt, id=f"decode-{g}-grid-{name}")
    for g in (4, 5) for dt, name in ((jnp.float32, "f32"), (jnp.bfloat16, "bf16"))
]


class TestFusedKernelsDirect:
    """Direct fused-kernel calls against the dense int oracle + exact scales."""

    @pytest.mark.parametrize("impl,g,m,kg,n,dtype", DIRECT_CASES)
    def test_single_segment_exact(self, impl, g, m, kg, n, dtype, rng):
        k = kg * g
        w = rng.integers(-1, 2, (m, k)).astype(np.int8)
        a = rng.standard_normal((k, n)).astype(np.float32)
        packed = pack_ternary(jnp.asarray(w), g)
        a_j = jnp.asarray(a).astype(dtype)
        a_q, a_scale = act_quant_tokens(a_j)
        want_int = np.asarray(ref_segment_gemm_int(packed, a_q, g))
        want = want_int.astype(np.float32) * np.asarray(a_scale)[None, :]

        fn = ternary_decode_gemm_fused if impl == "decode" else vlut_lookup_gemm_fused
        out = fn(
            packed,
            a_j.reshape(kg, g, n),
            a_scale[None, :],
            jnp.ones((m, 1), jnp.float32),
            g=g, bm=8, bn=32, bkg=4, interpret=True,
        )
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("impl", ["decode", "lookup"])
    def test_padded_groups_contribute_zero(self, impl, rng):
        """ops-style padding: zero-code weight columns + zero activations +
        unit scales on padded tokens change nothing."""
        g, m, kg, n = 5, 8, 6, 16
        k = kg * g
        w = rng.integers(-1, 2, (m, k)).astype(np.int8)
        a = rng.standard_normal((k, n)).astype(np.float32)
        packed = pack_ternary(jnp.asarray(w), g)
        a_j = jnp.asarray(a)
        a_q, a_scale = act_quant_tokens(a_j)
        want = (
            np.asarray(ref_segment_gemm_int(packed, a_q, g)).astype(np.float32)
            * np.asarray(a_scale)[None, :]
        )
        zero_code = (3 ** g - 1) // 2
        packed_p = jnp.pad(packed, ((0, 0), (0, 2)), constant_values=zero_code)
        a3_p = jnp.pad(a_j.reshape(kg, g, n), ((0, 2), (0, 0), (0, 8)))
        as_p = jnp.pad(a_scale[None, :], ((0, 0), (0, 8)), constant_values=1.0)
        fn = ternary_decode_gemm_fused if impl == "decode" else vlut_lookup_gemm_fused
        out = fn(
            packed_p, a3_p, as_p, jnp.ones((m, 1), jnp.float32),
            g=g, bm=8, bn=8, bkg=4, interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(out)[:, :n], want, rtol=1e-6, atol=1e-6
        )
        # padded token columns: activations are 0 → exactly 0 out
        assert np.all(np.asarray(out)[:, n:] == 0)


# Pipeline parity cases: (impl, packing mode, m, kg, n, activation dtype).
# The first four are one-tile problems (24 x 40 x 9). The "grid" cases run
# the heuristic tiles (bm 128, bn 256, bkg 128) on 2 x 2 x 2 grids with
# padding on every axis: m = 203 rows (pad to 208), kg = 150 K-groups (pad to
# 256), n = 300 tokens (pad to 384). The "decode" cases are a decode step's
# shape: 16 tokens, padded to one 128-wide token tile.
UNFUSED_PARITY_CASES = [
    pytest.param(impl, mode, 24, 8 if mode == "i1" else 10, 9, jnp.float32,
                 id=f"{mode}-{impl}")
    for mode in ("i1", "i2") for impl in ("decode", "lookup")
] + [
    pytest.param("decode", mode, 203, 150, n, dt,
                 id=f"{mode}-decode-{shape}-{name}")
    for mode in ("i1", "i2")
    for n, shape in ((300, "grid"), (16, "decode"))
    for dt, name in ((jnp.float32, "f32"), (jnp.bfloat16, "bf16"))
]


class TestFusedPipeline:
    """vlut_mpgemm(fusion='fused') — the single-pass hot path."""

    @pytest.mark.parametrize("impl", ["decode", "lookup"])
    @pytest.mark.parametrize("m,k,n", ODD_SHAPES)
    def test_matches_oracle_odd_shapes(self, impl, m, k, n):
        tw, a = _mk(m, k, n, seed=m * 1000 + n)
        pw = pack_weight(tw.values, tw.scale, "auto")  # mixed g=5/g=4 for most K
        out = np.asarray(
            vlut_mpgemm(pw, a, impl=impl, interpret=True, fusion="fused")
        )
        want = np.asarray(ref_mpgemm(pw, a))
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("impl,mode,m,kg,n,dtype", UNFUSED_PARITY_CASES)
    def test_single_segment_bit_identical_to_unfused(
        self, impl, mode, m, kg, n, dtype
    ):
        """Same quantizer + same int path + same scale-mult order → the fused
        kernel's f32 output is bit-identical to the unfused pipeline when
        only one segment exists. The reference runs jitted, as the pipelines
        do (eager and jitted XLA round the token scale differently), and
        applies the same scales in its own program: it agrees to within f32
        rounding."""
        k = kg * (5 if mode == "i1" else 4)
        tw, a = _mk(m, k, n, seed=3)
        a = a.astype(dtype)
        pw = pack_weight(tw.values, tw.scale, mode)
        fused = np.asarray(
            vlut_mpgemm(pw, a, impl=impl, interpret=True, fusion="fused")
        )
        unfused = np.asarray(
            vlut_mpgemm(pw, a, impl=impl, interpret=True, fusion="unfused")
        )
        np.testing.assert_array_equal(fused, unfused)
        np.testing.assert_allclose(
            fused, np.asarray(_ref_jit(pw, a)), rtol=1e-6, atol=1e-6
        )

    @pytest.mark.parametrize("impl", ["decode", "lookup"])
    def test_mixed_segments_match_unfused(self, impl):
        """g=5 + g=4 mixed packing: fused sums two f32 partials (vs int32 sum
        then scale) — equal within f32 rounding."""
        tw, a = _mk(32, 57, 21, seed=11)  # 57 = 5*9 + 4*3 → both segments
        pw = pack_weight(tw.values, tw.scale, "auto")
        assert pw.packed5.shape[-1] and pw.packed4.shape[-1]
        fused = np.asarray(
            vlut_mpgemm(pw, a, impl=impl, interpret=True, fusion="fused")
        )
        unfused = np.asarray(
            vlut_mpgemm(pw, a, impl=impl, interpret=True, fusion="unfused")
        )
        np.testing.assert_allclose(fused, unfused, rtol=1e-6, atol=1e-6)

    def test_scale_epilogue_per_channel(self):
        """Non-trivial per-channel w_scale must be applied inside the kernel
        epilogue exactly as the unfused dequant pass applies it."""
        rng = np.random.default_rng(7)
        m, k, n = 16, 40, 8
        w = rng.standard_normal((m, k)).astype(np.float32) * np.linspace(
            0.1, 4.0, m
        )[:, None]
        a = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
        tw = ternary_quantize(jnp.asarray(w))
        assert np.asarray(tw.scale).std() > 0.1  # genuinely per-channel
        pw = pack_weight(tw.values, tw.scale, "i1")
        fused = np.asarray(vlut_mpgemm(pw, a, impl="decode", interpret=True))
        want = np.asarray(ref_mpgemm(pw, a))
        np.testing.assert_allclose(fused, want, rtol=1e-6, atol=1e-6)

    def test_bf16_output_dtype(self):
        """The epilogue emits the requested dtype directly from the kernel."""
        tw, a = _mk(16, 40, 8, seed=5)
        pw = pack_weight(tw.values, tw.scale, "i1")
        out = vlut_mpgemm(
            pw, a, impl="decode", interpret=True, out_dtype=jnp.bfloat16
        )
        assert out.dtype == jnp.bfloat16
        want = np.asarray(ref_mpgemm(pw, a))
        np.testing.assert_allclose(
            np.asarray(out, np.float32), want, rtol=2e-2, atol=2e-2
        )


class TestFusedServeRouting:
    """ternary_matmul routes serve-shaped calls through the fused path."""

    def test_leading_dims_fused_interpret(self):
        rng = np.random.default_rng(3)
        k, m = 45, 32
        w = rng.standard_normal((m, k)).astype(np.float32)
        tw = ternary_quantize(jnp.asarray(w))
        pw = pack_weight(tw.values, tw.scale, "auto")
        x = rng.standard_normal((2, 3, 4, k)).astype(np.float32)
        with kernel_ops.dispatch_override(impl="decode", fusion="fused",
                                          interpret=True):
            y = np.asarray(ternary_matmul(pw, jnp.asarray(x)))
        assert y.shape == (2, 3, 4, m)
        want = np.asarray(
            ref_mpgemm(pw, jnp.asarray(x.reshape(-1, k).T))
        ).T.reshape(2, 3, 4, m)
        np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-4)

    def test_dispatch_override_restores(self):
        base = kernel_ops.dispatch_config()
        before = (base.impl, base.fusion, base.interpret)
        with kernel_ops.dispatch_override(impl="lookup", interpret=True):
            assert kernel_ops.dispatch_config().impl == "lookup"
        assert (base.impl, base.fusion, base.interpret) == before


@pytest.mark.slow
def test_engine_prefill_decode_fused_end_to_end():
    """serve/engine.py prefill + decode on the fused interpreted Pallas path
    produce the same greedy tokens as the default (XLA) path."""
    from repro.configs import get_config
    from repro.models import init_lm, pack_params
    from repro.serve import Engine, Request

    cfg = get_config("smollm-360m", smoke=True)
    params = pack_params(init_lm(jax.random.PRNGKey(0), cfg), cfg)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, size=9).astype(np.int32)

    def gen(**mpgemm_kw):
        eng = Engine(params, cfg, max_slots=1, max_len=32, **mpgemm_kw)
        req = Request(rid=0, prompt=prompt, max_new_tokens=4)
        assert eng.add(req)
        while eng.n_active:
            eng.decode_once()
        return req.generated

    want = gen()  # default routing (XLA on CPU)
    got = gen(mpgemm_impl="decode", mpgemm_fusion="fused", mpgemm_interpret=True)
    assert got == want
