"""Compile rehearsals for one TPU v5e chip, with shapes only.

The TPU compiler refuses what interpret mode cannot see: blocks that break
the (8, 128) tiling rule, operand types Mosaic has no matmul for, and more
VMEM than a kernel may use. These tests compile the fused mpGeMM kernels
and the engine's decode and verify steps at smollm-360m widths for a
described (not attached) `v5e:2x2` topology, and check that each program
holds a Pallas kernel (`tpu_custom_call`). Nothing runs.

The topology is described inside a fixture, never at import: one process
at a time may load the TPU library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models import decode_step, init_cache, init_lm, pack_params, verify_step

CFG = get_config("smollm-360m")
SLOTS, MAX_LEN = 8, 1024


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2; the persistent compile cache is
    off meanwhile (a compile for a described chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    saved_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    saved_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", saved_cache)
        if saved_log is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = saved_log


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def _custom_calls(jitted, *args) -> int:
    return jitted.lower(*args).compile().as_text().count("tpu_custom_call")


@pytest.mark.parametrize("impl", ["decode", "lookup"])
@pytest.mark.parametrize("g", [5, 4])
def test_fused_kernel_compiles(one_chip, impl, g):
    """The fused kernel at the MLP down projection (960 x 2560), heuristic
    tiles, 8 tokens."""
    m, kg, n = CFG.d_model, CFG.d_ff // g, 8
    fn = jax.jit(
        lambda p, a: ops.segment_mpgemm(p, a, g, impl, fused=True)
    )
    assert _custom_calls(
        fn,
        jax.ShapeDtypeStruct((m, kg), jnp.uint8, sharding=one_chip),
        jax.ShapeDtypeStruct((kg * g, n), jnp.float32, sharding=one_chip),
    ) == 1


@pytest.fixture(scope="module")
def model_shapes(one_chip):
    params = jax.eval_shape(
        lambda k: pack_params(init_lm(k, CFG), CFG), jax.random.PRNGKey(0)
    )
    cache = jax.eval_shape(lambda: init_cache(CFG, SLOTS, MAX_LEN))
    return _on(one_chip, params), _on(one_chip, cache)


@pytest.mark.parametrize("step,width", [("decode", 1), ("verify", 64)])
def test_engine_step_compiles(one_chip, model_shapes, step, width):
    """The engine's decode step (8 slots x 1 token) and verify step (8 x 64)
    over the dense 8 x 1024 cache, every BitLinear on the fused decode
    kernel."""
    params, cache = model_shapes
    fn = decode_step if step == "decode" else verify_step
    jitted = jax.jit(lambda p, c, t: fn(p, t, c, CFG))
    tokens = jax.ShapeDtypeStruct((SLOTS, width), jnp.int32, sharding=one_chip)
    with ops.dispatch_override(impl="decode"):
        assert _custom_calls(jitted, params, cache, tokens) > 0


def _kernel_call(name, one_chip):
    """(jitted caller, argument shapes) of one Pallas kernel at a small
    shape, called inside an outer program as the model calls it."""
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.ternary_decode_gemm import (
        ternary_decode_gemm, ternary_decode_gemm_fused,
    )
    from repro.kernels.vlut_lookup_gemm import (
        vlut_lookup_gemm, vlut_lookup_gemm_fused,
    )

    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    m, kg, g, n = 1024, 128, 5, 512
    if name == "flash_attention":
        q, kv = s((1, 4, 256, 128), jnp.bfloat16), s((1, 2, 256, 128), jnp.bfloat16)
        return jax.jit(lambda q, k, v: flash_attention(q, k, v) * 2), (q, kv, kv)
    fn = {"ternary_decode_gemm": ternary_decode_gemm,
          "ternary_decode_gemm_fused": ternary_decode_gemm_fused,
          "vlut_lookup_gemm": vlut_lookup_gemm,
          "vlut_lookup_gemm_fused": vlut_lookup_gemm_fused}[name]
    tiles = dict(g=g, bm=128, bn=128, bkg=128)
    if name.endswith("_fused"):
        args = (s((m, kg), jnp.uint8), s((kg, g, n), jnp.bfloat16),
                s((1, n), jnp.float32), s((m, 1), jnp.float32))
        return jax.jit(lambda *a: fn(*a, **tiles) * 2.0), args
    args = (s((m, kg), jnp.uint8), s((g, kg, n), jnp.int8))
    return jax.jit(lambda *a: fn(*a, **tiles) + 1), args


@pytest.mark.parametrize("name", [
    "ternary_decode_gemm", "ternary_decode_gemm_fused", "vlut_lookup_gemm",
    "vlut_lookup_gemm_fused", "flash_attention"])
def test_kernel_keeps_its_name(one_chip, name):
    """A kernel's device-trace events carry its HLO instruction name; the
    benchmark's trace reduction finds the mpGeMM kernels by these names,
    so each pallas_call names itself and the compiled program keeps it."""
    import re

    jitted, args = _kernel_call(name, one_chip)
    text = jitted.lower(*args).compile().as_text()
    calls = re.findall(r"%([A-Za-z_\-]+)[.0-9]* = \S+ custom-call\(", text)
    assert calls == [name]


@pytest.mark.parametrize("m,kg", [(8192, 512), (2048, 1664)])
def test_fused_decode_compiles_at_chunk_step_shapes(one_chip, m, kg):
    """The fused decode kernel as internlm2-1.8b's chunk step calls it: 4096
    token rows in bf16 through the MLP up/gate projection (8192 rows, K 2048
    as 408 K-groups padded to 512) and the down projection (2048 rows, K
    8192 as 1636 padded to 1664), g=5, heuristic tiles. Its working set,
    with the int8 token-tile scratch over the whole K extent, fits the
    budget, Mosaic accepts it at that limit, and it keeps its name."""
    import re

    from repro.kernels import autotune
    from repro.kernels.ternary_decode_gemm import ternary_decode_gemm_fused

    g, n = 5, 4096
    t = autotune.heuristic_tiles(g, "decode", fused=True, kg=kg)
    assert t == dict(bm=128, bn=256, bkg=128)
    assert autotune.tile_vmem_bytes(
        g, "decode", **t, fused=True, kg=kg
    ) <= autotune.vmem_budget_bytes()
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    jitted = jax.jit(
        lambda *a: ternary_decode_gemm_fused(*a, g=g, **t) * 2.0)
    text = jitted.lower(
        s((m, kg), jnp.uint8), s((kg, g, n), jnp.bfloat16),
        s((1, n), jnp.float32), s((m, 1), jnp.float32),
    ).compile().as_text()
    calls = re.findall(r"%([A-Za-z_\-]+)[.0-9]* = \S+ custom-call\(", text)
    assert calls == ["ternary_decode_gemm_fused"]
