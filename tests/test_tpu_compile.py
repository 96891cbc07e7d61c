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
