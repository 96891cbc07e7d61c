"""The plain reference against the served path (chunked prefill through the
paged cache, decode rows riding in chunk steps, plain decode steps), with
the Pallas kernels interpreted on the CPU."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import harness
import tiny
from seeds import model_key

SEED = 2**31 + 77


def _serve(cell, seed, prompts, new):
    from repro.serve.engine import Request

    engine, sched = harness.build(cell, seed, trace=False, interpret=True)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=new)
            for i, p in enumerate(prompts)]
    sched.submit(reqs)
    kinds = []
    while sched.queue or engine.has_work:
        kinds.append("chunk" if engine.prefilling else "decode")
        sched.tick()
    assert {"chunk", "decode"} <= set(kinds)
    return reqs


@pytest.fixture(scope="module")
def served():
    cell = tiny.cell()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, n, dtype=np.int32) for n in (40, 9, 3)]
    return cell, _serve(cell, SEED, prompts, new=6)


def test_reference_weights_are_the_served_weights():
    from repro.models import init_lm, pack_params

    cell = tiny.cell()
    mc = harness.model_config(cell.config)
    params = pack_params(init_lm(model_key(SEED), mc), mc)
    mod = harness.reference_module(cell.config)
    stage = jax.random.fold_in(model_key(SEED), 100)
    keys = jax.random.split(jax.random.fold_in(stage, 0), 2)
    ref = mod._layer_weights(keys[1], cell.config)
    pw = params["stages"][0]["b0"]["mixer"]["wq"]["pw"]
    t, scale = ref["q"]
    assert np.array_equal(np.asarray(pw.unpack()[1]), np.asarray(t.T, np.int8))
    np.testing.assert_allclose(np.asarray(pw.scale[1]), np.asarray(scale), rtol=1e-6)
    head = np.asarray(params["head"]["w"], np.float32)
    np.testing.assert_array_equal(head, np.asarray(
        mod.Reference(cell.config, model_key(SEED))._head()))


def test_served_tokens_are_the_reference_best(served):
    cell, reqs = served
    items = [(r.prompt, r.generated) for r in reqs]
    gaps = harness.served_gaps(cell.config, SEED, items, cell.engine["max_len"])
    assert sum(len(g) for g in gaps) == 18
    assert max(g.max() for g in gaps) < 0.05


def test_reference_of_other_weights_disagrees(served):
    cell, reqs = served
    items = [(r.prompt, r.generated) for r in reqs]
    gaps = harness.served_gaps(cell.config, SEED + 1, items, cell.engine["max_len"])
    assert max(g.max() for g in gaps) > 0.5
