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
    mc = cell.kind.model_config(cell.config)
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
    gaps = harness.served_gaps(cell.config, SEED, items)
    assert sum(len(g) for g in gaps) == 18
    assert max(g.max() for g in gaps) < 0.05


def test_reference_of_other_weights_disagrees(served):
    cell, reqs = served
    items = [(r.prompt, r.generated) for r in reqs]
    gaps = harness.served_gaps(cell.config, SEED + 1, items)
    assert max(g.max() for g in gaps) > 0.5


def test_blocked_attention_equals_unblocked():
    """A layer computed in blocks of query rows gives what one block over
    the whole sequence gives, at float32 round-off."""
    cell = tiny.cell()
    mod = harness.reference_module(cell.config)
    w = mod._layer_weights(jax.random.PRNGKey(3), cell.config)
    x = jax.random.normal(jax.random.PRNGKey(4), (80, 64), jnp.float32)
    whole = mod._layer(w, x, cfg=cell.config, lowp=None, block=80)
    for block in (16, 32, 48):
        blocked = mod._layer(w, x, cfg=cell.config, lowp=None, block=block)
        np.testing.assert_allclose(np.asarray(blocked), np.asarray(whole),
                                   rtol=1e-5, atol=1e-5)


def test_reference_pads_to_the_longest_sequence_in_blocks(monkeypatch):
    """Padding to a whole query block past the longest sequence changes no
    logit: causal rows never see it."""
    cell = tiny.cell()
    mod = harness.reference_module(cell.config)
    rng = np.random.default_rng(1)
    seqs = [(rng.integers(0, 512, n).astype(np.int32), np.arange(n - 4, n))
            for n in (40, 21)]
    ref = mod.Reference(cell.config, model_key(SEED))
    at_1024 = ref.logits(seqs)
    monkeypatch.setattr(mod, "QUERY_BLOCK", 16)
    at_16 = mod.Reference(cell.config, model_key(SEED)).logits(seqs)
    for a, b in zip(at_1024, at_16):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_ternary_product_is_exact_in_one_bfloat16_pass():
    """Integers of at most 127 times ternaries, summed over K = 8192 (the
    configurations' widest K), give the float32 product bit for bit."""
    cell = tiny.cell()
    mod = harness.reference_module(cell.config)
    rng = np.random.default_rng(5)
    xq = jnp.asarray(rng.integers(-127, 128, (64, 8192)), jnp.float32)
    xq = xq.at[0].set(127.0)
    t = jnp.asarray(rng.integers(-1, 2, (8192, 96)), jnp.float32)
    t = t.at[:, 0].set(1.0)
    exact = np.asarray(xq, np.int64) @ np.asarray(t, np.int64)
    got = np.asarray(mod._exact_product(xq, t))
    assert got[0, 0] == 127 * 8192
    np.testing.assert_array_equal(got, exact.astype(np.float32))
    with pytest.raises(ValueError, match="would round"):
        mod._exact_product(jnp.zeros((1, 2 ** 18)), jnp.zeros((2 ** 18, 1)))
