"""The ``shared`` block of a mix: prefixes shared by id, each request's own
part after its prefix, and mixes without the block left as they were."""
import hashlib
import json

import numpy as np
import pytest

import tiny
from harness import BENCH
from traffic import generate, prime_prompts


def _digest(arrivals) -> str:
    h = hashlib.sha256()
    for a in arrivals:
        h.update(np.float64(a.due_s).tobytes())
        h.update(a.prompt.tobytes())
        h.update(np.int64(a.max_new_tokens).tobytes())
        h.update(bytes([a.in_window]))
    return h.hexdigest()


def _agent(backlog: int = 8) -> dict:
    mix = json.loads((BENCH / "traffic" / "agent_prefix.json").read_text())
    return dict(mix, backlog=backlog)


def test_mixes_without_shared_prefixes_generate_what_they_did():
    """Digests of the generator's output before shared prefixes existed."""
    off = json.loads((BENCH / "traffic" / "prefill_offline.json").read_text())
    off["backlog"] = 64
    assert _digest(generate(off, 2**31 + 7, 51.0, 92544)) == (
        "3f9edbfabe7fe3dc9a8927d86c1aa7ce0d24ed5d92b8d75264a5f8994332be10")
    assert _digest(generate(dict(tiny.CHAT), 12345, 10.0, 512)) == (
        "9a47b08ed080aa5be480d16b847f358c74d913c785eafdf60a488696ba59df00")
    assert prime_prompts(off, 3, 92544) == []


@pytest.mark.parametrize("mix", [_agent(), dict(tiny.CHAT, shared=tiny.AGENT["shared"])],
                         ids=["backlog", "poisson"])
def test_prefixes_are_shared_by_id_and_primed(mix):
    """Request i starts with prefix i mod P, the prefix that set-up serves,
    and goes on with its own part, of the mix's prompt lengths."""
    seed = 2**31 + 99
    arrivals = generate(mix, seed, 2.0, 92544)
    primes = prime_prompts(mix, seed, 92544)
    spec, own = mix["shared"]["prefix"], mix["prompt"]
    assert len(primes) == 2
    assert all(spec["min"] <= len(p) <= spec["max"] for p in primes)
    for i, a in enumerate(arrivals):
        p = primes[i % 2]
        assert np.array_equal(a.prompt[:len(p)], p)
        assert own["min"] <= len(a.prompt) - len(p) <= own["max"]
    rest = [a.prompt[len(primes[i % 2]):] for i, a in enumerate(arrivals)]
    assert not any(np.array_equal(x[:8], y[:8])
                   for i, x in enumerate(rest) for y in rest[i + 1:])


def test_backlog_sizes_come_from_the_mix_and_ids_from_the_seed():
    mix = _agent(backlog=6)
    a = generate(mix, 2**31 + 12345, 51.0, 92544)
    b = generate(mix, 2**31 + 12345, 51.0, 92544)
    c = generate(mix, 11, 51.0, 92544)
    assert _digest(a) == _digest(b)
    assert [len(x.prompt) for x in a] == [len(x.prompt) for x in c]
    assert [x.max_new_tokens for x in a] == [x.max_new_tokens for x in c]
    assert {x.due_s for x in a} == {0.0} and all(x.in_window for x in a)
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


def test_longest_request_fits_the_cell():
    """Every request of the committed mix fits the cell's max_len."""
    cell = json.loads((BENCH / "cells" / "internlm2-1.8b.agent_prefix.json")
                      .read_text())
    arrivals = generate(_agent(backlog=256), 3, 51.0, 92544)
    assert max(len(a.prompt) + a.max_new_tokens - 1 for a in arrivals) <= (
        cell["engine"]["max_len"])
