"""A cell small enough for the CPU: the structure of the real cells at toy
widths."""
from __future__ import annotations

import json

from harness import BENCH, Cell

CONFIG = {
    "name": "tiny", "reference": "dense_gqa", "num_hidden_layers": 2,
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 128, "vocab_size": 512,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "dtype": "bfloat16",
}
ENGINE = {"max_slots": 4, "max_len": 128, "prefill_chunk": 16, "page_size": 16}
OFFLINE = {
    "arrival": "backlog", "backlog": 3000,
    "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.6, "min": 8, "max": 64},
    "output": {"dist": "lognormal", "median": 6, "sigma": 1.0, "min": 2, "max": 16},
    "lead_in_s": 0.3, "shape_seed": 5,
}
CHAT = {
    "arrival": "poisson", "rate_per_s": 20.0,
    "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.8, "min": 4, "max": 64},
    "output": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 2, "max": 16},
    "lead_in_s": 0.3, "tail_s": 5.0, "drain_cap_s": 20.0, "shape_seed": 5,
}
CHECK = {"tokens": 40, "min_tokens": 10, "min_requests": 4, "max_requests": 8,
         "gap_limit": 0.1}
#: the open-loop cell's end-to-end metric, which no committed cell reports yet
TTFT = {"name": "ttft_p95_ms", "unit": "ms", "better": "lower",
        "source": "host_clock"}


def cell(mix: dict | None = None, **config) -> Cell:
    """The tiny model under the offline backlog by default (the committed
    cell's kind), or under an open loop with ``mix=CHAT``."""
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    mix = dict(mix or OFFLINE)
    e2e = [m for m in bench["end_to_end"] if m["name"] == "setup_s"]
    if mix["arrival"] == "backlog":
        e2e += [m for m in bench["end_to_end"] if m["name"] == "tok_s"]
    else:
        e2e.append(TTFT)
    reported = {m["name"] for m in e2e}
    return Cell(
        name=f"tiny.{'prefill_offline' if mix['arrival'] == 'backlog' else 'chat'}",
        config={**CONFIG, **config}, mix=mix, engine=dict(ENGINE),
        check=dict(CHECK), end_to_end=e2e,
        per_layer=[m for m in bench["per_layer"] if m["moves"] in reported],
    )
