"""One general traffic generator, driven by a mix's parameters.

A mix is a JSON file under ``bench/traffic/`` (merged with the cell's own
``traffic`` overrides from ``bench/cells/<workload>.json``). Two arrival
kinds exist:

* ``poisson``: an open loop at ``rate_per_s``. Arrivals are laid out over
  three segments: the lead-in (``lead_in_s``, before the measured window),
  the window (``--seconds``) and the tail (``tail_s``, arrivals that keep
  the load on while the window's own requests finish).
* ``backlog``: ``backlog`` requests all due at time 0 (an offline job).

The sizes and the inter-arrival gaps of every segment are drawn once from
the mix's fixed ``shape_seed``; for an open loop ``--seed`` permutes them
inside their segment, so every seed offers the window the same work in
another order. A backlog keeps the drawn order: a window serves only the
head of the backlog, so its order is its work, and any reordering handed
each seed other prompt lengths (tok_s 530-736 over six seeds on a TPU v5e,
where two runs of one seed agreed within a few per cent). ``--seed``
always draws the prompts' token ids.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Arrival:
    due_s: float          # offset from the start of the lead-in
    prompt: np.ndarray    # (S,) int32
    max_new_tokens: int
    in_window: bool       # due inside the measured window


def _lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        x = rng.lognormal(np.log(float(spec["median"])), float(spec["sigma"]), n)
    elif spec["dist"] == "uniform":
        x = rng.uniform(lo, hi + 1, n)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def _segment_gaps(n: int, length_s: float, rng: np.random.Generator) -> np.ndarray:
    """n exponential gaps scaled so that the n arrivals fill length_s."""
    if n == 0:
        return np.zeros(0)
    gaps = rng.exponential(1.0, n)
    return gaps * (length_s / gaps.sum())


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> list[Arrival]:
    """The arrivals of one run, in due order."""
    shape = np.random.default_rng(int(mix["shape_seed"]))
    order = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 1])
    if mix["arrival"] == "backlog":
        segments = [(int(mix["backlog"]), 0.0, True)]
    elif mix["arrival"] == "poisson":
        rate = float(mix["rate_per_s"])
        lead, tail = float(mix["lead_in_s"]), float(mix["tail_s"])
        segments = [
            (int(round(rate * lead)), lead, False),
            (int(round(rate * seconds)), seconds, True),
            (int(round(rate * tail)), tail, False),
        ]
    else:
        raise ValueError(f"unknown arrival kind {mix['arrival']!r}")
    out: list[Arrival] = []
    start = 0.0
    for n, length_s, in_window in segments:
        prompts = _lengths(mix["prompt"], n, shape)
        outputs = _lengths(mix["output"], n, shape)
        gaps = _segment_gaps(n, length_s, shape)
        if mix["arrival"] == "poisson":
            prompts = prompts[order.permutation(n)]
            outputs = outputs[order.permutation(n)]
            gaps = gaps[order.permutation(n)]
        due = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) if n else gaps
        for i in range(n):
            out.append(Arrival(
                due_s=float(due[i]),
                prompt=order.integers(0, vocab, int(prompts[i]), dtype=np.int32),
                max_new_tokens=int(outputs[i]),
                in_window=in_window,
            ))
        start += length_s
    return out
