"""Operations and bytes that the served model's work needs, from shapes, and
the table of chip peaks they are measured against.

Every roofline share and MFU the benchmark reports is computed here, so a
change to the program cannot change the yardstick.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str) -> dict:
    """The peaks of one chip, by JAX's ``device_kind``. An unknown device is
    an error: a share of a guessed peak would be no measurement."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]


def mpgemm_cost(m: int, kg: int, g: int, n: int, act_bytes: int,
                out_bytes: int) -> tuple[float, float]:
    """(int8 operations, HBM bytes) of one ternary mpGeMM call.

    The weight is (m, kg*g) ternary, packed one byte per g weights (1.6 bits
    per weight at g=5); n tokens of activations come in at ``act_bytes`` a
    value and leave at ``out_bytes``; per-token and per-row f32 scales ride
    along. Shapes are those the kernel was given, padding included."""
    k = kg * g
    ops = 2.0 * m * k * n
    nbytes = m * kg + k * n * act_bytes + m * n * out_bytes + 4 * (m + n)
    return ops, nbytes


def least_time(ops: float, nbytes: float, peak_ops: float,
               peak_bytes_per_s: float) -> float:
    """Seconds the chip needs at least: bound by compute or by HBM."""
    return max(ops / peak_ops, nbytes / peak_bytes_per_s)


def linear_shapes(cfg: dict) -> list[tuple[int, int]]:
    """(out, in) of every ternary linear in one decoder layer."""
    d, h, kv, hd, f = (cfg["hidden_size"], cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], cfg["head_dim"],
                       cfg["intermediate_size"])
    return [(h * hd, d), (kv * hd, d), (kv * hd, d), (d, h * hd),
            (f, d), (f, d), (d, f)]


def mpgemm_ops_per_token(cfg: dict) -> float:
    """Ternary-matmul operations one token needs through the whole stack."""
    per_layer = sum(2.0 * m * k for m, k in linear_shapes(cfg))
    return per_layer * cfg["num_hidden_layers"]


def attention_ops(cfg: dict, keys: int) -> float:
    """Operations of one query token attending ``keys`` cached positions in
    every layer (scores and the weighted sum of values)."""
    return (4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * keys
            * cfg["num_hidden_layers"])


def head_ops(cfg: dict) -> float:
    """Operations of one row of output logits."""
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
