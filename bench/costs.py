"""Operations and bytes that the served model's work needs, from shapes, and
the table of chip peaks they are measured against.

Every roofline share and MFU the benchmark reports is computed here or in
the configuration's kind module (``bench/kinds/<kind>.py``, found by
``kind``), so a change to the program cannot change the yardstick.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
PEAKS_FILE = BENCH / "peaks.json"


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


def kind(cfg: dict, bench: Path = BENCH):
    """The module of a configuration's ``kind``: ``bench/kinds/<kind>.py``,
    which builds the program's ModelConfig and counts the model's
    operations. A kind without its file is an error, with no fallback."""
    path = bench / "kinds" / f"{cfg['kind']}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"configuration {cfg.get('name', '?')!r} is of kind "
            f"{cfg['kind']!r}, and {path} does not exist")
    spec = importlib.util.spec_from_file_location(f"kind_{cfg['kind']}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
