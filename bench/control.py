#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

    python3 bench/control.py --workload <name> --seconds <s> --seeds 1 2 3 ...

For each seed, in one process: one run of the cell as the benchmark makes
it (a short window), then on the requests its check samples

* the program's reading: the widest gap by which a served token's logit
  lies below the plain float32 reference's best (the number ``correct``
  compares), and
* the control's reading: the same gap for the tokens that the reference
  computed in float8 (e4m3, the precision below the configuration's
  bfloat16) puts first at each of those positions.

The limit lies above the largest program reading and below the smallest
control reading. Prints one JSON line per seed and a summary line. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def readings(cell, seed: int, seconds: float, *, interpret: bool = False,
             lowp=None) -> dict:
    """Program and control readings of one seed."""
    import jax.numpy as jnp

    lowp = jnp.float8_e4m3fn if lowp is None else lowp
    counter = harness.CompileCounter()
    engine, sched = harness.build(cell, seed, trace=False, interpret=interpret)
    harness.warm_up(cell, engine, sched)
    run = harness.Run(cell, seed, seconds, False)
    harness.serve_window(run, engine, sched, counter)
    del engine, sched
    gc.collect()
    picked = harness._sample_for_check(run)
    items = [(t.req.prompt, list(t.req.generated)) for t in picked]
    length = int(cell.engine["max_len"])
    program = harness.served_gaps(cell.config, seed, items, length)
    control = harness.served_gaps(cell.config, seed, items, length,
                                  lowp=lowp, control=True)
    return {
        "seed": seed, "requests": len(items),
        "tokens": int(sum(len(g) for g in program)),
        "program_widest_gap": float(max(g.max() for g in program)),
        "control_widest_gap": float(max(g.max() for g in control)),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    harness.pin_environment()
    cell = harness.load_cell(args.workload)
    harness.use_compile_cache()
    import jax

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("the control readings are taken on a TPU")
    rows = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        row = readings(cell, seed, args.seconds)
        row["wall_s"] = time.perf_counter() - t0
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({
        "workload": args.workload, "seeds": len(rows),
        "lower_reading": max(r["program_widest_gap"] for r in rows),
        "upper_reading": min(r["control_widest_gap"] for r in rows),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
