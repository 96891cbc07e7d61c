"""Benchmark harness — one module per paper table/figure. Prints
``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run [--full]

Mapping to the paper:
  gemm_bench       Fig. 9 / Fig. 4    mpGeMM kernel vs baselines
  prefill_bench    Fig. 10 / Fig. 13  e2e prefill tokens/s
  decode_bench     Fig. 11 / §5.3.2   parallel decode + continuous batching
  spec_bench       §5.3 multi-token   speculative decoding: K×batch sweep +
                                      scalar-vs-vector verify GeMMs
  crossover        ROADMAP item 1     M × impl winner table (CI-gated)
  breakdown_bench  Tables 1 & 5       stage time breakdown
  ablation_bench   Fig. 12 / §5.5     technique ablation + tile sweep
  packing_bench    Table 3 / §3.3     bpw compactness & shape support
  roofline_report  §Roofline          dry-run roofline table
"""
import argparse
import sys
import traceback

from repro.launch.compile_cache import use_compile_cache


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="larger shapes/sweeps")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    quick = not args.full

    from . import (
        ablation_bench,
        breakdown_bench,
        crossover,
        decode_bench,
        gemm_bench,
        packing_bench,
        prefill_bench,
        roofline_report,
        spec_bench,
    )

    suites = {
        "gemm": gemm_bench,
        "crossover": crossover,
        "prefill": prefill_bench,
        "decode": decode_bench,
        "spec": spec_bench,
        "breakdown": breakdown_bench,
        "ablation": ablation_bench,
        "packing": packing_bench,
        "roofline": roofline_report,
    }
    failures = 0
    for name, mod in suites.items():
        if args.only and name != args.only:
            continue
        print(f"# === {name} ===", flush=True)
        try:
            mod.run(quick=quick)
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
        finally:
            # flush any rows the suite buffered but didn't write itself
            from . import common

            common.write_results(name)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
