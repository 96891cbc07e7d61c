"""Serving launcher: continuous batching over synthetic requests.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
      --requests 32 --max-new 16

Add --spec-k N for speculative decoding (n-gram drafter, N draft tokens per
batched verify step); the summary line then reports acceptance and tok/step.
--spec-adaptive adapts each slot's draft length to its acceptance EWMA
(cold slots skip drafting entirely), adding mean_k and skip-rate columns.
--spec-tree B1,B2,... verifies a draft *tree* (top-B candidates at each of
the first depths) in one flattened pass, adding a nodes/step column.
--prefill-chunk N switches admission to chunked prefill: each tick runs one
batched mixed step carrying every prefilling slot's next N-token chunk plus
the decode rows, so the Vec-LUT kernels see parallel tokens every tick;
--token-budget caps the real tokens scheduled per tick.
--page-size N switches the KV cache to the paged layout (block tables over a
physical page pool, serve.paging) with radix prompt-prefix sharing; --kv-pages
sizes the pool (out-of-pages requests queue instead of rejecting) and
--offload-pages bounds the host-RAM tier for cold prefix pages.

Observability (repro.obs) is on by default (--no-obs disables): the periodic
stats line (--stats-interval S) and the summary's latency/acceptance columns
read from the metrics registry — the single export surface synced from the
engine's counters — and --metrics-out/--trace-out dump the Prometheus-style
JSON metrics snapshot and a Perfetto-loadable trace on exit.
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import encdec_init, init_lm, pack_params
from repro.obs import ObsConfig
from repro.serve import ContinuousBatchingScheduler, Engine, Request


def run(argv: list[str] | None = None):
    """Serve synthetic requests as the command line `argv` says (None reads
    sys.argv), print the summary line, and return (engine, stats)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32,
                    help="longest prompt, in tokens")
    ap.add_argument("--min-prompt-len", type=int, default=4,
                    help="shortest prompt, in tokens")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--no-pack", action="store_true",
                    help="serve the QAT (unpacked) weights for comparison")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding draft length (0 = off; "
                         "n-gram prompt-lookup drafter)")
    ap.add_argument("--spec-adaptive", action="store_true",
                    help="per-slot adaptive draft length from the running "
                         "acceptance rate (cold slots skip drafting)")
    ap.add_argument("--spec-tree", default="",
                    help="comma-separated branching factors (e.g. '2,2') for "
                         "tree-structured multi-candidate verification")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: consume prompts N tokens per tick "
                         "in one batched mixed prefill/decode step "
                         "(0 = whole-prompt admission prefill)")
    ap.add_argument("--token-budget", type=int, default=0,
                    help="cap on real tokens scheduled per chunked tick "
                         "(0 = unlimited; needs --prefill-chunk)")
    ap.add_argument("--page-size", type=int, default=0,
                    help="paged KV: tokens per page (0 = dense slot cache); "
                         "enables radix prompt-prefix sharing")
    ap.add_argument("--kv-pages", type=int, default=0,
                    help="paged KV pool size incl. the null page "
                         "(0 = auto: slots*max_len/page_size + 1; "
                         "needs --page-size)")
    ap.add_argument("--offload-pages", type=int, default=0,
                    help="host-RAM offload tier capacity in pages for cold "
                         "prefix pages (0 = drop instead; needs --page-size)")
    ap.add_argument("--no-obs", action="store_true",
                    help="disable the observability layer (metrics + trace)")
    ap.add_argument("--stats-interval", type=float, default=0.0,
                    help="print a registry-backed stats line every S seconds "
                         "while serving (0 = off)")
    ap.add_argument("--metrics-out", default="",
                    help="write the JSON metrics snapshot here on exit")
    ap.add_argument("--trace-out", default="",
                    help="write the Perfetto trace_event JSON here on exit")
    args = ap.parse_args(argv)
    if not 1 <= args.min_prompt_len <= args.prompt_len:
        ap.error("--min-prompt-len must be in [1, --prompt-len]")
    if (args.spec_adaptive or args.spec_tree) and not args.spec_k:
        ap.error("--spec-adaptive/--spec-tree require --spec-k N (N >= 1)")
    if args.token_budget and not args.prefill_chunk:
        ap.error("--token-budget requires --prefill-chunk N (N >= 1)")
    if (args.kv_pages or args.offload_pages) and not args.page_size:
        ap.error("--kv-pages/--offload-pages require --page-size N (N >= 1)")
    if args.spec_adaptive and args.spec_tree:
        ap.error("--spec-tree and --spec-adaptive are mutually exclusive")
    if args.no_obs and (args.stats_interval or args.metrics_out
                        or args.trace_out):
        ap.error("--no-obs conflicts with --stats-interval/--metrics-out/"
                 "--trace-out")

    cfg = get_config(args.arch, smoke=args.smoke)
    init = encdec_init if cfg.family == "encdec" else init_lm
    params = init(jax.random.PRNGKey(0), cfg)
    if not args.no_pack:
        params = pack_params(params, cfg)

    spec = None
    if args.spec_k:
        from repro.spec import SpecConfig

        tree = (
            tuple(int(x) for x in args.spec_tree.split(","))
            if args.spec_tree else None
        )
        spec = SpecConfig(k=args.spec_k, adaptive_k=args.spec_adaptive,
                          tree=tree)
    obs_cfg = None if args.no_obs else ObsConfig(
        metrics_out=args.metrics_out or None,
        trace_out=args.trace_out or None,
    )
    paged = None
    if args.page_size:
        from repro.serve import PagedKVConfig

        paged = PagedKVConfig(
            page_size=args.page_size, n_pages=args.kv_pages,
            host_offload_pages=args.offload_pages,
        )
    engine = Engine(
        params, cfg, max_slots=args.slots, max_len=args.max_len,
        temperature=args.temperature, spec=spec,
        prefill_chunk=args.prefill_chunk, token_budget=args.token_budget,
        paged_kv=paged, obs=obs_cfg,
    )
    sched = ContinuousBatchingScheduler(engine)
    rng = np.random.default_rng(0)
    reqs = [
        Request(
            rid=i,
            prompt=rng.integers(
                0, cfg.vocab,
                size=rng.integers(args.min_prompt_len, args.prompt_len + 1),
            ).astype(np.int32),
            max_new_tokens=args.max_new,
        )
        for i in range(args.requests)
    ]
    sched.submit(reqs)
    obs = engine.obs
    t_serve = time.perf_counter()
    if args.stats_interval:
        # registry-backed periodic logging: tick manually, report from the
        # metric objects (the gauges/counters obs.on_tick syncs each tick)
        next_at = time.perf_counter() + args.stats_interval
        while sched.queue or engine.has_work:
            sched.tick()
            if time.perf_counter() >= next_at:
                print(f"[obs] {obs.stats_line()}", flush=True)
                next_at = time.perf_counter() + args.stats_interval
    stats = sched.run_to_completion()
    # the manual tick loop's work lands in this run's token counters, so its
    # wall time must land in the run's clock too or tok/s is inflated
    stats.wall_s = time.perf_counter() - t_serve
    spec_cols = (
        f" accept={stats.acceptance_rate:.2f} "
        f"tok/step={stats.decode_tokens_per_step:.2f}"
        if stats.spec_steps else ""
    )
    if stats.spec_steps and args.spec_adaptive:
        spec_cols += (
            f" mean_k={stats.mean_draft_k:.2f} skip={stats.skip_rate:.2f}"
        )
    if stats.spec_steps and args.spec_tree:
        spec_cols += f" nodes/step={stats.nodes_per_step:.1f}"
    rej_cols = f" rejected={stats.rejected}" if stats.rejected else ""
    paged_cols = (
        f" pages={engine.pager.free_pages}/{engine.pager.total_pages}"
        f" prefix_hit={stats.prefix_hit_tokens}tok"
        f"/{stats.prefix_hit_requests}req"
        if engine.pager is not None else ""
    )
    chunk_cols = (
        f" chunk_steps={stats.chunk_steps} pad={stats.prefill_pad_tokens}"
        if args.prefill_chunk else ""
    )
    # latency columns come from the registry histograms when obs is on (the
    # single latency surface — p50/p95 interpolated from the bucket counts);
    # the --no-obs fallback keeps the ad-hoc median over ServeStats events.
    # Either way: no TTFT events → omit the column, never a fake 0.
    if obs.enabled and obs.h_ttft.count:
        ttft_col = (
            f" ttft_p50={1e3 * obs.h_ttft.percentile(0.5):.1f}ms"
            f" p95={1e3 * obs.h_ttft.percentile(0.95):.1f}ms"
        )
        if obs.h_tpot.count:
            ttft_col += f" tpot_p50={1e3 * obs.h_tpot.percentile(0.5):.1f}ms"
        if obs.s_eff_m.count:
            ttft_col += f" eff_m={obs.s_eff_m.mean:.1f}"
    else:
        ttft_col = (
            f" ttft_median={1e3 * float(np.median(stats.ttft_s)):.1f} ms"
            if stats.ttft_s else ""
        )
    print(
        f"completed={stats.completed}/{args.requests} "
        f"throughput={stats.throughput_tok_s:.1f} tok/s "
        f"(prefill {stats.prefill_tok_s:.1f}, decode {stats.decode_tok_s:.1f})"
        f"{ttft_col}{spec_cols}{chunk_cols}{paged_cols}{rej_cols}"
    )
    for path in obs.finalize():
        print(f"wrote {path}")
    return engine, stats


def main():
    use_compile_cache()
    run()


if __name__ == "__main__":
    main()
