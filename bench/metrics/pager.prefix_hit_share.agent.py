"""Share of the window's prompt tokens that the pager served from shared
prefix pages, in per cent: the engine's own counters, read as the window
opens and closes (``harness.token_counts``, shared tokens served with
their request's prefill), as hits / (hits + prompt tokens prefilled).
None where the window prefilled and hit nothing."""


def read(run):
    hits, prefilled = (run.counts_close[k] - run.counts_open[k]
                       for k in ("prefix_hit_tokens", "prefill_tokens"))
    if hits + prefilled == 0:
        return None
    return 100.0 * hits / (hits + prefilled)
