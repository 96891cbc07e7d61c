"""Median host time from one step's sampled tokens reaching the host to
the next chunk step's launch, in ms (offline cells): the program's own
``launch_gap_us`` on its ``engine_step/chunk`` spans. The median, because
the profiler's stop, inside every traced window, lands in one step's gap
and takes seconds. None where the spans carry no such count."""
import statistics


def read(run):
    gaps = [s["args"]["launch_gap_us"] for s in run.spans
            if s["name"] == "engine_step/chunk" and "launch_gap_us" in s["args"]]
    if not gaps:
        return None
    return statistics.median(gaps) / 1e3
