"""itl_p99_ms: 99th percentile of the same pool of gaps as itl_mean_ms;
its tail is the steps that carry prefill chunks."""
from harness import driver, stats


def read(run):
    g = driver.window_gaps(run.log)
    return 1e3 * stats.percentile(g, 99) if len(g) >= 1000 else None
