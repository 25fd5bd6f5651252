"""itl_mean_ms: mean gap between consecutive output tokens, pooled over
every gap that ends inside the window."""
from harness import driver, stats


def read(run):
    g = driver.window_gaps(run.log)
    return 1e3 * stats.mean(g) if g else None
