"""ttft_p90_ms: 90th percentile of the time to first token of the
window's requests, from each one's scheduled arrival; a request that
never got its first token counts as infinite."""
from harness import driver, stats


def read(run):
    t = driver.window_ttft(run.log)
    return 1e3 * stats.percentile(t, 90) if t else None
