"""sched_wait_p90_ms: 90th percentile over the window's requests of the
time each waited in the scheduler's queue, or between its prefill chunks
for step budget, before its first token (the flight recorder's
``sched_queued`` segments).  None where no recorder was attached."""
from harness import stats


def read(run):
    prog = getattr(run.log, "program", None)
    if not prog or not prog["ttft_segs"]:
        return None
    return 1e3 * stats.percentile(
        [s.get("sched_queued", 0.0) for s in prog["ttft_segs"].values()],
        90)
