"""door_wait_p90_ms: 90th percentile over the window's requests of the
time each waited in the gateway's door queue before its first token (the
flight recorder's ``door_queued`` segments).  None where no recorder was
attached to the run."""
from harness import stats


def read(run):
    prog = getattr(run.log, "program", None)
    if not prog or not prog["ttft_segs"]:
        return None
    return 1e3 * stats.percentile(
        [s.get("door_queued", 0.0) for s in prog["ttft_segs"].values()], 90)
