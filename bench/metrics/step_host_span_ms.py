"""step_host_span_ms: mean over the window's steps of the program's own
host spans of a step, ``step.plan`` + ``step.pack`` + ``step.put`` +
``step.fetch`` + ``step.commit`` (the in-program counterpart of
``step_host_ms``).  None where no recorder was attached."""
from harness import driver, spans, stats


def read(run):
    if not getattr(run.log, "program", None):
        return None
    steps = driver.window_steps(run.log)
    if not steps:
        return None
    return 1e3 * stats.mean([
        sum(d for name, _, d in spans.step_spans(run.log, s)
            if name in spans.HOST_STEP_SPANS) for s in steps])
