"""decode_step_ms: mean device-step time (``StepReport.compute_s``) of the
window's steps that carry no prefill rows."""
from harness import driver, stats


def read(run):
    steps = [s for s in driver.window_steps(run.log)
             if s.prefill_tokens == 0]
    return 1e3 * stats.mean([s.compute_s for s in steps]) if steps else None
