"""prefill_ms_per_token: device-step time of the window's steps that carry
prefill rows, over the prompt tokens they prefilled."""
from harness import driver


def read(run):
    steps = [s for s in driver.window_steps(run.log) if s.prefill_tokens]
    n = sum(s.prefill_tokens for s in steps)
    return 1e3 * sum(s.compute_s for s in steps) / n if n else None
