"""step_host_ms: mean host time of an engine step in the window: the wall
time of ``ServingEngine.step()`` less the program's device-step time
(``compute_s``) and any compile; i.e. planning, packing, block tables,
the transfer in and the argmax fetch."""
from harness import driver, stats


def read(run):
    steps = driver.window_steps(run.log)
    if not steps:
        return None
    return 1e3 * stats.mean([s.t1 - s.t0 - s.compute_s - s.compile_s
                             for s in steps])
