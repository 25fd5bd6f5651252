"""setup_s: process start until the window opens (JAX start-up, weights,
page pools, fused-step compiles or cache loads, warm-up traffic)."""


def read(run):
    return run.setup["setup_s"]
