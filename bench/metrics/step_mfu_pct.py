"""step_mfu_pct: model FLOPs of the rows the traced steps computed (2 x
the layers' matmul weights per row, attention over each row's context,
2 d V per logit row; prefix-cached prompt tokens are not computed and do
not count) over the traced window's length times the chip's bf16 peak."""
from harness import driver


def read(run):
    tr = run.log.trace
    if tr is None:
        return None
    d, m = run.dims, run.model
    flops = 0.0
    for s in driver.traced_steps(run.log):
        flops += sum(m.row_flops(d, c) for c in s.dec_ctx)
        flops += sum(m.row_flops(d, start + i + 1)
                     for start, n in s.pre for i in range(n))
        flops += (len(s.dec_ctx) + len(s.pre)) * m.logit_flops(d)
    return 100.0 * flops / (tr["window_s"] * run.peaks["bf16_flops_per_s"])
