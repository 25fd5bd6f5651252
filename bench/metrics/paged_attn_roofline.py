"""paged_attn_roofline: the paged-attention kernel's share of its
roofline in the traced window.  For each traced step, the least time the
chip could take for the attention the step needs (its FLOPs over the
bf16 peak, or its bytes over HBM bandwidth, whichever is larger; counted
from the live lanes and their contexts, not from what the kernel walks),
summed, over the summed device time of the kernel's events."""
from harness import driver


def read(run):
    tr = run.log.trace
    if tr is None or not tr["kernel_s"].get("paged_attention"):
        return None
    d, pk, m = run.dims, run.peaks, run.model
    least = 0.0
    for s in driver.traced_steps(run.log):
        flops = sum(m.attn_flops(d, c) for c in s.dec_ctx)
        nbytes = sum(m.attn_bytes(d, c, 1, run.kv_bytes)
                     for c in s.dec_ctx)
        for start, n in s.pre:
            flops += sum(m.attn_flops(d, start + i + 1)
                         for i in range(n))
            nbytes += m.attn_bytes(d, start + n, n, run.kv_bytes)
        least += max(flops / pk["bf16_flops_per_s"],
                     nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / tr["kernel_s"]["paged_attention"]
