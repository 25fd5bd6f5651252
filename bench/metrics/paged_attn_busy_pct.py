"""paged_attn_busy_pct: the paged-attention kernel's device time over the
device's busy time, in the traced window."""


def read(run):
    tr = run.log.trace
    if tr is None or not tr["busy_s"]:
        return None
    k = tr["kernel_s"].get("paged_attention", 0.0)
    return 100.0 * k / tr["busy_s"] if k else None
