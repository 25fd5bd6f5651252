"""kv_pool_busy_pct: device self time under the fused step's ``kv_pool``
and ``kv_scatter`` scopes (each layer's K/V pool sliced out of the
stacked pool, relaid out, scattered into and written back) over the
device's busy time, in the traced window.  Needs the scoped reduction
(``harness.spans``); None where the trace was reduced without it."""


def read(run):
    tr = run.log.trace
    if tr is None or not tr["busy_s"] or not tr.get("scope_s"):
        return None
    s = tr["scope_s"]
    return 100.0 * (s.get("kv_pool", 0.0) + s.get("kv_scatter", 0.0)) \
        / tr["busy_s"]
