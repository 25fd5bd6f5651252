"""The reduction from a device trace to busy time, kernel time and the
breakdown, on a small recorded trace."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from harness import profile  # noqa: E402

KERNELS = {"paged_attention": "paged_attention_mixed"}


def _synthetic():
    ms = 1_000_000
    ops = [("fusion.1", 0, 2), ("paged_attention_mixed.3", 2, 5),
           ("fusion.2", 6, 7), ("paged_attention_mixed.3", 12, 15),
           ("outside", 30, 40)]
    ev = [{"kind": "op", "plane": "/device:TPU:0", "name": n,
           "start": s * ms, "dur": (t - s) * ms} for n, s, t in ops]
    ev += [{"kind": "span", "name": profile.WINDOW_SPAN, "start": 0,
            "dur": 20 * ms},
           {"kind": "span", "name": "engine.step", "start": 0,
            "dur": 8 * ms},
           {"kind": "span", "name": "gateway.finalize", "start": 8 * ms,
            "dur": 2 * ms},
           {"kind": "span", "name": "driver.wait_arrival",
            "start": 10 * ms, "dur": 2 * ms},
           {"kind": "span", "name": "engine.step", "start": 12 * ms,
            "dur": 8 * ms}]
    return ev


def test_reduce_by_hand():
    r = profile.reduce(_synthetic(), KERNELS)
    assert r["window_s"] == pytest.approx(0.020)
    # busy: [0,5] + [6,7] + [12,15] = 9 ms; the op past the window is cut
    assert r["busy_s"] == pytest.approx(0.009)
    assert r["kernel_s"]["paged_attention"] == pytest.approx(0.006)
    assert r["device_ops"][0] == ["paged_attention_mixed.3",
                                  pytest.approx(0.006)]
    # idle: [7,12] (midpoint in finalize), [15,20] and [5,6] (in steps),
    # longest first
    assert [(n, round(s * 1e3, 6)) for n, s in r["idle_gaps"]] == [
        ("gateway.finalize", 5.0), ("engine.step", 5.0),
        ("engine.step", 1.0)]


def test_reduce_without_window_or_ops_is_nothing():
    ev = [e for e in _synthetic() if e["name"] != profile.WINDOW_SPAN]
    assert profile.reduce(ev, KERNELS) is None
    ev = [e for e in _synthetic() if e["kind"] == "span"]
    assert profile.reduce(ev, KERNELS) is None


RECORDED = HERE / "data" / "trace_slice_v5e.json"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_chip_trace():
    """A 60 ms slice of a traced stablelm_3b.chat window on one v5e: the
    kernel's events are found and every share stays inside 0-100%."""
    events = json.loads(RECORDED.read_text())
    r = profile.reduce(events, KERNELS)
    assert r is not None
    assert 0 < r["busy_s"] <= r["window_s"] + 1e-9
    assert 0 < r["kernel_s"]["paged_attention"] <= r["busy_s"]
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
