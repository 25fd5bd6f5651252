"""The wall-clock driver's warm-up and window, over a stand-in engine: the
warm-up opens with its burst, ends only once no new executable has
appeared for ``quiet_s`` seconds, and the window's arrivals start when
the window opens and do not depend on how long the warm-up took."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import driver, traffic  # noqa: E402


class FakeEngine:
    """Serves every submitted request in one step; ``new_keys`` steps each
    add one executable to ``runtime.compile_s``, as a new bucket would."""

    def __init__(self, new_keys: int):
        self.runtime = SimpleNamespace(compile_s={})
        self.kv = SimpleNamespace(reserved_pages=0)
        self.live, self.new_keys = [], new_keys

    def has_work(self):
        return bool(self.live)

    def step(self):
        if self.new_keys:
            self.new_keys -= 1
            self.runtime.compile_s[len(self.runtime.compile_s)] = 0.0
        done, self.live = self.live, []
        return SimpleNamespace(kind="decode", decoded=[], compute_s=0.0,
                               prefill_tokens=0, preempted=[], chunks=[])


class FakeGateway:
    def __init__(self, eng):
        self.eng = eng

    def offer(self, req, now):
        self.eng.live.append(req)

    def dispatch(self, now):
        pass

    def finalize(self, tenant, eng, rep, t1, t0):
        pass


MIX = {"rate_per_s": 40.0, "block": 20, "drain_s": 0.1,
       "prompt": {"median": 8, "sigma": 0.3, "min": 4, "max": 16},
       "output": {"median": 4, "sigma": 0.3, "min": 2, "max": 8},
       "warm": {"burst": 5, "min_s": 0.2, "quiet_s": 0.15, "max_s": 3.0}}


def _driver(new_keys: int, seed: int = 3):
    eng = FakeEngine(new_keys)
    log = driver.RunLog(window_s=0.3)
    drv = driver.Driver(FakeGateway(eng), eng, MIX, 100, seed, log)
    drv.t_origin = driver.time.perf_counter()
    return drv, log


def test_warm_up_opens_with_a_burst_and_waits_for_quiet():
    drv, log = _driver(new_keys=0)
    drv.warm_up()
    assert [r.req.arrival for r in log.reqs[:5]] == [0.0] * 5
    assert log.reqs[5].req.arrival > 0.0
    assert MIX["warm"]["min_s"] <= log.end_s < MIX["warm"]["max_s"]
    assert all(r.phase == traffic.WARM for r in log.reqs)

    drv, log = _driver(new_keys=10**6)      # never quiet: capped
    drv.warm_up()
    assert log.end_s >= MIX["warm"]["max_s"]


@pytest.mark.parametrize("new_keys", [0, 40])
def test_window_arrivals_start_at_the_window(new_keys):
    """Whatever the warm-up compiled, the window's requests arrive at the
    same offsets from its opening, with the seed's window stream."""
    drv, log = _driver(new_keys)
    drv.warm_up()
    drv.open_window()
    end = log.w0 + log.window_s
    drv.run(until=end, offer_until=end)
    win = driver.window_reqs(log)
    assert win and win[0].req.arrival == log.w0
    offsets = [round(r.req.arrival - log.w0, 9) for r in win]
    ref = driver.Driver(None, FakeEngine(0), MIX, 100, 3,
                        driver.RunLog(window_s=1.0)).streams[traffic.WINDOW]
    gaps = [next(ref).gap_s for _ in range(len(win))]
    assert offsets == [round(sum(gaps[:i]), 9) for i in range(len(win))]
    assert all(log.w0 <= r.req.arrival < end for r in win)
