"""The load generator: the Poisson schedule, latency from the schedule,
and an open loop that a slow connection cannot hold back."""
import threading
import time

import numpy as np
import pytest

from bench import loadgen
from bench.corpus import seeded


@pytest.mark.parametrize("rate,seconds", [(20.0, 30.0), (3.5, 10.0)])
def test_poisson_schedule_fixed_count_and_gaps(rate, seconds):
    a = loadgen.poisson_schedule(rate, seconds, seeded(1, 3))
    b = loadgen.poisson_schedule(rate, seconds, seeded(2**31 + 9, 3))
    assert len(a) == len(b) == round(rate * seconds)
    assert a[0] == 0.0 and np.all(np.diff(a) > 0) and a[-1] < seconds
    # the same gaps in another order
    ga = np.sort(np.diff(np.r_[a, seconds]))
    gb = np.sort(np.diff(np.r_[b, seconds]))
    assert np.allclose(ga, gb)
    assert not np.allclose(a, b)
    # exponential: the gap quantiles of rate r, scaled to fill the window
    assert np.mean(ga) == pytest.approx(seconds / len(a))
    assert np.median(ga) == pytest.approx(np.log(2) / rate, rel=0.15)


def test_latency_and_lateness_arithmetic():
    r = loadgen.Request([0], t_sched=1.0)
    r.t_dispatch, r.t_send, r.t_done = 1.002, 1.010, 1.050
    r.rankings = [[]]
    assert loadgen.latency_ms([r])[0] == pytest.approx(50.0)
    late = loadgen.lateness_ms([r])
    assert late["dispatch_late_max_ms"] == pytest.approx(2.0)
    assert late["conn_wait_max_ms"] == pytest.approx(8.0)
    failed = loadgen.Request([1], t_sched=2.0, t_done=2.5, error="shed")
    assert not failed.ok and loadgen.latency_ms([failed])[0] == 500.0


class _FakeClient:
    """``rank`` sleeps; the first connection made is slow."""

    made = 0
    lock = threading.Lock()

    def __init__(self):
        with _FakeClient.lock:
            self.slow = _FakeClient.made == 0
            _FakeClient.made += 1

    def rank(self, q):
        time.sleep(0.3 if self.slow else 0.005)
        return [(0, 0, 0.5)]

    def rank_batch(self, qs):
        time.sleep(0.01)
        return [[(0, 0, 0.5)] for _ in qs]

    def close(self):
        pass


def test_open_loop_times_from_schedule_and_skips_a_busy_connection():
    _FakeClient.made = 0
    texts = [f"q{i}" for i in range(40)]
    out = loadgen.open_loop(_FakeClient, texts, 20.0, 2.0, 4,
                            seeded(5, 3), drain_s=10.0)
    reqs = out["requests"]
    assert len(reqs) == 40 and all(r.ok for r in reqs)
    # each request is released at its own arrival in the schedule
    want = loadgen.poisson_schedule(20.0, 2.0, seeded(5, 3))
    assert [r.t_sched for r in reqs] == pytest.approx(list(want))
    assert all(0 <= r.t_dispatch - r.t_sched < 0.05 for r in reqs)
    lat = loadgen.latency_ms(reqs)
    # one slow connection holds a few requests; the rest go elsewhere
    assert np.median(lat) < 100 and lat.max() >= 300
    assert all(r.t_done - r.t_sched >= r.t_send - r.t_sched for r in reqs)


def test_closed_loop_sends_distinct_batches():
    _FakeClient.made = 1
    texts = [f"q{i}" for i in range(1000)]
    out = loadgen.closed_loop(_FakeClient, texts.__getitem__, 4, 4, 0.5,
                              drain_s=5.0)
    seen = [q for r in out["requests"] for q in r.queries]
    assert len(seen) == len(set(seen))
    assert all(len(r.rankings) == 4 for r in out["requests"])
    assert {r.conn for r in out["requests"]} == {0, 1, 2, 3}


def test_closed_rate_counts_every_batch_with_its_time():
    def req(conn, t_done, ok=True):
        r = loadgen.Request([0, 1, 2, 3], conn=conn, t_done=t_done)
        r.rankings = [[]] * 4 if ok else None
        return r
    # connection 0: 3 batches by 12 s; connection 1: 2 by 10 s, one failed
    reqs = [req(0, 4.0), req(0, 8.0), req(0, 12.0),
            req(1, 5.0), req(1, 10.0), req(1, 11.0, ok=False)]
    assert loadgen.closed_rate(reqs) == pytest.approx(12 / 12 + 8 / 11)
    assert loadgen.closed_rate([]) == 0.0


def test_sweep_takes_the_highest_rate_below_the_first_backlog():
    from bench import sweep

    def row(rate, done, first, last, failed=0):
        return {"offered_qps": rate, "completed_qps": done, "failed": failed,
                "first_quarter_p50_ms": first, "last_quarter_p50_ms": last}
    rows = [row(80, 79.9, 17.7, 19.2), row(100, 99.6, 32.8, 28.6),
            row(120, 118.7, 53.1, 70.8), row(140, 125.9, 183.2, 854.1)]
    assert [sweep.sustained(r) for r in rows] == [True, True, False, False]
    assert sweep.highest_sustained(rows) == 100
    assert not sweep.sustained(row(60, 60.0, 14.0, 14.0, failed=1))
    assert not sweep.sustained(row(60, 57.0, 14.0, 14.0))
    # a rate above the first backlog does not count, even if it reads well
    assert sweep.highest_sustained(rows[:2] + [rows[2], row(130, 130, 9, 9)]) \
        == 100
