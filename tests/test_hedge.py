"""Hedged dispatch (serving.hedge.HedgedTransport): hedge fires after the
delay, the backup's answer wins, the loser's reply is drained without
corrupting its framed stream, and errors fail over instead of winning."""
import threading
import time

import numpy as np
import pytest

from repro.core import service as SV
from repro.core import wire
from repro.serving.hedge import HedgedTransport


class _StubTransport:
    """In-process endpoint with a controllable delay and call log."""

    def __init__(self, name, value, delay_s=0.0, fail=False):
        self.name = name
        self.value = value
        self.delay_s = delay_s
        self.fail = fail
        self.calls = 0
        self.completed = 0
        self._lock = threading.Lock()

    def rank_batch(self, queries):
        with self._lock:
            self.calls += 1
        time.sleep(self.delay_s)
        if self.fail:
            raise wire.ShedError("stub shed")
        out = [[(self.value, 0, float(self.value))] for _ in queries]
        with self._lock:
            self.completed += 1
        return out

    def get_score_batch(self, pairs):
        time.sleep(self.delay_s)
        with self._lock:
            self.calls += 1
        return [float(self.value)] * len(pairs)


def test_hedge_wins_over_slow_primary_and_loser_drains():
    slow = _StubTransport("slow", 1, delay_s=0.3)
    fast = _StubTransport("fast", 2)
    ht = HedgedTransport([slow, fast], hedge_s=0.02)
    t0 = time.perf_counter()
    out = ht.rank_batch(["q"])          # primary = slow (round robin @ 0)
    dt = time.perf_counter() - t0
    assert out == [[(2, 0, 2.0)]]       # the backup's answer won
    assert dt < 0.25                    # did not wait out the slow replica
    s = ht.stats()
    assert s["hedged"] == 1.0 and s["hedge_wins"] == 1.0
    # The loser keeps draining in the background and completes cleanly —
    # its (discarded) reply never desyncs the endpoint.
    deadline = time.time() + 2.0
    while slow.completed < 1 and time.time() < deadline:
        time.sleep(0.01)
    assert slow.completed == 1
    # the endpoint is reusable after the drain (stream intact)
    slow.delay_s = 0.0
    assert ht.rank_batch(["q2"]) in ([[(1, 0, 1.0)]], [[(2, 0, 2.0)]])


def test_fast_primary_never_hedges():
    a = _StubTransport("a", 1)
    b = _StubTransport("b", 2)
    ht = HedgedTransport([a, b], hedge_s=0.2)
    assert ht.rank_batch(["q"]) == [[(1, 0, 1.0)]]
    assert ht.stats()["hedged"] == 0.0
    assert b.calls == 0


def test_unhedged_baseline_waits_out_slow_replica():
    slow = _StubTransport("slow", 1, delay_s=0.1)
    fast = _StubTransport("fast", 2)
    ht = HedgedTransport([slow, fast], hedge_s=float("inf"))
    t0 = time.perf_counter()
    out = ht.rank_batch(["q"])          # primary = slow, no hedge
    assert time.perf_counter() - t0 >= 0.1
    assert out == [[(1, 0, 1.0)]]
    assert ht.stats()["hedged"] == 0.0


def test_failed_primary_fails_over_to_backup():
    bad = _StubTransport("bad", 1, fail=True)
    good = _StubTransport("good", 2)
    ht = HedgedTransport([bad, good], hedge_s=0.5)
    # the primary fails fast -> immediate hedge, backup's success wins
    assert ht.rank_batch(["q"]) == [[(2, 0, 2.0)]]
    assert ht.stats()["hedge_wins"] == 1.0


def test_all_endpoints_failing_raises_primary_error():
    bad1 = _StubTransport("bad1", 1, fail=True)
    bad2 = _StubTransport("bad2", 2, fail=True)
    ht = HedgedTransport([bad1, bad2], hedge_s=0.01)
    with pytest.raises(wire.ShedError):
        ht.rank_batch(["q"])


def test_single_endpoint_no_hedging():
    only = _StubTransport("only", 7)
    ht = HedgedTransport([only], hedge_s=0.001)
    assert ht.rank_batch(["q"]) == [[(7, 0, 7.0)]]
    assert ht.stats()["hedged"] == 0.0
    only.fail = True
    with pytest.raises(wire.ShedError):
        ht.rank_batch(["q"])


def test_adaptive_delay_tracks_p95():
    a = _StubTransport("a", 1)
    b = _StubTransport("b", 2)
    ht = HedgedTransport([a, b], min_samples=4, default_hedge_s=0.123,
                         min_hedge_s=0.002)
    assert ht.hedge_delay_s() == 0.123          # no samples yet: default
    for _ in range(8):
        ht.rank_batch(["q"])
    # sub-millisecond stubs -> the p95 clamps up to min_hedge_s
    assert ht.hedge_delay_s() == pytest.approx(0.002)


def test_hedged_over_real_sockets_stream_stays_clean():
    """Socket endpoints: the loser's reply is read by its own attempt
    thread on its own connection, so a later request through the same
    client decodes the RIGHT frame (no off-by-one-reply desync)."""

    class SleepyHandler:
        def __init__(self, delay_s):
            self.delay_s = delay_s

        def get_scores(self, pairs):
            time.sleep(self.delay_s)
            return np.full((len(pairs),), self.delay_s, np.float32)

    slow_h, fast_h = SleepyHandler(0.25), SleepyHandler(0.0)
    srv_slow = SV.SimpleServer(slow_h).start_background()
    srv_fast = SV.SimpleServer(fast_h).start_background()
    ht = None
    try:
        ht = HedgedTransport([SV.Client(srv_slow.address),
                              SV.Client(srv_fast.address)],
                             hedge_s=0.02)
        out = ht.get_score_batch([("q", "a"), ("q2", "a2")])
        assert list(out) == pytest.approx([0.0, 0.0])   # fast replica won
        assert ht.stats()["hedge_wins"] == 1.0
        # after the loser drains, the slow endpoint answers correctly
        slow_h.delay_s = 0.0
        for _ in range(2):          # hits both endpoints round-robin
            out = ht.get_score_batch([("x", "y")])
            assert list(out) == pytest.approx([0.0])
    finally:
        if ht is not None:
            ht.close()
        srv_slow.stop()
        srv_fast.stop()


# --------------------------- single-pair deadline propagation (bugfix) ----

def _stub_scorer(q_tok, a_tok, feats):
    return np.full((q_tok.shape[0],), 0.5, np.float32)


def test_serving_engine_get_score_sheds_expired():
    from repro.data.tokenizer import HashingTokenizer
    from repro.serving.engine import ServingEngine
    eng = ServingEngine(_stub_scorer, HashingTokenizer(512), idf={},
                        max_len=8)
    try:
        with pytest.raises(wire.ShedError, match="expired"):
            eng.get_score("q", "a",
                          deadline_abs=time.perf_counter() - 1.0)
        # a live deadline still scores, and no-deadline callers are intact
        live = eng.get_score("q", "a",
                             deadline_abs=time.perf_counter() + 30.0)
        assert live == pytest.approx(0.5)
        assert eng.get_score("q", "a") == pytest.approx(0.5)
    finally:
        eng.stop()


def test_replica_pool_get_score_sheds_expired():
    from repro.data.tokenizer import HashingTokenizer
    from repro.serving.cluster import ReplicaPool
    pool = ReplicaPool([_stub_scorer], HashingTokenizer(512), idf={},
                       max_len=8)
    try:
        with pytest.raises(wire.ShedError, match="expired"):
            pool.get_score("q", "a",
                           deadline_abs=time.perf_counter() - 1.0)
        assert pool.get_score("q", "a") == pytest.approx(0.5)
    finally:
        pool.stop()


def test_batches_stat_is_monotonic_not_windowed():
    """The 'batches' stat must count all batches ever scored, not the
    sliding batch_sizes window (which bounds mean_batch only)."""
    from repro.serving.batcher import MicroBatcher
    mb = MicroBatcher(_stub_scorer, max_batch=4, max_wait_s=0.0)
    try:
        mb.batch_sizes = type(mb.batch_sizes)(maxlen=2)  # tiny window
        q = np.zeros((1, 4), np.int32)
        f = np.zeros((1, 4), np.float32)
        for _ in range(5):
            mb.submit_many(q, q, f).result(timeout=2.0)
        stats = mb.stats()
        assert stats["batches"] == 5.0          # all-time, not min(5, 2)
        assert stats["mean_batch"] == 1.0       # window still feeds the mean
    finally:
        mb.stop()


def test_cold_start_default_delay_prevents_hedge_storm():
    """Regression: with an EMPTY tracker the adaptive p95 is 0.0, so
    without the min-samples floor every request would hedge immediately
    (doubling fleet load from the first request). The cold transport must
    use the fixed default delay and never hedge fast requests."""
    a = _StubTransport("a", 1, delay_s=0.005)
    b = _StubTransport("b", 2, delay_s=0.005)
    ht = HedgedTransport([a, b], default_hedge_s=0.05, min_samples=16)
    assert ht.tracker.percentile(0.95) == 0.0   # degenerate adaptive value
    assert ht.hedge_delay_s() == pytest.approx(0.05)
    for _ in range(8):                          # still below min_samples
        ht.rank_batch(["q"])
    s = ht.stats()
    assert s["hedged"] == 0.0                   # 5ms stubs never hit 50ms
    assert a.calls + b.calls == 8               # no duplicate dispatches


def test_warmed_tracker_switches_from_default_to_adaptive():
    a = _StubTransport("a", 1)
    b = _StubTransport("b", 2)
    ht = HedgedTransport([a, b], min_samples=4, default_hedge_s=0.2,
                         min_hedge_s=0.001)
    for i in range(4):
        assert ht.hedge_delay_s() == pytest.approx(0.2)   # still cold
        ht.rank_batch(["q"])
    # Warm: the delay is now the observed p95 (clamped), not the default.
    assert ht.hedge_delay_s() < 0.2
    assert ht.hedge_delay_s() >= 0.001


def test_fresh_requests_route_around_busy_endpoint():
    """Regression (repro-lint LOCK001 follow-up): a losing attempt holds
    its endpoint lock while it drains the discarded reply — by design, the
    lock is the drain barrier. Plain round-robin then assigned every other
    request to the draining endpoint and made it QUEUE behind the drain: a
    tail-latency cliff for requests that had a free replica available.
    _pick_endpoints now skews away from endpoints whose lock is held."""
    import queue as queue_mod

    slow = _StubTransport("slow", 1, delay_s=0.6)
    fast = _StubTransport("fast", 2)
    # Infinite hedge delay isolates the routing decision: nothing hedges,
    # so a request parked on the busy endpoint would wait the full 0.6s.
    ht = HedgedTransport([slow, fast], hedge_s=float("inf"))

    # Occupy endpoint 0 the way a loser drain does: an attempt in flight
    # holding the endpoint lock.
    drain = threading.Thread(
        target=ht._attempt,
        args=(0, "get_score_batch", ([("q", "a")],), queue_mod.Queue()),
        daemon=True)
    drain.start()
    deadline = time.time() + 2.0
    while not ht._locks[0].locked() and time.time() < deadline:
        time.sleep(0.001)
    assert ht._locks[0].locked()

    # Every request issued while 0 drains must land on the free endpoint
    # and return fast — the old rotation parked half of them behind the
    # 0.6s drain.
    t0 = time.perf_counter()
    outs = [ht.get_score_batch([("q", "a")]) for _ in range(4)]
    dt = time.perf_counter() - t0
    assert all(out == [2.0] for out in outs)
    assert dt < 0.4, f"queued behind the draining endpoint ({dt:.3f}s)"
    assert fast.calls == 4 and slow.calls == 0
    drain.join(timeout=2.0)
    assert not drain.is_alive()


def test_settle_waits_for_the_losing_attempt():
    """After the winner's answer, ``settle`` returns once the loser has
    drained its reply too, so nothing of the call is still running."""
    slow = _StubTransport("slow", 1, delay_s=0.3)
    fast = _StubTransport("fast", 2)
    ht = HedgedTransport([slow, fast], hedge_s=0.02)
    assert ht.rank_batch(["q"]) == [[(2, 0, 2.0)]]
    assert slow.completed == 0              # the loser is still running
    assert ht.settle(timeout_s=5.0)
    assert slow.completed == 1
    assert ht.settle(timeout_s=0.0)         # nothing in flight: at once
