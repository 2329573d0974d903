"""Replica pool + admission control: routing, correctness, shedding."""
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import backends as BK
from repro.data import qa as QA
from repro.data.tokenizer import HashingTokenizer
from repro.models import sm_cnn
from repro.serving import telemetry
from repro.serving.admission import (SHED_EXPIRED, SHED_LATE,
                                     SHED_QUEUE_FULL, SHED_TOO_LARGE,
                                     AdmissionController)
from repro.serving.cluster import POLICIES, ReplicaPool
from repro.serving.stats import LatencyTracker


@pytest.fixture(scope="module")
def world():
    cfg = reduced(get_config("sm-cnn"))
    params = sm_cnn.init_sm_cnn(jax.random.PRNGKey(0), cfg)
    corpus = QA.generate_corpus(n_docs=20, n_questions=5, seed=11)
    tok = HashingTokenizer(cfg.vocab_size)
    return cfg, params, corpus, tok


def _pairs(corpus, n):
    out = []
    for i in range(n):
        out.append((corpus.questions[i % len(corpus.questions)],
                    corpus.documents[i % len(corpus.documents)][0]))
    return out


# ------------------------------------------------------ featurize spans --

def _pool_spans(pool, pairs):
    """The spans of one ``pool.get_scores`` call, by name."""
    tracer = telemetry.get_tracer()
    tracer.clear()
    with tracer.span("test.request") as root:
        pool.get_scores(pairs)
    by_name = {}
    for s in tracer.finished(trace_id=root.context.trace_id):
        by_name.setdefault(s.name, []).append(s)
    return by_name


@pytest.fixture()
def numpy_pool(world):
    cfg, params, corpus, tok = world
    pool = ReplicaPool.build("numpy", params, cfg, tok, corpus.idf,
                             n_replicas=1, buckets=(1, 8, 64, 1024),
                             max_batch=1024)
    yield pool
    pool.stop()


def test_pool_featurize_span_and_children(world, numpy_pool):
    """``featurize`` sits under ``pool.get_scores`` with
    ``featurize.encode`` then ``featurize.pairs`` under it, and counts this
    call's own lookups of answer rows and pair features."""
    cfg, params, corpus, tok = world
    pairs = [(corpus.questions[0], corpus.documents[i][0]) for i in range(10)]
    spans = _pool_spans(numpy_pool, pairs)
    (call,) = spans["pool.get_scores"]
    (feat,) = spans["featurize"]
    (enc,) = spans["featurize.encode"]
    (prs,) = spans["featurize.pairs"]
    assert feat.parent_id == call.span_id
    assert enc.parent_id == prs.parent_id == feat.span_id
    assert enc.ts_us + enc.dur_us <= prs.ts_us
    assert feat.attrs["rows"] == 10
    assert min(s.attrs["cpu_ms"] for s in (feat, enc, prs)) > 0
    # a fresh cache: every answer row and pair feature misses once; the
    # query's row, looked up for every pair, is not counted
    assert (feat.attrs["hits"], feat.attrs["misses"]) == (0, 20)
    assert (feat.attrs["row_misses"], feat.attrs["pair_misses"]) == (10, 10)
    again = _pool_spans(numpy_pool, pairs)["featurize"][0]
    assert (again.attrs["hits"], again.attrs["misses"]) == (20, 0)
    assert (again.attrs["row_hits"], again.attrs["pair_hits"]) == (10, 10)


def test_pool_opens_as_many_spans_for_1000_candidates_as_for_10(
        world, numpy_pool):
    cfg, params, corpus, tok = world
    few = _pool_spans(numpy_pool, _pairs(corpus, 10))
    many = _pool_spans(numpy_pool, _pairs(corpus, 1000))
    assert {k: len(v) for k, v in many.items()} == {
        k: len(v) for k, v in few.items()}


def test_pool_featurization_equals_the_pair_by_pair_loop(world, numpy_pool):
    """Two passes over the pairs give the arrays the one-pass loop gave,
    bit for bit."""
    cfg, params, corpus, tok = world
    from repro.data.featurize import FeaturizationCache
    pairs = _pairs(corpus, 37) + [("", corpus.documents[0][1])]
    fresh = FeaturizationCache(tok, corpus.idf, cfg.max_len)
    rows = [fresh.featurize(q, a) for q, a in pairs]
    want = [np.stack([r[k] for r in rows]) for k in range(3)]
    for _ in range(2):                      # cold, then from the cache
        got = numpy_pool.features.featurize_many(pairs)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(g, w)


def test_pool_featurize_counts_only_its_own_lookups(world, numpy_pool):
    """Under concurrent callers on the shared cache, every ``featurize``
    span still counts exactly its own two lookups a pair (answer row,
    pair features)."""
    cfg, params, corpus, tok = world
    tracer = telemetry.get_tracer()
    tracer.clear()

    def call(i):
        with tracer.span("test.request"):
            numpy_pool.get_scores(_pairs(corpus, 20 + i))

    threads = [threading.Thread(target=call, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    feats = [s for s in tracer.finished() if s.name == "featurize"]
    assert len(feats) == 6
    for s in feats:
        assert s.attrs["hits"] + s.attrs["misses"] == 2 * s.attrs["rows"]


# ---------------------------------------------------------------- replica pool

@pytest.mark.parametrize("backend", ["jit", "numpy"])
def test_pool_matches_direct_scorer(world, backend):
    cfg, params, corpus, tok = world
    pool = ReplicaPool.build(backend, params, cfg, tok, corpus.idf,
                             n_replicas=2, buckets=(1, 8, 64))
    scorer = BK.make_scorer(backend, params, cfg, buckets=(1, 8, 64))
    from repro.core.service import QuestionAnsweringHandler
    handler = QuestionAnsweringHandler(scorer, tok, corpus.idf, cfg.max_len)
    pairs = _pairs(corpus, 12)
    got = pool.get_scores(pairs)
    want = handler.get_scores(pairs)
    pool.stop()
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


def test_pool_policies_route_and_complete(world):
    cfg, params, corpus, tok = world
    pairs = _pairs(corpus, 4)
    for policy in POLICIES:
        pool = ReplicaPool.build("jit", params, cfg, tok, corpus.idf,
                                 n_replicas=3, buckets=(1, 8, 64),
                                 policy=policy)
        for _ in range(9):
            out = pool.get_scores(pairs)
            assert out.shape == (4,)
        s = pool.stats()
        total = sum(s[f"replica{i}_requests"] for i in range(3))
        assert total == 9
        if policy == "round_robin":
            assert all(s[f"replica{i}_requests"] == 3 for i in range(3))
        assert pool.outstanding_rows() == 0
        pool.stop()


def test_pool_concurrent_clients_agree_with_direct(world):
    cfg, params, corpus, tok = world
    pool = ReplicaPool.build("jit", params, cfg, tok, corpus.idf,
                             n_replicas=2, buckets=(1, 8, 64),
                             policy="p2c")
    scorer = BK.make_scorer("jit", params, cfg, buckets=(1, 8, 64))
    from repro.core.service import QuestionAnsweringHandler
    handler = QuestionAnsweringHandler(scorer, tok, corpus.idf, cfg.max_len)
    pairs = _pairs(corpus, 8)
    want = handler.get_scores(pairs)
    results = {}

    def client(i):
        results[i] = pool.get_scores(pairs)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    pool.stop()
    assert len(results) == 8
    for got in results.values():
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_pool_rejects_bad_policy(world):
    cfg, params, corpus, tok = world
    with pytest.raises(ValueError, match="unknown policy"):
        ReplicaPool([lambda q, a, f: np.zeros(q.shape[0])], tok, corpus.idf,
                    cfg.max_len, policy="random-guess")
    with pytest.raises(ValueError, match="at least one"):
        ReplicaPool([], tok, corpus.idf, cfg.max_len)


# ---------------------------------------------------------- admission control

def test_admission_expired_deadline_sheds():
    ac = AdmissionController(max_queue_rows=100)
    now = time.perf_counter()
    assert ac.try_admit(1, deadline_abs=now - 0.01, now=now) == SHED_EXPIRED
    assert ac.stats()["shed_expired"] == 1
    assert ac.stats()["admission_outstanding_rows"] == 0  # nothing reserved


def test_admission_queue_bound_sheds_then_recovers():
    ac = AdmissionController(max_queue_rows=10)
    assert ac.try_admit(8) is None
    assert ac.try_admit(4) == SHED_QUEUE_FULL
    assert ac.try_admit(2) is None          # exactly fills the bound
    ac.release(8, service_s=0.008)
    assert ac.try_admit(4) is None
    s = ac.stats()
    assert s["admitted"] == 3 and s["shed_queue_full"] == 1
    assert s["admission_outstanding_rows"] == 6


def test_admission_oversized_request_is_permanent_not_queue_full():
    ac = AdmissionController(max_queue_rows=10)
    # Larger than the bound on an IDLE cluster: retrying can never help,
    # so the reason must be the permanent one, not back-pressure.
    assert ac.try_admit(11) == SHED_TOO_LARGE
    assert ac.stats()["shed_too_large"] == 1
    assert ac.stats()["admission_outstanding_rows"] == 0


def test_admission_estimated_wait_sheds_unmeetable_deadline():
    ac = AdmissionController(max_queue_rows=10_000,
                             init_row_service_s=0.010)
    now = time.perf_counter()
    assert ac.try_admit(100) is None        # backlog: 100 rows ~ 1s of work
    # 50 more rows => ~1.5s estimated completion, deadline in 100ms: shed.
    assert ac.try_admit(50, deadline_abs=now + 0.1, now=now) == SHED_LATE
    # Same rows with a 10s budget: admitted.
    assert ac.try_admit(50, deadline_abs=now + 10.0, now=now) is None


def test_admission_ewma_tracks_service_time():
    ac = AdmissionController(ewma_alpha=0.5, init_row_service_s=0.001)
    ac.try_admit(10)
    ac.release(10, service_s=0.1)           # 10 ms/row observed
    est = ac.estimated_wait_s(100)
    assert 0.1 < est < 1.5                  # pulled toward 10ms/row


# ------------------------------------------------------------------- tracker

def test_latency_tracker_concurrent_observe():
    tr = LatencyTracker()

    def hammer():
        for _ in range(500):
            tr.observe(0.001)
            tr.summary()

    threads = [threading.Thread(target=hammer) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert tr.summary()["count"] == 8 * 500


def test_latency_tracker_interpolated_percentiles():
    tr = LatencyTracker()
    for v in (0.001, 0.002, 0.003, 0.004):
        tr.observe(v)
    # q=0.5 over 4 samples: between samples 1 and 2 -> 2.5 ms exactly.
    assert tr.percentile(0.5) == pytest.approx(0.0025)
    assert tr.percentile(0.0) == pytest.approx(0.001)
    assert tr.percentile(1.0) == pytest.approx(0.004)


def test_microbatcher_stop_fails_pending_futures_not_hangs():
    from repro.serving.batcher import MicroBatcher

    def slow_scorer(q, a, f):
        time.sleep(0.2)
        return np.zeros((q.shape[0],), np.float32)

    mb = MicroBatcher(slow_scorer, max_batch=1, max_wait_s=0.001)
    row = np.zeros((4,), np.int32)
    feats = np.zeros((4,), np.float32)
    futs = [mb.submit(row, row, feats) for _ in range(3)]
    time.sleep(0.05)                     # let the worker start item 0
    mb.stop()
    # First item completes; the ones the worker never reached must resolve
    # with an error instead of stranding .result() callers forever.
    outcomes = []
    for f in futs:
        try:
            f.result(timeout=5)
            outcomes.append("ok")
        except RuntimeError:
            outcomes.append("stopped")
    assert outcomes[0] == "ok"
    assert "stopped" in outcomes[1:]
    # Submitting after stop fails fast, not silently queues.
    with pytest.raises(RuntimeError, match="stopped"):
        mb.submit(row, row, feats).result(timeout=5)


def test_pool_row_service_feeds_admission_estimate(world):
    cfg, params, corpus, tok = world
    pool = ReplicaPool.build("numpy", params, cfg, tok, corpus.idf,
                             n_replicas=2, buckets=(1, 8, 64))
    assert pool.row_service_s() is None          # nothing scored yet
    pool.get_scores(_pairs(corpus, 4))
    per_row = pool.row_service_s()
    assert per_row is not None and per_row > 0
    ac = AdmissionController(init_row_service_s=123.0,  # absurd fallback
                             service_time_source=pool.row_service_s)
    # The scorer-side source must win over the sojourn fallback.
    assert ac.estimated_wait_s(10) == pytest.approx(10 * per_row)
    pool.stop()


def test_microbatcher_outstanding_rows_settle(world):
    cfg, params, corpus, tok = world
    from repro.serving.batcher import MicroBatcher
    scorer = BK.make_scorer("numpy", params, cfg, buckets=(1, 8, 64))
    mb = MicroBatcher(scorer, max_batch=8, max_wait_s=0.002)
    rng = np.random.default_rng(0)
    q = rng.integers(0, cfg.vocab_size, (6, cfg.max_len)).astype(np.int32)
    a = rng.integers(0, cfg.vocab_size, (6, cfg.max_len)).astype(np.int32)
    f = rng.random((6, 4), np.float32)
    fut = mb.submit_many(q, a, f)
    fut.result(timeout=10)
    deadline = time.time() + 5
    while mb.outstanding_rows and time.time() < deadline:
        time.sleep(0.01)
    s = mb.stats()
    mb.stop()
    assert s["outstanding_rows"] == 0
    assert s["rows_scored"] == 6


# ------------------------------------------------- drain-model parallelism

def test_admission_parallelism_divides_wait_estimate():
    ac = AdmissionController(max_queue_rows=10_000,
                             init_row_service_s=0.010,
                             effective_parallelism=4)
    now = time.perf_counter()
    assert ac.try_admit(100) is None
    # Serially 150 rows x 10ms = 1.5s > the 0.5s budget (the old model
    # shed this as late); four concurrent servers drain it in ~0.375s.
    assert ac.try_admit(50, deadline_abs=now + 0.5, now=now) is None
    s = ac.stats()
    assert s["shed_late"] == 0
    assert s["effective_parallelism"] == 4.0
    assert ac.estimated_wait_s(0) == pytest.approx(150 * 0.010 / 4)


def test_set_effective_parallelism_updates_and_clamps():
    ac = AdmissionController(init_row_service_s=0.010)
    ac.try_admit(100)
    serial = ac.estimated_wait_s(0)
    ac.set_effective_parallelism(4)
    assert ac.estimated_wait_s(0) == pytest.approx(serial / 4)
    ac.set_effective_parallelism(0)          # nonsense input clamps to 1
    assert ac.estimated_wait_s(0) == pytest.approx(serial)


def test_four_replica_pool_no_spurious_late_sheds(world):
    """Regression: moderate load on a 4-replica pool, deadlines that fit
    through four concurrent replicas but NOT through a serial drain. The
    parallelism-aware controller admits everything; the old serial model
    (parallelism hint left at 1) sheds the tail of the same load late."""
    cfg, params, corpus, tok = world

    def make_scorer():
        def scorer(q_tok, a_tok, feats):
            time.sleep(0.002 * q_tok.shape[0])      # 2ms/row, one replica
            return np.zeros((q_tok.shape[0],), np.float32)
        return scorer

    pool = ReplicaPool([make_scorer() for _ in range(4)], tok, corpus.idf,
                       cfg.max_len, policy="least_outstanding")
    try:
        pool.get_scores(_pairs(corpus, 8))           # warm row_service_s
        per_row = pool.row_service_s()
        assert per_row is not None and per_row > 0
        assert pool.effective_parallelism == 4

        # Wired exactly as ThreadPoolServer wires a pool handler.
        ac = AdmissionController(max_queue_rows=4096,
                                 service_time_source=pool.row_service_s)
        ac.set_effective_parallelism(pool.effective_parallelism)
        serial = AdmissionController(max_queue_rows=4096,
                                     service_time_source=pool.row_service_s)

        now = time.perf_counter()
        deadline = now + 100 * per_row
        sheds_serial = 0
        for _ in range(20):                          # 20 x 16 = 320 rows
            assert ac.try_admit(16, deadline_abs=deadline, now=now) is None
            if serial.try_admit(16, deadline_abs=deadline,
                                now=now) is not None:
                sheds_serial += 1
        assert ac.stats()["shed_late"] == 0          # the fix
        assert sheds_serial > 0                      # the old behavior
    finally:
        pool.stop()


# ------------------------------------------------------- device placement

_PLACEMENT_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import jax
from repro.core.plan import PlanContext, plan
from repro.launch.serve import canonical_pipeline
from repro.launch.world import build_world
from repro.serving.cluster import ReplicaPool

cfg, params, corpus, tok, index, _ = build_world(train_steps=2)
queries = corpus.questions[:24]
ctx = PlanContext.from_world(cfg, params, corpus, tok, index,
                             buckets=(1, 8, 64))
rankings = {}
for n in (1, 4):
    with ReplicaPool.build("BACKEND", params, cfg, tok, corpus.idf,
                           n_replicas=n, buckets=(1, 8, 64),
                           policy="round_robin") as pool:
        scorers = [r.batcher.scorer for r in pool.replicas]
        assert [s.device for s in scorers] == jax.devices()[:n]
        for s in scorers:
            assert {d for leaf in jax.tree.leaves(s.params)
                    for d in leaf.devices()} == {s.device}
        with plan(canonical_pipeline("BACKEND"), "remote",
                  dataclasses.replace(ctx, remote=pool)) as p:
            rankings[n] = [[(c.doc_id, c.sent_id, c.score) for c in cands]
                           for cands, _ in (p.run(q) for q in queries)]
        rows = [pool.stats()[f"replica{i}_rows_scored"] for i in range(n)]
        assert all(r > 0 for r in rows), rows
assert rankings[4] == rankings[1]
print("PLACEMENT_OK")
"""


@pytest.mark.parametrize("backend", ["jit", "aot"])
def test_pool_puts_each_replica_on_its_own_device(backend):
    """4 replicas on 4 (virtual) devices: each replica's params live on its
    own device, every replica scores, and the rankings equal one
    replica's. A subprocess, because the device count must be set before
    jax initializes."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", _PLACEMENT_SCRIPT.replace("BACKEND", backend)],
        env=dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
                 JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300, cwd=root)
    assert "PLACEMENT_OK" in out.stdout, out.stdout + out.stderr
