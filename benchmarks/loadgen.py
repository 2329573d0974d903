"""Open-loop Poisson load generator for the RPC serving cluster.

Closed-loop benchmarks (issue, wait, repeat — Table 2's client) hide
queueing collapse: the client slows down with the server, so offered load
sags exactly when the system saturates. This generator is open-loop: a
Poisson arrival schedule is fixed up front at an offered QPS and every
request is launched at its scheduled time whether or not earlier ones have
completed, so latency includes the queueing delay a real user would see
(coordinated-omission-free: lateness counts from the SCHEDULED arrival).

``sweep`` walks offered QPS levels and reports achieved throughput with
p50/p99 — the throughput-vs-tail-latency curve for SimpleServer vs
ThreadPoolServer x replicas that extends the paper's Table 2. Shed replies
(MSG_SHED from admission control) are counted separately from errors:
under overload a well-behaved cluster sheds fast instead of queueing
unboundedly.

Ranking-RPC mode (``run_level(mode="rank")``) drives wire-v3 whole-pipeline
requests (``Client.rank``) instead of pair scoring; ``run_hedged`` stands up
two pipeline-serving replicas — one artificially slowed — and contrasts the
p99 of unhedged round-robin dispatch against hedged dispatch
(``serving.hedge.HedgedTransport``: same code path with the hedge delay set
to infinity for the unhedged baseline).

Process-scaling mode (``run_fabric`` / ``--processes``) spawns N
pipeline-serving worker PROCESSES behind the health-probed hedging router
(``serving.fabric``) and drives the open-loop rank schedule through the
router — the multi-core scaling curve the in-process thread cluster
structurally cannot produce (featurization holds the GIL). Rows record
``host_cores``: on a single-core host every process count shares one core,
so the curve is flat by construction there.

  PYTHONPATH=src python -m benchmarks.loadgen            # standalone sweep
  PYTHONPATH=src python -m benchmarks.loadgen --processes 1,2,4   # fabric
  PYTHONPATH=src python -m benchmarks.run --table loadgen --json out.json
"""
from __future__ import annotations

import random
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core import service as SV
from repro.core import wire


def poisson_arrivals(offered_qps: float, duration_s: float,
                     seed: int = 0) -> List[float]:
    """Exponential inter-arrival times at rate ``offered_qps``."""
    rng = random.Random(seed)
    t, out = 0.0, []
    while True:
        t += rng.expovariate(offered_qps)
        if t >= duration_s:
            return out
        out.append(t)


def run_level(address: Tuple[str, int], reqs: Sequence,
              offered_qps: float, duration_s: float, n_conns: int = 4,
              deadline_s: Optional[float] = None, seed: int = 0,
              mode: str = "score") -> Dict[str, float]:
    """Drive one offered-QPS level with ``n_conns`` persistent connections.

    Arrivals are struck round-robin across connections; a connection that
    falls behind its schedule fires immediately and the lateness shows up
    in the measured latency (open-loop semantics).

    ``mode="score"`` drives pair-scoring RPCs (``reqs`` holds (q, a)
    pairs); ``mode="rank"`` drives v3 whole-pipeline ranking RPCs
    (``reqs`` holds query strings, one ``Client.rank`` per arrival).

    ``address`` may instead be a callable ``factory(wid) -> client`` for
    transports that are not one socket per connection (the fabric sweep
    passes router-backed connections so requests route least-loaded across
    worker processes).
    """
    arrivals = poisson_arrivals(offered_qps, duration_s, seed)
    lock = threading.Lock()
    lats: List[float] = []
    counts = {"ok": 0, "shed": 0, "error": 0}
    clients: List[SV.Client] = []
    stop = threading.Event()
    t0_box = [0.0]
    last_done = [0.0]

    def worker(wid: int):
        try:
            cl = address(wid) if callable(address) else SV.Client(address)
        except OSError:
            with lock:
                counts["error"] += len(arrivals[wid::n_conns])
            return
        with lock:
            clients.append(cl)
        for i, at in list(enumerate(arrivals))[wid::n_conns]:
            if stop.is_set():
                break
            wait = at - (time.perf_counter() - t0_box[0])
            if wait > 0:
                time.sleep(wait)
            req = reqs[i % len(reqs)]
            try:
                # The deadline is a budget from the SCHEDULED arrival: a
                # request fired late (connection behind schedule) has
                # already burned part of it, so the server can shed it as
                # expired — the wire deadline is relative to send time.
                budget = deadline_s
                if budget is not None:
                    budget -= (time.perf_counter() - t0_box[0]) - at
                if mode == "rank":
                    cl.rank(req, deadline_s=budget)
                else:
                    cl.get_score(req[0], req[1], deadline_s=budget)
                done = time.perf_counter() - t0_box[0]
                with lock:
                    lats.append(done - at)
                    counts["ok"] += 1
                    last_done[0] = max(last_done[0], done)
            except wire.ShedError:
                with lock:
                    counts["shed"] += 1
            except (ConnectionError, OSError, RuntimeError, ValueError):
                if stop.is_set():
                    break
                with lock:
                    counts["error"] += 1

    threads = [threading.Thread(target=worker, args=(w,), daemon=True)
               for w in range(n_conns)]
    t0_box[0] = time.perf_counter()
    for t in threads:
        t.start()
    # Grace beyond the schedule for in-flight requests, then force-stop:
    # workers stuck behind a saturated server (e.g. SimpleServer never
    # accepting their connection) are unblocked by closing their sockets.
    deadline_join = duration_s + max(2.0, duration_s)
    for t in threads:
        t.join(timeout=max(deadline_join - (time.perf_counter() - t0_box[0]),
                           0.05))
    stop.set()
    with lock:
        snapshot = list(clients)
    for cl in snapshot:
        cl.reconnect = False
        try:
            cl.close()
        except OSError:
            pass
    for t in threads:
        t.join(timeout=1.0)
    with lock:
        # Sustained-throughput window: the schedule length, extended to the
        # last completion (stuck connections don't inflate it forever).
        elapsed = max(duration_s, last_done[0])
        xs = sorted(lats)
        done = dict(counts)
    from repro.serving.stats import LatencyTracker
    pct = LatencyTracker._interp_percentile
    n_sched = len(arrivals)
    return {
        "offered_qps": offered_qps,
        "achieved_qps": done["ok"] / max(elapsed, 1e-9),
        "p50_ms": pct(xs, 0.50) * 1e3,
        "p99_ms": pct(xs, 0.99) * 1e3,
        "n_scheduled": float(n_sched),
        "n_ok": float(done["ok"]),
        "n_shed": float(done["shed"]),
        "n_error": float(done["error"]),
        "shed_rate": done["shed"] / max(n_sched, 1),
        "duration_s": elapsed,
        "n_conns": float(n_conns),
    }


def sweep(address, reqs, qps_levels: Sequence[float], duration_s: float,
          n_conns: int = 4, deadline_s: Optional[float] = None,
          seed: int = 0) -> List[Dict[str, float]]:
    return [run_level(address, reqs, qps, duration_s, n_conns,
                      deadline_s, seed + i)
            for i, qps in enumerate(qps_levels)]


class _SlowRankHandler:
    """Wrap a pipeline handler with a fixed per-request delay — the
    'one artificially slow replica' of the hedging experiment (a straggler
    from GC, paging, a noisy neighbor...)."""

    def __init__(self, inner, delay_s: float):
        self._inner = inner
        self._delay_s = delay_s
        self.rows_per_query = getattr(inner, "rows_per_query", 1)

    def rank_batch(self, queries):
        time.sleep(self._delay_s)
        return self._inner.rank_batch(queries)


def run_hedged(world=None, backend: str = "jit", n_requests: int = 60,
               slow_delay_s: float = 0.05, hedge_s: float = 0.005
               ) -> List[Dict]:
    """Hedged vs unhedged ranking dispatch over two pipeline replicas, one
    slowed by ``slow_delay_s`` per request. Round-robin routing means the
    unhedged client eats the full delay on half its requests; the hedged
    client races the other replica after ``hedge_s`` and its p99 collapses
    to roughly hedge delay + fast service time."""
    from benchmarks.common import build_world
    from repro.core import ops
    from repro.core.plan import PlanContext
    from repro.serving.engine import PipelineEngine
    from repro.serving.hedge import HedgedTransport
    from repro.serving.stats import LatencyTracker

    cfg, params, corpus, tok, index, _ = world or build_world()
    pipeline = ops.Retrieve(h=10) >> ops.Rerank(backend, k=5)
    queries = corpus.questions[:16]

    def make_engine():
        return PipelineEngine(
            pipeline,
            PlanContext.from_world(cfg, params, corpus, tok, index,
                                   buckets=(64, 256, 1024)),
            target="batched")

    fast_eng, slow_eng = make_engine(), make_engine()
    srv_fast = SV.SimpleServer(fast_eng).start_background()
    srv_slow = SV.SimpleServer(
        _SlowRankHandler(slow_eng, slow_delay_s)).start_background()

    rows: List[Dict] = []
    pct = LatencyTracker._interp_percentile
    try:
        for tag, hedge in (("unhedged", float("inf")), ("hedged", hedge_s)):
            # Two clients (one socket per replica); hedge=inf IS the
            # unhedged baseline — identical code path, no second attempt.
            ht = HedgedTransport([SV.Client(srv_fast.address),
                                  SV.Client(srv_slow.address)],
                                 hedge_s=hedge)
            try:
                ht.rank(queries[0])     # warm compiled entries both ways
                ht.rank(queries[1])
                lats = []
                t0 = time.perf_counter()
                for i in range(n_requests):
                    t1 = time.perf_counter()
                    ht.rank(queries[i % len(queries)])
                    lats.append(time.perf_counter() - t1)
                dt = time.perf_counter() - t0
            finally:
                ht.close()
            xs = sorted(lats)
            s = ht.stats()
            rows.append({
                "name": f"loadgen/rank-{tag}",
                "us_per_call": 1e6 * dt / n_requests,
                "derived": (f"qps={n_requests / dt:.1f} "
                            f"p50_ms={pct(xs, 0.50) * 1e3:.2f} "
                            f"p99_ms={pct(xs, 0.99) * 1e3:.2f} "
                            f"hedged={int(s['hedged'])} "
                            f"hedge_wins={int(s['hedge_wins'])}"),
                "hedge": {"p50_ms": pct(xs, 0.50) * 1e3,
                          "p99_ms": pct(xs, 0.99) * 1e3,
                          "slow_delay_ms": slow_delay_s * 1e3,
                          **s},
            })
        # The v3 ranking service under open-loop Poisson load (run_level's
        # ranking-RPC mode): one Client.rank per scheduled arrival against
        # the fast replica.
        lvl = run_level(srv_fast.address, queries, offered_qps=50.0,
                        duration_s=1.0, n_conns=1, mode="rank")
        qps = max(lvl["achieved_qps"], 1e-9)
        rows.append({
            "name": "loadgen/rank-openloop-offered50",
            "us_per_call": 1e6 / qps,
            "derived": (f"qps={lvl['achieved_qps']:.1f} "
                        f"p50_ms={lvl['p50_ms']:.2f} "
                        f"p99_ms={lvl['p99_ms']:.2f} "
                        f"err={int(lvl['n_error'])}"),
            "loadgen": lvl,
        })
    finally:
        srv_fast.stop()
        srv_slow.stop()
    return rows


class _RouterConn:
    """One loadgen 'connection' over the fabric's shared router. The
    router serializes attempts per worker endpoint (one socket each), so
    M concurrent _RouterConns keep at most n_workers requests in flight —
    exactly the fleet's service parallelism. The router owns the sockets;
    close here is a no-op."""

    reconnect = False

    def __init__(self, router):
        self._router = router

    def rank(self, query, deadline_s=None):
        # The router's hedge path retries sheds/drains on the backup
        # worker; per-request deadlines stay client-side here (the
        # HedgedTransport protocol methods carry no deadline).
        return self._router.rank(query)

    def close(self):
        pass


def run_fabric(process_counts: Sequence[int] = (1, 2, 4),
               offered_qps: float = 60.0, duration_s: float = 3.0,
               backend: str = "numpy", train_steps: int = 1) -> List[Dict]:
    """Process-scaling sweep: for each N, spawn N pipeline-serving worker
    processes behind the health-probed hedging router and drive the same
    open-loop rank schedule through it. The client side needs only the
    query strings (the deterministic demo corpus), not a trained world —
    every worker process builds its own.

    Rows record ``host_cores``; interpret the curve against it (N worker
    processes on one core time-share that core, so the single-core curve
    is flat — the fabric removes the GIL ceiling, not the hardware's).
    """
    import os

    from repro.data import qa as QA
    from repro.serving.fabric import Fabric

    queries = QA.generate_corpus(n_docs=80, n_questions=60,
                                 seed=0).questions
    host_cores = float(os.cpu_count() or 1)
    rows: List[Dict] = []
    for n in process_counts:
        with Fabric(n_workers=n, backend=backend,
                    train_steps=train_steps) as fab:
            router = fab.router
            for q in queries[:max(2 * n, 4)]:
                router.rank(q)          # warm every worker's scoring path
            lvl = run_level(lambda wid: _RouterConn(router), queries,
                            offered_qps, duration_s,
                            n_conns=max(2 * n, 4), mode="rank")
            qps = max(lvl["achieved_qps"], 1e-9)
            rs = router.stats()
            rows.append({
                "name": f"loadgen/fabric-x{n}-offered{int(offered_qps)}",
                "us_per_call": 1e6 / qps,
                "derived": (f"qps={lvl['achieved_qps']:.1f} "
                            f"p50_ms={lvl['p50_ms']:.2f} "
                            f"p99_ms={lvl['p99_ms']:.2f} "
                            f"err={int(lvl['n_error'])} "
                            f"workers={n} "
                            f"host_cores={int(host_cores)}"),
                "fabric": {**lvl, "n_workers": float(n),
                           "host_cores": host_cores,
                           **{f"router_{k}": v for k, v in rs.items()}},
            })
    return rows


def _make_requests(corpus, pairs, n: int):
    reqs = []
    for qi, di, si, _ in (pairs * 50)[:n]:
        reqs.append((corpus.questions[qi], corpus.documents[di][si]))
    return reqs


def run(world=None, qps_levels: Sequence[float] = (100.0, 300.0),
        duration_s: float = 1.5, n_conns: int = 4, replicas: int = 2,
        backend: str = "jit") -> List[Dict]:
    """Benchmark entry (benchmarks.run): SimpleServer vs ThreadPoolServer x
    replicas on the same backend, same offered-QPS sweep, plus one overload
    level demonstrating deadline/queue shedding."""
    from benchmarks.common import build_world
    from repro.serving.admission import AdmissionController
    from repro.serving.cluster import ReplicaPool
    from repro.core import backends as BK

    cfg, params, corpus, tok, index, pairs = world or build_world()
    reqs = _make_requests(corpus, pairs, 512)
    rows: List[Dict] = []

    def to_row(tag: str, r: Dict[str, float]) -> Dict:
        qps = max(r["achieved_qps"], 1e-9)
        return {"name": f"loadgen/{tag}-offered{int(r['offered_qps'])}",
                "us_per_call": 1e6 / qps,
                "derived": (f"qps={r['achieved_qps']:.1f} "
                            f"p50_ms={r['p50_ms']:.2f} "
                            f"p99_ms={r['p99_ms']:.2f} "
                            f"shed={int(r['n_shed'])} "
                            f"err={int(r['n_error'])}"),
                "loadgen": r}

    # -- paper-faithful single-threaded server ------------------------------
    scorer = BK.make_scorer(backend, params, cfg, buckets=(1, 8, 64))
    handler = SV.QuestionAnsweringHandler(scorer, tok, corpus.idf,
                                          cfg.max_len)
    srv = SV.SimpleServer(handler).start_background()
    with SV.Client(srv.address) as cl:
        cl.get_score(*reqs[0])  # warm the compiled entry
    for r in sweep(srv.address, reqs, qps_levels, duration_s, n_conns):
        rows.append(to_row("simple", r))
    srv.stop()

    # -- threadpool server over a replica pool ------------------------------
    pool = ReplicaPool.build(backend, params, cfg, tok, corpus.idf,
                             n_replicas=replicas, buckets=(1, 8, 64),
                             policy="least_outstanding")
    # Warm every replica at every coalescing bucket so runtime jit
    # compilation doesn't masquerade as tail latency in the sweep.
    for bucket in (1, 8, 64):
        q_tok, a_tok, feats = pool.features.featurize_many(reqs[:bucket])
        for rep in pool.replicas:
            rep.batcher.submit_many(q_tok, a_tok, feats).result()
    admission = AdmissionController(max_queue_rows=256)
    srv = SV.ThreadPoolServer(pool, num_workers=max(n_conns * 2, 8),
                              admission=admission).start_background()
    with SV.Client(srv.address) as cl:
        cl.get_score(*reqs[0])
    tag = f"threadpool-x{replicas}"
    for r in sweep(srv.address, reqs, qps_levels, duration_s, n_conns):
        rows.append(to_row(tag, r))
    srv.stop()

    # Overload: many connections offering far past capacity against a tight
    # queue bound and deadline — the cluster must shed (SHED replies)
    # rather than queue unboundedly.
    over_conns = max(n_conns * 4, 16)
    srv = SV.ThreadPoolServer(pool, num_workers=over_conns,
                              admission=AdmissionController(max_queue_rows=8)
                              ).start_background()
    over = run_level(srv.address, reqs, offered_qps=qps_levels[-1] * 10,
                     duration_s=min(duration_s, 1.0), n_conns=over_conns,
                     deadline_s=0.05)
    rows.append(to_row(f"{tag}-overload", over))
    srv.stop()
    pool.stop()

    # Tail tolerance: hedged vs unhedged ranking RPCs with one replica
    # artificially slowed (Dean & Barroso's experiment in miniature).
    rows += run_hedged(world=(cfg, params, corpus, tok, index, pairs),
                       backend=backend)
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--processes", default=None, metavar="N,N,...",
                    help="fabric process-scaling sweep over these worker-"
                         "process counts (e.g. 1,2,4) instead of the "
                         "default server sweep")
    ap.add_argument("--qps", type=float, default=60.0,
                    help="offered QPS for the fabric sweep")
    ap.add_argument("--duration", type=float, default=3.0,
                    help="seconds per fabric sweep level")
    ap.add_argument("--backend", default="numpy",
                    help="worker scorer backend for the fabric sweep")
    ap.add_argument("--train-steps", type=int, default=1,
                    help="worker training steps for the fabric sweep")
    cli = ap.parse_args()
    if cli.processes:
        counts = tuple(int(x) for x in cli.processes.split(","))
        out = run_fabric(counts, offered_qps=cli.qps,
                         duration_s=cli.duration, backend=cli.backend,
                         train_steps=cli.train_steps)
    else:
        out = run()
    for row in out:
        print(f"{row['name']},{row['us_per_call']:.1f},{row['derived']}")
