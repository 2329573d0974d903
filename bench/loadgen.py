"""Load generation against a ranking server: one general generator that a
traffic file parameterizes.

Open loop (``"loop": "open"``): Poisson arrivals at a fixed offered rate.
The inter-arrival gaps are the exponential distribution's quantiles, in
an order the traffic file's ``schedule_seed`` draws, scaled to fill the
window: every run offers the same rate x seconds arrivals. A dispatcher
thread releases each request at its scheduled time into a queue that
every connection drains, so a request never waits behind one slow
connection while another is free. Latency is timed from
the scheduled arrival (a late dispatcher or a busy connection counts),
and how late the dispatcher ran is reported.

Closed loop (``"loop": "closed"``): ``connections`` clients, each sending
``batch`` distinct queries per ``rank_batch`` back to back until the
window closes. Its rate is each connection's queries over the time to its
last reply, summed: every batch sent in the window counts, with the time
it took, so no batch is cut at the window's edge.

Copied from ``benchmarks/loadgen.py`` (``poisson_arrivals``/``run_level``)
and changed in the two ways that file gets wrong: in-flight requests are
no longer capped by a fixed striping over connections, and generator
lateness is measured.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np


@dataclasses.dataclass
class Request:
    queries: List[int]            # indices into the run's query list
    t_sched: float = 0.0          # offset from window start, seconds
    t_dispatch: float = 0.0
    t_send: float = 0.0
    t_done: float = 0.0
    rankings: Optional[list] = None
    error: Optional[str] = None
    conn: int = 0                 # the connection that sent it

    @property
    def ok(self) -> bool:
        return self.rankings is not None and self.error is None


def poisson_schedule(rate: float, seconds: float,
                     rng: np.random.Generator) -> np.ndarray:
    """round(rate * seconds) arrival offsets in [0, seconds): exponential
    gaps at fixed quantiles, in an order drawn from ``rng``, scaled so the
    last gap ends at ``seconds``."""
    n = max(int(round(rate * seconds)), 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = rng.permutation(gaps)
    gaps *= seconds / gaps.sum()
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def open_loop(connect: Callable, texts: Sequence[str], rate: float,
              seconds: float, connections: int, rng: np.random.Generator,
              drain_s: float) -> Dict:
    """Run the open loop; returns {"requests": [...]}. Request i carries
    query i."""
    sched = poisson_schedule(rate, seconds, rng)
    if len(sched) > len(texts):
        raise ValueError(f"{len(sched)} arrivals but {len(texts)} queries")
    reqs = [Request([i], t_sched=float(t)) for i, t in enumerate(sched)]
    work: "queue.Queue[Optional[Request]]" = queue.Queue()
    clients = [connect() for _ in range(connections)]
    t0 = time.perf_counter()

    def send(client):
        while True:
            r = work.get()
            if r is None:
                return
            r.t_send = time.perf_counter() - t0
            try:
                r.rankings = [client.rank(texts[r.queries[0]])]
            except Exception as e:  # noqa: BLE001 - counted as failed
                r.error = f"{type(e).__name__}: {e}"
            r.t_done = time.perf_counter() - t0

    senders = [threading.Thread(target=send, args=(c,), daemon=True)
               for c in clients]
    for s in senders:
        s.start()
    for r in reqs:
        wait = r.t_sched - (time.perf_counter() - t0)
        if wait > 0:
            time.sleep(wait)
        r.t_dispatch = time.perf_counter() - t0
        work.put(r)
    for _ in senders:
        work.put(None)
    for s in senders:
        s.join(timeout=max(drain_s - (time.perf_counter() - t0 - seconds),
                           0.1))
    hung = [s for s in senders if s.is_alive()]
    for c in clients:
        c.close()
    for s in hung:
        s.join(timeout=5.0)
    end = time.perf_counter() - t0
    for r in reqs:
        if r.rankings is None and r.error is None:
            r.error = "no reply before the drain ended"
            r.t_done = end
    return {"requests": reqs}


def closed_loop(connect: Callable, query: Callable[[int], str],
                connections: int, batch: int, seconds: float,
                drain_s: float) -> Dict:
    """``connections`` clients each sending ``batch`` distinct queries per
    ``rank_batch`` until the window closes; returns {"requests": [...]}.
    Queries are handed out in order: ``query(j)`` is query j."""
    lock = threading.Lock()
    next_q = [0]
    done: List[Request] = []
    clients = [connect() for _ in range(connections)]
    t0 = time.perf_counter()

    def loop(k, client):
        while time.perf_counter() - t0 < seconds:
            with lock:
                i = next_q[0]
                next_q[0] += batch
            r = Request(list(range(i, i + batch)), conn=k)
            texts = [query(j) for j in r.queries]
            r.t_sched = r.t_dispatch = r.t_send = time.perf_counter() - t0
            try:
                r.rankings = client.rank_batch(texts)
            except Exception as e:  # noqa: BLE001 - counted as failed
                r.error = f"{type(e).__name__}: {e}"
            r.t_done = time.perf_counter() - t0
            with lock:
                done.append(r)

    threads = [threading.Thread(target=loop, args=(k, c), daemon=True)
               for k, c in enumerate(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=seconds + drain_s)
    for c in clients:
        c.close()
    for t in threads:
        t.join(timeout=5.0)
    return {"requests": sorted(done, key=lambda r: r.t_send)}


def closed_rate(reqs: Sequence[Request]) -> float:
    """Queries per second of a closed loop: for each connection, the
    queries of its answered batches over the time to its last reply (it
    was busy all of that time), summed over connections."""
    rate = 0.0
    for k in {r.conn for r in reqs}:
        mine = [r for r in reqs if r.conn == k]
        end = max(r.t_done for r in mine)
        if end > 0:
            rate += sum(len(r.queries) for r in mine if r.ok) / end
    return rate


def latency_ms(reqs: Sequence[Request]) -> np.ndarray:
    """Every request's latency from its scheduled arrival (a failed one
    counts with the time until it failed or the drain gave up)."""
    return np.asarray([(r.t_done - r.t_sched) * 1e3 for r in reqs])


def lateness_ms(reqs: Sequence[Request]) -> Dict[str, float]:
    """How late the dispatcher released requests, and how long released
    requests waited for a free connection."""
    late = np.asarray([(r.t_dispatch - r.t_sched) * 1e3 for r in reqs])
    wait = np.asarray([(r.t_send - r.t_dispatch) * 1e3 for r in reqs])
    return {"dispatch_late_p50_ms": float(np.median(late)),
            "dispatch_late_max_ms": float(late.max()),
            "conn_wait_p50_ms": float(np.median(wait)),
            "conn_wait_max_ms": float(wait.max())}
