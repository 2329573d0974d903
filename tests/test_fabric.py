"""Multi-process serving fabric (serving.fabric): worker spawn/discovery,
health-probed routing, graceful drain with zero in-flight loss, crash
detection + respawn, and plan() binding through the fabric router.

The fast smoke spawns 2 real worker processes (numpy backend, train_steps=1
— ~5s each, overlapped) and stays in the tier-1 fast set; the drain-under-
load and crash-respawn tests carry the slow marker.
"""
import threading
import time

import pytest

from repro.serving.fabric import (Fabric, FabricWorker, HealthRouter,
                                  WorkerEndpoint, chip_env, host_tpu_chips,
                                  opens_tpu)


@pytest.fixture(scope="module")
def fabric():
    # --plan-target remote puts a MicroBatcher-backed ReplicaPool inside
    # each worker, so the telemetry tests below can see the queue-wait vs
    # compute split that MSG_STATS reports per worker process.
    with Fabric(n_workers=2, backend="numpy", train_steps=1,
                probe_interval_s=0.05,
                extra_args=("--plan-target", "remote")) as fab:
        yield fab


# ------------------------------------------------------------------ smoke --

def test_fabric_smoke(fabric):
    """Spawn -> discover -> health-route -> rank -> stats, end to end."""
    assert all(w.alive for w in fabric.workers)
    snaps = fabric.router.snapshot()
    assert set(snaps) == {0, 1}
    for snap in snaps.values():
        assert snap["draining"] == 0.0
        assert snap["rows_per_query"] > 0
    out = fabric.router.rank_batch(["what is the capital",
                                    "who wrote the book"])
    assert len(out) == 2
    for ranking in out:
        assert ranking, "empty ranking from fabric worker"
        doc, sent, score = ranking[0]
        assert isinstance(doc, int) and isinstance(score, float)
    s = fabric.stats()
    assert s["alive_workers"] == 2.0
    assert s["router_routable_workers"] == 2.0


def test_fabric_plan_binding(fabric):
    """A Fabric binds into the pipeline algebra: plan(pipeline,
    'remote_pipeline', ctx) with ctx.remote = the fabric routes rankings
    through the HealthRouter."""
    from repro.configs import get_config, reduced
    from repro.core import ops
    from repro.core.plan import PlanContext, plan
    from repro.data import qa as QA
    from repro.data.tokenizer import HashingTokenizer

    cfg = reduced(get_config("sm-cnn"))
    corpus = QA.generate_corpus(n_docs=80, n_questions=60, seed=0)
    tok = HashingTokenizer(cfg.vocab_size)
    ctx = PlanContext(tokenizer=tok, idf=corpus.idf, max_len=cfg.max_len,
                      documents=corpus.documents, remote=fabric)
    pipeline = (ops.Retrieve(h=10) >> ops.DynamicCutoff(margin=3.0)
                >> ops.Rerank("numpy", k=3))
    pl = plan(pipeline, "remote_pipeline", ctx)
    assert "hedged" in pl.describe()
    out = pl.run_many(list(corpus.questions[:3]))
    assert len(out) == 3 and all(len(r) > 0 for r in out)


def test_router_routes_around_draining_worker(fabric):
    """After MSG_DRAIN a worker stops being routable; requests keep
    succeeding via the other worker; restart brings it back."""
    snap = fabric.drain_worker(0)
    assert snap["draining"] == 1.0 and snap["inflight"] == 0.0
    assert fabric.router.stats()["routable_workers"] == 1.0
    for q in ("during drain one", "during drain two"):
        assert fabric.router.rank_batch([q])[0]
    fabric.restart_worker(0)
    assert fabric.router.stats()["routable_workers"] == 2.0
    assert fabric.router.rank_batch(["after restart"])[0]


# ------------------------------------------------------------ heavy tests --

@pytest.mark.slow
def test_drain_under_load_loses_nothing():
    """The acceptance bar: drain a worker mid-load and count every
    request — zero errors, zero losses. New work sheds retriably at the
    draining worker and the router's hedge path fails it over; in-flight
    work finishes before drain returns."""
    with Fabric(n_workers=2, backend="numpy", train_steps=1,
                probe_interval_s=0.02) as fab:
        results = {"ok": 0, "err": []}
        lock = threading.Lock()
        stop = threading.Event()

        def pump(tid):
            i = 0
            while not stop.is_set():
                try:
                    out = fab.router.rank(f"load query {tid} {i}")
                    with lock:
                        results["ok"] += 1
                    assert out
                except Exception as e:  # noqa: BLE001 — counted, asserted
                    with lock:
                        results["err"].append(repr(e))
                i += 1

        threads = [threading.Thread(target=pump, args=(t,), daemon=True)
                   for t in range(4)]
        for th in threads:
            th.start()
        time.sleep(0.5)                     # load flowing through both
        snap = fab.drain_worker(0)          # drain mid-load
        assert snap["inflight"] == 0.0      # finished, not cancelled
        time.sleep(0.5)                     # load continues on worker 1
        stop.set()
        for th in threads:
            th.join(timeout=10.0)
        assert results["err"] == []         # ZERO lost requests
        assert results["ok"] > 20
        # the drained worker took no traffic after the drain settled
        assert fab.router.stats()["routable_workers"] == 1.0
        # ...and a restarted worker rejoins and serves again
        fab.restart_worker(0)
        assert fab.router.stats()["routable_workers"] == 2.0
        assert fab.router.rank_batch(["rejoined"])[0]


@pytest.mark.slow
def test_crashed_worker_is_respawned_and_rejoins():
    with Fabric(n_workers=2, backend="numpy", train_steps=1,
                probe_interval_s=0.02) as fab:
        victim = fab.workers[0]
        first_pid = victim.proc.pid
        victim.proc.kill()                  # hard crash, NOT expect_exit
        deadline = time.time() + 60.0
        while fab.respawns == 0 and time.time() < deadline:
            time.sleep(0.05)
        assert fab.respawns >= 1, "supervisor never respawned the worker"
        assert victim.alive and victim.proc.pid != first_pid
        # the respawned worker answers through the router again
        deadline = time.time() + 10.0
        while (fab.router.stats()["routable_workers"] < 2.0
               and time.time() < deadline):
            time.sleep(0.05)
        assert fab.router.stats()["routable_workers"] == 2.0
        assert fab.router.rank_batch(["after respawn"])[0]


# ------------------------------------------------------------- unit-level --

def test_worker_command_shape():
    w = FabricWorker(3, backend="jit", train_steps=7, workers=4,
                     max_queue=128)
    cmd = w.command()
    assert "--serve-pipeline" in cmd
    assert cmd[cmd.index("--backend") + 1] == "jit"
    assert cmd[cmd.index("--train-steps") + 1] == "7"
    assert cmd[cmd.index("--port") + 1] == "0"
    assert "-u" in cmd                      # unbuffered: READY must flush


@pytest.fixture
def tpu_host(monkeypatch):
    """A host with ``n`` TPU chips whose workers open the TPU."""
    import repro.serving.fabric as FB

    def make(n):
        monkeypatch.setattr(FB, "host_tpu_chips", lambda: n)
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    return make


def test_each_worker_gets_its_own_chip(monkeypatch, tpu_host):
    tpu_host(4)
    fab = Fabric(n_workers=3, supervise=False)
    assert [w.env for w in fab.workers] == [chip_env(i, 4) for i in range(3)]
    assert [w.env["TPU_VISIBLE_CHIPS"] for w in fab.workers] == ["0", "1",
                                                                 "2"]
    assert all(w.env["TPU_PROCESS_BOUNDS"] == "1,1,1" for w in fab.workers)
    # The spawned process sees the slot's variables over the parent's.
    import repro.serving.fabric as FB
    seen = {}

    class _Popen:
        def __init__(self, cmd, env, **kw):
            seen.update(env)
            self.stdout = iter(())
            self.pid = 0

        def poll(self):
            return 0

    monkeypatch.setattr(FB.subprocess, "Popen", _Popen)
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0,1,2,3")
    fab.workers[2].spawn()
    assert seen["TPU_VISIBLE_CHIPS"] == "2"
    assert seen["PYTHONPATH"].startswith(FB._src_root())


def test_no_chip_environment_without_chips(tpu_host):
    tpu_host(0)
    fab = Fabric(n_workers=2, supervise=False)
    assert [w.env for w in fab.workers] == [{}, {}]


@pytest.mark.parametrize("platforms", ["cpu", "CPU", "cuda,cpu"])
def test_workers_off_the_tpu_need_no_chip(monkeypatch, tpu_host, platforms):
    # A one-chip host still runs a 2-worker fabric held off the TPU.
    tpu_host(1)
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    fab = Fabric(n_workers=2, supervise=False)
    assert [w.env for w in fab.workers] == [{}, {}]


@pytest.mark.parametrize("env,opens", [
    ({}, True), ({"JAX_PLATFORMS": ""}, True),
    ({"JAX_PLATFORMS": "tpu"}, True), ({"JAX_PLATFORMS": "tpu,cpu"}, True),
    ({"JAX_PLATFORMS": "cpu"}, False), ({"JAX_PLATFORMS": "cpu,cuda"}, False),
])
def test_opens_tpu(env, opens):
    assert opens_tpu(env) is opens


def test_more_workers_than_chips_is_refused_before_spawning(tpu_host):
    tpu_host(4)
    with pytest.raises(ValueError, match="5 fabric workers .* 4 TPU chips"):
        Fabric(n_workers=5, supervise=False)
    with pytest.raises(ValueError, match="slot 4 has no chip"):
        chip_env(4, 4)


def test_host_tpu_chips_counts_device_files(monkeypatch, tmp_path):
    import repro.serving.fabric as FB
    dev, groups = tmp_path / "dev", tmp_path / "iommu_groups"
    monkeypatch.setattr(FB, "_DEV", dev)
    monkeypatch.setattr(FB, "_IOMMU_GROUPS", groups)
    assert host_tpu_chips() == 0
    (dev / "vfio").mkdir(parents=True)
    for group, vendor in (("0", FB.GOOGLE_PCI_VENDOR),
                          ("1", FB.GOOGLE_PCI_VENDOR),
                          ("2", "0x10de"),               # not a TPU
                          ("3", FB.GOOGLE_PCI_VENDOR)):  # not passed through
        pci = groups / group / "devices" / f"0000:00:0{group}.0"
        pci.mkdir(parents=True)
        (pci / "vendor").write_text(vendor + "\n")
        if group != "3":
            (dev / "vfio" / group).touch()
    (dev / "vfio" / "vfio").touch()                      # the control file
    assert host_tpu_chips() == 2
    (dev / "accel0").touch()
    assert host_tpu_chips() == 3


def test_health_router_prefers_less_loaded_worker():
    class _FakeEndpoint:
        def __init__(self, slot):
            self.slot = slot
            self.client = object()

        def close(self):
            pass

    router = HealthRouter([_FakeEndpoint(0), _FakeEndpoint(1),
                           _FakeEndpoint(2)])
    router._snaps = {
        0: {"queue_depth": 50.0, "inflight": 2.0, "draining": 0.0},
        1: {"queue_depth": 0.0, "inflight": 0.0, "draining": 0.0},
        2: {"queue_depth": 8.0, "inflight": 1.0, "draining": 0.0},
    }
    primary, backup = router._pick_endpoints()
    assert primary == 1                     # idle worker wins
    assert backup == 2                      # next least-loaded hedges
    # Draining workers drop out of routing entirely.
    router._snaps[1]["draining"] = 1.0
    primary, backup = router._pick_endpoints()
    assert primary == 2 and backup == 0
    # Dead workers too — and with nobody routable we fall back to
    # round-robin over everything rather than stalling.
    router._snaps[0]["draining"] = 1.0
    router._alive[2] = False
    primary, backup = router._pick_endpoints()
    assert primary in (0, 1, 2) and backup is not None


def test_health_router_spreads_ties_round_robin():
    class _FakeEndpoint:
        def __init__(self, slot):
            self.client = object()

        def close(self):
            pass

    router = HealthRouter([_FakeEndpoint(0), _FakeEndpoint(1)])
    router._snaps = {
        0: {"queue_depth": 0.0, "inflight": 0.0, "draining": 0.0},
        1: {"queue_depth": 0.0, "inflight": 0.0, "draining": 0.0},
    }
    primaries = {router._pick_endpoints()[0] for _ in range(4)}
    assert primaries == {0, 1}              # an idle fleet still spreads


class _StubRestartWorker:
    """FabricWorker stand-in whose wait_ready parks on an event, so a test
    can hold a respawn mid-flight and inspect the fabric's lock state."""

    def __init__(self, slot):
        self.slot = slot
        self.alive = False
        self.spawned = 0
        self.release = threading.Event()

    def spawn(self):
        self.spawned += 1
        self.alive = True

    def wait_ready(self, timeout_s):
        assert self.release.wait(10.0), "test never released wait_ready"
        return ("127.0.0.1", 9000 + self.slot)


class _StubRouter:
    def __init__(self):
        self.replaced = []
        self.probes = 0

    def replace_endpoint(self, slot, ep):
        self.replaced.append((slot, ep))

    def probe_once(self):
        self.probes += 1


def test_respawn_claims_slot_then_works_outside_the_lock(monkeypatch):
    """Regression (repro-lint LOCK001): _respawn/restart_worker used to
    hold Fabric._lock across spawn + wait_ready + probe — seconds of
    blocking under the bookkeeping lock, so stats() readers and any
    concurrent restart froze behind one slot's respawn. The slot is now
    CLAIMED under the lock (a set entry) and all slow work happens with
    the lock released; a second actor hitting the same slot backs off
    instead of queueing."""
    import repro.serving.fabric as FB

    # The real WorkerEndpoint connects eagerly in __init__; the stub just
    # records what the router was handed.
    monkeypatch.setattr(FB, "WorkerEndpoint",
                        lambda slot, addr: ("ep", slot, addr))
    fab = Fabric(n_workers=2, supervise=False)
    w0, w1 = _StubRestartWorker(0), _StubRestartWorker(1)
    fab.workers = [w0, w1]
    fab.router = _StubRouter()

    t = threading.Thread(target=fab._respawn, args=(w0,), daemon=True)
    t.start()
    deadline = time.time() + 5.0
    while w0.spawned == 0 and time.time() < deadline:
        time.sleep(0.001)
    assert w0.spawned == 1              # parked inside wait_ready now

    # The bookkeeping lock is FREE while slot 0 respawns ...
    assert fab._lock.acquire(timeout=1.0), \
        "_respawn holds Fabric._lock across wait_ready"
    fab._lock.release()
    # ... the slot itself is claimed, other slots stay claimable ...
    assert not fab._claim_slot(0)
    assert fab._claim_slot(1)
    fab._release_slot(1)
    # ... a racing respawn of the same slot is a silent no-op ...
    fab._respawn(w0)
    assert w0.spawned == 1
    # ... and an explicit restart of the same slot refuses loudly.
    with pytest.raises(RuntimeError, match="already restarting"):
        fab.restart_worker(0)

    w0.release.set()
    t.join(timeout=10.0)
    assert not t.is_alive()
    assert fab.respawns == 1
    assert fab.router.replaced == [(0, ("ep", 0, ("127.0.0.1", 9000)))]
    assert fab.router.probes == 1
    assert fab._claim_slot(0)           # slot released after the respawn
    fab._release_slot(0)


# ------------------------------------------------------------- telemetry --

def test_trace_crosses_process_boundary(fabric):
    """The observability acceptance bar: ONE query fired at the fabric
    yields ONE trace whose span tree crosses the process boundary — the
    router-side client span parents the worker-side server/batcher/scorer
    spans fetched back over MSG_STATS."""
    import os

    from repro.serving import telemetry

    tr = telemetry.get_tracer()
    tr.clear()
    with tr.span("test.request") as root:
        out = fabric.router.rank("follow this query across processes")
    assert out
    trace_id = root.context.trace_id

    spans = fabric.collect_spans(trace_id)
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)

    # Router side of the tree, recorded in THIS process.
    assert "hedge.primary" in by_name
    client_ids = {s.span_id for s in by_name.get("client.rank", ())}
    assert client_ids, "router-side client span missing from the trace"

    # Worker side, fetched over the wire: same trace, different pid, and
    # the server span's parent is the router's client span.
    here = os.getpid()
    servers = by_name.get("server.rank", [])
    assert servers, "worker-side server span never joined the trace"
    assert all(s.pid != here for s in servers)
    assert any(s.parent_id in client_ids for s in servers)
    for name in ("admission", "engine.rank_many", "pool.get_scores",
                 "batcher.queue_wait", "batcher.compute", "scorer"):
        assert name in by_name, f"span {name!r} missing from worker side"
        assert all(s.pid != here for s in by_name[name]), name

    # The assembled tree has the test's root at the top and the worker
    # spans reachable beneath it — one connected tree, two processes.
    roots, children = telemetry.span_tree(spans, trace_id=trace_id)
    assert [r.name for r in roots] == ["test.request"]

    def walk(span):
        yield span
        for kid in children.get(span.span_id, ()):
            yield from walk(kid)

    reach = {s.name for s in walk(roots[0])}
    assert {"client.rank", "server.rank", "batcher.compute",
            "scorer"} <= reach
    text = telemetry.format_span_tree(spans, trace_id=trace_id)
    assert text.splitlines()[0].startswith("test.request")


def test_msg_stats_aggregates_batcher_histograms(fabric):
    """MSG_STATS returns each live worker's registry snapshot, including
    the batcher queue-wait vs compute histograms; the fabric-wide
    aggregate is their key-wise sum."""
    for i in range(6):                      # tie-spread routing feeds both
        assert fabric.router.rank_batch([f"stats traffic {i}"])[0]
    per_worker = fabric.worker_metrics()
    assert set(per_worker) == {0, 1}
    for slot, snap in per_worker.items():
        assert snap.get("batcher_queue_wait_ms_count", 0.0) > 0.0, slot
        assert snap.get("batcher_compute_ms_count", 0.0) > 0.0, slot
        assert any(k.startswith("batcher_queue_wait_ms_bucket{")
                   for k in snap), slot
        assert snap.get("server_requests{type=rank}", 0.0) > 0.0, slot
    agg = fabric.aggregate_metrics()
    assert agg["batcher_compute_ms_count"] == pytest.approx(
        sum(s["batcher_compute_ms_count"] for s in per_worker.values()))
    assert agg["batcher_queue_wait_ms_count"] >= 2.0


def test_cross_process_chrome_trace_exports(fabric, tmp_path):
    """Spans collected across the fabric export as valid Chrome
    trace-event JSON with one pid lane per process."""
    import json
    import os

    from repro.serving import telemetry

    tr = telemetry.get_tracer()
    tr.clear()
    with tr.span("test.export") as root:
        fabric.router.rank_batch(["export this trace"])
    spans = fabric.collect_spans(root.context.trace_id)
    path = tmp_path / "fabric_trace.json"
    n = telemetry.export_chrome_trace(str(path), spans)
    assert n == len(spans) > 0
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    assert len(events) == n
    for ev in events:
        assert ev["ph"] == "X"
        assert ev["ts"] > 0.0 and ev["dur"] >= 0.0
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
    pids = {ev["pid"] for ev in events}
    assert len(pids) >= 2, "trace should span router + worker processes"
    assert os.getpid() in pids
