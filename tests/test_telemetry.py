"""Telemetry fabric unit tests: registry flattening/merging, tracer context
propagation (stack, explicit parent, cross-thread activation), Chrome trace
export, the span-tree renderers, and spans on a JAX profile's host
plane."""
import glob
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from repro.serving import telemetry
from repro.serving.telemetry import (MetricsRegistry, SpanContext,
                                     SpanRecord, Tracer, merge_snapshots)


# ------------------------------------------------------------- registry --

def test_counter_and_gauge_snapshot_keys():
    reg = MetricsRegistry()
    reg.inc("requests")
    reg.inc("requests", 2.0)
    reg.inc("requests", type="rank")
    reg.set_gauge("depth", 7.0, worker=3)
    snap = reg.snapshot()
    assert snap["requests"] == 3.0
    assert snap["requests{type=rank}"] == 1.0
    assert snap["depth{worker=3}"] == 7.0


def test_histogram_flattens_to_cumulative_buckets():
    reg = MetricsRegistry()
    for v in (0.05, 0.3, 0.3, 40.0):
        reg.observe("wait_ms", v, buckets=(0.1, 1.0, 50.0))
    snap = reg.snapshot()
    assert snap["wait_ms_bucket{le=0.1}"] == 1.0       # cumulative
    assert snap["wait_ms_bucket{le=1}"] == 3.0
    assert snap["wait_ms_bucket{le=50}"] == 4.0
    assert snap["wait_ms_bucket{le=+inf}"] == 4.0
    assert snap["wait_ms_count"] == 4.0
    assert snap["wait_ms_sum"] == pytest.approx(40.65)


def test_histogram_over_top_bucket_lands_in_inf_only():
    reg = MetricsRegistry()
    reg.observe("ms", 999.0, buckets=(1.0,))
    snap = reg.snapshot()
    assert snap["ms_bucket{le=1}"] == 0.0
    assert snap["ms_bucket{le=+inf}"] == 1.0


def test_labeled_histogram_keys_carry_labels():
    reg = MetricsRegistry()
    reg.observe("batch_ms", 3.0, buckets=(5.0,), backend="jit", bucket=64)
    snap = reg.snapshot()
    assert snap["batch_ms_bucket{le=5,backend=jit,bucket=64}"] == 1.0
    assert snap["batch_ms_count{backend=jit,bucket=64}"] == 1.0
    assert snap["batch_ms_sum{backend=jit,bucket=64}"] == 3.0


def test_merge_snapshots_sums_into_valid_histogram():
    """Cumulative bucket counts from N workers must sum to the histogram
    of the union — the property Fabric.aggregate_metrics relies on."""
    a, b = MetricsRegistry(), MetricsRegistry()
    for v in (0.5, 2.0):
        a.observe("ms", v, buckets=(1.0, 10.0))
    for v in (0.7, 20.0):
        b.observe("ms", v, buckets=(1.0, 10.0))
    merged = merge_snapshots([a.snapshot(), b.snapshot()])
    assert merged["ms_bucket{le=1}"] == 2.0
    assert merged["ms_bucket{le=10}"] == 3.0
    assert merged["ms_bucket{le=+inf}"] == 4.0
    assert merged["ms_count"] == 4.0
    assert merged["ms_sum"] == pytest.approx(23.2)


def test_registry_reset_clears_everything():
    reg = MetricsRegistry()
    reg.inc("c")
    reg.set_gauge("g", 1.0)
    reg.observe("h", 1.0)
    reg.reset()
    assert reg.snapshot() == {}


def test_registry_concurrent_inc():
    reg = MetricsRegistry()

    def hammer():
        for _ in range(1000):
            reg.inc("n")

    threads = [threading.Thread(target=hammer) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert reg.snapshot()["n"] == 8000.0


# --------------------------------------------------------------- tracer --

def test_nested_spans_share_trace_and_link_parents():
    tr = Tracer()
    with tr.span("outer") as outer:
        with tr.span("inner"):
            pass
    spans = {s.name: s for s in tr.finished()}
    assert spans["inner"].trace_id == spans["outer"].trace_id
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert spans["outer"].parent_id == 0           # fresh root
    assert outer.context.trace_id == spans["outer"].trace_id


def test_sibling_roots_get_distinct_traces():
    tr = Tracer()
    with tr.span("a"):
        pass
    with tr.span("b"):
        pass
    a, b = tr.finished()
    assert a.trace_id != b.trace_id


def test_explicit_parent_joins_foreign_trace():
    """The wire pattern: a context decoded off a frame parents the server
    span into the caller's trace."""
    tr = Tracer()
    foreign = SpanContext(1234, 5678)
    with tr.span("server.rank", parent=foreign):
        with tr.span("stage"):
            pass
    spans = {s.name: s for s in tr.finished()}
    assert spans["server.rank"].trace_id == 1234
    assert spans["server.rank"].parent_id == 5678
    assert spans["stage"].trace_id == 1234


def test_activate_hands_context_across_threads():
    tr = Tracer()
    captured = {}

    def worker(parent):
        with tr.activate(parent):
            with tr.span("in_thread"):
                pass
        captured["ctx"] = parent

    with tr.span("root") as root:
        t = threading.Thread(target=worker, args=(root.context,))
        t.start()
        t.join(timeout=10)
    spans = {s.name: s for s in tr.finished()}
    assert spans["in_thread"].trace_id == spans["root"].trace_id
    assert spans["in_thread"].parent_id == spans["root"].span_id


def test_record_explicit_interval_with_parent():
    tr = Tracer()
    with tr.span("root") as root:
        parent = root.context
    ctx = tr.record("queue_wait", 10.0, 10.005, parent=parent, rows=4)
    (rec,) = tr.finished(trace_id=parent.trace_id)[1:]
    assert rec.name == "queue_wait"
    assert rec.dur_us == pytest.approx(5000.0)
    assert rec.attrs["rows"] == 4
    assert ctx.trace_id == parent.trace_id


def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    sp = tr.span("x")
    assert sp is telemetry.NOOP_SPAN
    with sp:
        assert tr.current_context() is None
    assert tr.record("y", 0.0, 1.0) is None
    assert tr.finished() == []


def test_ring_is_bounded():
    tr = Tracer(max_spans=16)
    for i in range(64):
        with tr.span(f"s{i}"):
            pass
    spans = tr.finished()
    assert len(spans) == 16
    assert spans[-1].name == "s63"      # most recent survive


def test_finished_filter_and_limit():
    tr = Tracer()
    with tr.span("a") as a:
        with tr.span("a.child"):
            pass
    with tr.span("b"):
        pass
    only_a = tr.finished(trace_id=a.context.trace_id)
    assert {s.name for s in only_a} == {"a", "a.child"}
    assert len(tr.finished(limit=1)) == 1


def test_span_attrs_set_during_block():
    tr = Tracer()
    with tr.span("s", rows=3) as sp:
        sp.set_attr("shed", "queue_full")
    (rec,) = tr.finished()
    assert rec.attrs == {"rows": 3, "shed": "queue_full"}


# ----------------------------------------------------- wire span tuples --

def test_span_record_wire_roundtrip():
    rec = SpanRecord(1, 2, 3, "server.rank", 1000.5, 42.0, 777, 9,
                     {"rows": 80, "shed": "draining"})
    back = SpanRecord.from_wire(rec.to_wire())
    assert (back.trace_id, back.span_id, back.parent_id) == (1, 2, 3)
    assert back.name == "server.rank"
    assert back.ts_us == rec.ts_us and back.dur_us == rec.dur_us
    assert back.pid == 777
    assert back.attrs == {"rows": "80", "shed": "draining"}  # stringified


def test_wire_spans_cap():
    tr = Tracer()
    for i in range(600):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.wire_spans(limit=512)) == 512


# ------------------------------------------------- rendering / export ----

def _demo_spans(tr: Tracer) -> None:
    with tr.span("client.rank", endpoint="x"):
        with tr.span("server.rank", rows=80):
            with tr.span("scorer"):
                pass


def test_span_tree_assembles_roots_and_children():
    tr = Tracer()
    _demo_spans(tr)
    roots, children = telemetry.span_tree(tr.finished())
    assert [r.name for r in roots] == ["client.rank"]
    kid = children[roots[0].span_id][0]
    assert kid.name == "server.rank"
    assert children[kid.span_id][0].name == "scorer"


def test_span_tree_orphan_becomes_root():
    """Worker-side spans fetched without the client half still render."""
    tr = Tracer()
    with tr.span("worker_only", parent=SpanContext(9, 9)):
        pass
    roots, _ = telemetry.span_tree(tr.finished())
    assert [r.name for r in roots] == ["worker_only"]


def test_format_span_tree_indents_by_depth():
    tr = Tracer()
    _demo_spans(tr)
    text = telemetry.format_span_tree(tr.finished())
    lines = text.splitlines()
    assert lines[0].startswith("client.rank")
    assert lines[1].startswith("  server.rank")
    assert lines[2].startswith("    scorer")
    assert "rows=80" in lines[1]


def test_export_chrome_trace_validates(tmp_path):
    """The exported file must be loadable Chrome trace-event JSON: a
    traceEvents list of complete ("X") events with µs ts/dur and
    pid/tid/args fields — what Perfetto/chrome://tracing require."""
    tr = Tracer()
    _demo_spans(tr)
    path = tmp_path / "trace.json"
    n = telemetry.export_chrome_trace(str(path), tr.finished())
    assert n == 3
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    assert len(events) == 3
    for ev in events:
        assert ev["ph"] == "X"
        assert isinstance(ev["name"], str)
        assert ev["dur"] >= 0.0 and ev["ts"] > 0.0
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert set(ev["args"]) >= {"trace_id", "span_id", "parent_id"}
    by_name = {e["name"]: e for e in events}
    assert by_name["server.rank"]["args"]["rows"] == "80"
    # parent/child wall-clock containment holds in the exported view
    srv, sc = by_name["server.rank"], by_name["scorer"]
    assert srv["ts"] <= sc["ts"]
    assert sc["ts"] + sc["dur"] <= srv["ts"] + srv["dur"] + 1.0


def test_chrome_trace_tid_remap_is_stable_per_thread():
    tr = Tracer()
    with tr.span("a"):
        pass
    with tr.span("b"):
        pass
    events = telemetry.chrome_trace_events(tr.finished())
    assert events[0]["tid"] == events[1]["tid"]   # same thread, same lane


# ------------------------------------------------------ process default --

def test_reset_all_clears_default_registry_and_tracer():
    telemetry.get_registry().inc("junk")
    with telemetry.get_tracer().span("junk"):
        pass
    telemetry.reset_all()
    assert "junk" not in telemetry.get_registry().snapshot()
    assert telemetry.get_tracer().finished() == []


# --------------------------------------------------- the profiler's clock --

def _host_events(log_dir):
    """{name: [(line index, start_ns, end_ns, stats)]} over every /host:
    plane of the newest profile under ``log_dir``."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                out.setdefault(e.name, []).append(
                    (li, e.start_ns, e.end_ns, dict(e.stats)))
    return out


def test_spans_show_on_the_profile_host_plane(tmp_path):
    """While a JAX profile is taken, a span is a host-plane event under its
    name, on its own thread's line, nested in its parent's interval, and
    still lands in the ring."""
    import jax
    tr = Tracer()

    def worker():
        with tr.span("test.worker_span"):
            time.sleep(0.002)

    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("test.outer_span", rows=3):
            with tr.span("test.inner_span"):
                time.sleep(0.002)
            t = threading.Thread(target=worker)
            t.start()
            t.join(timeout=10)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    (outer,) = events["test.outer_span"]
    (inner,) = events["test.inner_span"]
    (other,) = events["test.worker_span"]
    assert outer[0] == inner[0] != other[0]      # one line per thread
    assert outer[1] <= inner[1] and inner[2] <= outer[2]
    assert outer[3]["rows"] == 3                  # the span's attributes
    assert {s.name for s in tr.finished()} == {
        "test.outer_span", "test.inner_span", "test.worker_span"}


def test_spans_land_in_the_ring_with_no_profile_running():
    from jax.profiler import TraceAnnotation
    assert not TraceAnnotation.is_enabled()
    tr = Tracer()
    with tr.span("a", rows=1):
        with tr.span("b"):
            pass
    spans = {s.name: s for s in tr.finished()}
    assert set(spans) == {"a", "b"}
    assert spans["b"].parent_id == spans["a"].span_id
    assert spans["a"].attrs == {"rows": 1}


def test_telemetry_runs_without_jax():
    """Wire-only processes never import jax; spans still work there."""
    code = ("import sys\n"
            "from repro.serving import telemetry\n"
            "tr = telemetry.Tracer()\n"
            "with tr.span('x', cpu=True):\n"
            "    pass\n"
            "assert [s.name for s in tr.finished()] == ['x']\n"
            "assert 'jax' not in sys.modules\n")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        p for p in sys.path if p))
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=60)


def test_cpu_span_carries_thread_cpu_time():
    tr = Tracer()
    with tr.span("sleeps", cpu=True):
        time.sleep(0.05)
    with tr.span("spins", cpu=True):
        t_end = time.perf_counter() + 0.02
        while time.perf_counter() < t_end:
            pass
    with tr.span("plain"):
        pass
    spans = {s.name: s for s in tr.finished()}
    assert spans["sleeps"].attrs["cpu_ms"] < 0.5 * spans["sleeps"].dur_us / 1e3
    assert 0 < spans["spins"].attrs["cpu_ms"] <= spans["spins"].dur_us / 1e3 + 1
    assert "cpu_ms" not in spans["plain"].attrs
