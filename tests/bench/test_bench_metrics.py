"""Per-layer metric readers on hand-made spans, counters and requests:
each reads its number, and reads nothing where there is nothing."""
import types

import pytest

from bench import flops, harness, loadgen
from benchtree import ROOT


def _span(name, sid, parent, ts, dur, **attrs):
    return types.SimpleNamespace(name=name, span_id=sid, parent_id=parent,
                                 ts_us=ts, dur_us=dur, tid=1, attrs=attrs)


def _run(spans=(), registry=None, requests=(), trace=None):
    cell = types.SimpleNamespace(
        chips=1, config={"trace": {"scorer_program": "jit__lambda"}})
    model = {"vocab_size": 30000, "embed_dim": 50, "conv_filters": 100,
             "filter_width": 5, "n_extra_feats": 4, "n_hidden": 204,
             "max_len": 64}
    return harness.RunData(cell, list(requests), 10.0, list(spans),
                           registry or {}, trace, "TPU v5 lite", model)


SPANS = [
    _span("client.rank", 1, 0, 0, 5000),
    _span("server.rank", 2, 1, 1000, 3000),
    _span("stage.bm25-h10", 3, 2, 1000, 1200, queries=2),
    _span("pool.get_scores", 4, 2, 2300, 1000),
    _span("batcher.queue_wait", 5, 4, 2500, 200),
    _span("batcher.compute", 6, 4, 2700, 300),
    _span("scorer", 7, 6, 2700, 300, rows=64, bucket=64),
]
REGISTRY = {"batcher_queue_wait_ms_sum": 3.0, "batcher_queue_wait_ms_count": 2,
            "scorer_batch_ms_sum{backend=aot,bucket=64}": 4.0,
            "scorer_batch_ms_count{backend=aot,bucket=64}": 1,
            "scorer_batch_ms_sum{backend=aot,bucket=8}": 2.0,
            "scorer_batch_ms_count{backend=aot,bucket=8}": 1,
            "batcher_batch_rows_sum": 640.0, "batcher_batch_rows_count": 10}


@pytest.mark.parametrize("metric,want", [
    ("rpc_ms.interactive", 2.0),
    ("retrieve_ms.bulk", 0.6),
    ("featurize_ms.interactive", 0.25),
    ("queue_wait_ms.interactive", 1.5),
    ("scorer_ms.bulk", 3.0),
    ("batch_rows.bulk", 64.0),
    ("serve_mfu.bulk", 100 * 640 * 6_884_048 / (10.0 * 197e12)),
])
def test_reader_reads_its_number(metric, want):
    read = harness.load_reader(ROOT, metric)
    assert read(_run(SPANS, REGISTRY)) == pytest.approx(want)
    assert read(_run()) is None


def test_kernel_and_device_readers_need_a_trace():
    trace = {"program_s": {"jit__lambda": 1e-4, "jit_other": 1.0},
             "busy_s": 2.5, "window_s": 10.0, "devices": 1}
    roof = harness.load_reader(ROOT, "scorer_roofline.bulk")
    idle = harness.load_reader(ROOT, "device_idle.bulk")
    # 64 rows are bound by bytes: gathered embedding rows and weights
    least = max(64 * 6_884_048 / 197e12, flops.call_bytes(_run().model, 64)
                / 819e9)
    assert roof(_run(SPANS, REGISTRY, trace=trace)) == pytest.approx(
        100 * least / 1e-4)
    assert idle(_run(trace=trace)) == pytest.approx(75.0)
    assert roof(_run(SPANS, REGISTRY)) is None and idle(_run()) is None


def test_tail_reader_reads_every_request_from_its_scheduled_arrival():
    read = harness.load_reader(ROOT, "latency_p95_ms.interactive")
    reqs = []
    for i in range(100):
        r = loadgen.Request([i], t_sched=0.01 * i, rankings=[[]])
        r.t_done = r.t_sched + 0.001 * (i + 1)       # 1 ms .. 100 ms
        reqs.append(r)
    reqs[0].rankings, reqs[0].error = None, "shed"  # a failed one counts too
    assert read(_run(requests=reqs)) == pytest.approx(95.05)
    assert read(_run()) is None
