"""The readers of the spans inside retrieval and featurization, on
hand-made spans: each reads its number, and reads nothing where the
program opens no such span (as a program from before these spans)."""
import types

import pytest

from bench import harness
from benchtree import ROOT


def _span(name, sid, parent, ts, dur, **attrs):
    return types.SimpleNamespace(name=name, span_id=sid, parent_id=parent,
                                 ts_us=ts, dur_us=dur, tid=1, attrs=attrs)


def _run(spans=()):
    cell = types.SimpleNamespace(chips=1, config={})
    return harness.RunData(cell, [], 10.0, list(spans), {}, None,
                           "TPU v5 lite", {})


#: Two queries through one retrieval stage, and one pool call whose
#: featurization splits 0.6 + 0.3 ms (0.1 + 0.14 ms of thread CPU time).
SPANS = [
    _span("stage.bm25-h10", 1, 0, 0, 4000, queries=2, cpu_ms=3.0),
    _span("bm25.gather", 2, 1, 0, 1000),
    _span("bm25.score", 3, 1, 1000, 2400),
    _span("bm25.segment", 4, 1, 3400, 500),
    _span("pool.get_scores", 5, 0, 4000, 2000, rows=30),
    _span("featurize", 6, 5, 4000, 1000, rows=30, hits="45", misses="15",
          cpu_ms="0.25"),
    _span("featurize.encode", 7, 6, 4000, 600, cpu_ms=0.1),
    _span("featurize.pairs", 8, 6, 4600, 300, cpu_ms="0.14"),
]


@pytest.mark.parametrize("metric,want", [
    ("retrieve_gather_ms.interactive", 0.5),
    ("retrieve_score_ms.interactive", 1.2),
    ("retrieve_segment_ms.interactive", 0.25),
    ("retrieve_cpu_share.interactive", 75.0),
    ("featurize_encode_ms.bulk", 0.3),
    ("featurize_pairs_ms.bulk", 0.15),
    ("featurize_cpu_share.bulk", 25.0),
    ("featurize_hit_share.bulk", 75.0),
    ("featurize_span_ms.interactive", 0.5),
    ("featurize_span_ms.bulk", 0.5),
    ("featurize_encode_cpu_ms.bulk", 0.05),
    ("featurize_pairs_cpu_ms.bulk", 0.07),
])
def test_reader_reads_its_number(metric, want):
    read = harness.load_reader(ROOT, metric)
    assert read(_run(SPANS)) == pytest.approx(want)
    assert read(_run()) is None
    # a program from before the split: stage and pool spans, none inside
    before = [_span("stage.bm25-h10", 1, 0, 0, 4000, queries=2),
              _span("pool.get_scores", 5, 0, 4000, 2000, rows=30)]
    assert read(_run(before)) is None


def test_the_split_adds_up_to_the_stage():
    """Gather, score and segment cover the stage span here, so the three
    readers sum to what ``retrieve_ms`` reads."""
    run = _run(SPANS)
    parts = sum(harness.load_reader(ROOT, f"retrieve_{p}_ms.interactive")(run)
                for p in ("gather", "score", "segment"))
    whole = harness.load_reader(ROOT, "retrieve_ms.interactive")(run)
    assert parts == pytest.approx(0.975 * whole)
