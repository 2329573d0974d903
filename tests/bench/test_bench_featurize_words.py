"""The reader of the word table's lookups, ``featurize_word_hit_share.bulk``:
on hand-made spans beside the other featurization readers' fixture, and on
the spans the program's own featurization opens."""
import types

import pytest

from bench import harness
from benchtree import ROOT

READER = "featurize_word_hit_share.bulk"


def _span(name, sid, attrs):
    return types.SimpleNamespace(name=name, span_id=sid, parent_id=0,
                                 ts_us=0, dur_us=1000, tid=1, attrs=attrs)


def _run(spans=()):
    cell = types.SimpleNamespace(chips=1, config={})
    return harness.RunData(cell, [], 10.0, list(spans), {}, None,
                           "TPU v5 lite", {})


#: The ``featurize`` span of the other readers' fixture, with word counts.
FEATURIZE = {"rows": 30, "hits": "45", "misses": "15", "cpu_ms": "0.25"}


@pytest.mark.parametrize("words,want", [
    ([{"word_hits": 990, "word_misses": 10}], 99.0),
    ([{"word_hits": "3", "word_misses": "1"}], 75.0),
    ([{"word_hits": 0, "word_misses": 4}], 0.0),
    ([{"word_hits": 6, "word_misses": 2}, {"word_hits": 0, "word_misses": 0},
      {"word_hits": 2, "word_misses": 0}], 80.0),
    # a program from before the word table: no word counts on the span
    ([{}], None),
    ([{"word_hits": 0, "word_misses": 0}], None),
])
def test_reader_reads_the_word_counts(words, want):
    read = harness.load_reader(ROOT, READER)
    spans = [_span("featurize", i + 6, dict(FEATURIZE, **w))
             for i, w in enumerate(words)]
    spans.append(_span("featurize.encode", 99,
                       {"word_hits": 1, "word_misses": 1}))
    got = read(_run(spans))
    assert got == (None if want is None else pytest.approx(want))
    assert read(_run()) is None


def test_reader_reads_the_programs_spans():
    """Two calls on a fresh cache: 4 words, 3 new; then the 4 words of
    the one new text, none new: 5 hits in 8 lookups."""
    from repro.data.featurize import FeaturizationCache
    from repro.data.tokenizer import HashingTokenizer
    from repro.serving import telemetry
    tracer = telemetry.get_tracer()
    tracer.clear()
    cache = FeaturizationCache(HashingTokenizer(500), {"b": 1.0}, 8)
    cache.featurize_many([("a b", "b c")])
    cache.featurize_many([("a b", "c c a b"), ("a b", "b c")])
    spans = [s for s in tracer.finished() if s.name.startswith("featurize")]
    read = harness.load_reader(ROOT, READER)
    assert read(_run(spans)) == pytest.approx(62.5)
