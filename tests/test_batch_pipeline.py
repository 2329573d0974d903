"""Batched cross-query pipeline engine: equivalence with the sequential
ranker on every backend, Scorer chunking past the top bucket, sub-batch
micro-batching (submit_many), and featurization-cache behaviour."""
import itertools
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import backends as BK
from repro.core import bm25 as BM
from repro.core import pipeline as PL
from repro.core.batch_pipeline import BatchedMultiStageRanker, verify_equivalence
from repro.data import featurize as FZ
from repro.data import qa as QA
from repro.data.featurize import FeaturizationCache, LRUCache
from repro.data.tokenizer import HashingTokenizer, overlap_features
from repro.models import sm_cnn
from repro.serving import telemetry
from repro.serving.batcher import MicroBatcher


@pytest.fixture(scope="module")
def world():
    cfg = reduced(get_config("sm-cnn"))
    corpus = QA.generate_corpus(n_docs=40, n_questions=24, seed=3)
    tok = HashingTokenizer(cfg.vocab_size)
    index = BM.build_index([tok.encode(" ".join(d)) for d in corpus.documents],
                           cfg.vocab_size)
    params = sm_cnn.init_sm_cnn(jax.random.PRNGKey(0), cfg)
    return cfg, params, corpus, tok, index


def _stages(scorer, world, cutoff=True):
    cfg, params, corpus, tok, index = world
    stages = [PL.RetrievalStage(index, corpus.documents, tok, h=8)]
    if cutoff:
        stages.append(PL.CutoffStage(margin=2.0))
    stages.append(PL.RerankStage(scorer, tok, corpus.idf, cfg.max_len, k=5))
    return stages


@pytest.mark.parametrize("backend", ["eager", "jit", "aot", "numpy", "pallas"])
def test_batched_matches_sequential(world, backend):
    """The batched engine must produce byte-identical rankings to the
    sequential cascade on every integration backend."""
    cfg, params, corpus, tok, index = world
    scorer = BK.make_scorer(backend, params, cfg, buckets=(8, 64))
    stages = _stages(scorer, world)
    seq = PL.MultiStageRanker(stages)
    bat = BatchedMultiStageRanker(stages)
    queries = corpus.questions[:12]
    verify_equivalence(seq, bat, queries)
    # scores agree too (same rows through the same backend)
    for (sc, _), (bc, _) in zip([seq.run(q) for q in queries],
                                bat.run_batch(queries)):
        np.testing.assert_allclose([c.score for c in bc],
                                   [c.score for c in sc], rtol=1e-5, atol=1e-6)


def test_batched_traces_cover_all_stages(world):
    cfg, params, corpus, tok, index = world
    scorer = BK.make_scorer("jit", params, cfg, buckets=(8, 64))
    stages = _stages(scorer, world)
    results = BatchedMultiStageRanker(stages).run_batch(corpus.questions[:4])
    for cands, trace in results:
        assert [t.name for t in trace] == [s.name for s in stages]
        assert all(t.latency_s >= 0 for t in trace)


def test_batched_handles_empty_and_single(world):
    cfg, params, corpus, tok, index = world
    scorer = BK.make_scorer("jit", params, cfg, buckets=(8, 64))
    stages = _stages(scorer, world, cutoff=False)
    bat = BatchedMultiStageRanker(stages)
    assert bat.run_batch([]) == []
    # single-query run + an out-of-corpus query match the sequential ranker
    verify_equivalence(PL.MultiStageRanker(stages), bat,
                       [corpus.questions[0], "zzzz qqqq xxxx"])
    # a rerank stage with no upstream candidates yields an empty StageResult
    rerank_only = BatchedMultiStageRanker([stages[-1]])
    cands, trace = rerank_only.run(corpus.questions[0])
    assert cands == []
    assert len(trace) == 1 and trace[0].candidates == []


def test_retrieve_many_matches_retrieve(world):
    cfg, params, corpus, tok, index = world
    terms = [tok.encode(q) for q in corpus.questions[:8]]
    batched = BM.retrieve_many(index, terms, h=6)
    for t, (bs, bi) in zip(terms, batched):
        ss, si = BM.retrieve(index, t, h=6)
        np.testing.assert_allclose(bs, ss, rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(bi, si)
    assert BM.retrieve_many(index, [], h=6) == []


def test_scorer_chunks_past_top_bucket(world):
    """Coalesced cross-query batches can exceed the largest bucket; the
    Scorer must chunk instead of negative-padding."""
    cfg, params, corpus, tok, index = world
    scorer = BK.make_scorer("jit", params, cfg, buckets=(8, 16))
    rng = np.random.default_rng(0)
    n = 41  # > 2x top bucket, non-divisible remainder
    q = rng.integers(0, cfg.vocab_size, (n, cfg.max_len)).astype(np.int32)
    a = rng.integers(0, cfg.vocab_size, (n, cfg.max_len)).astype(np.int32)
    f = rng.random((n, 4), np.float32)
    out = scorer(q, a, f)
    assert out.shape == (n,)
    ref = np.concatenate([scorer(q[i:i + 8], a[i:i + 8], f[i:i + 8])
                          for i in range(0, n, 8)])
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


# --- MicroBatcher.submit_many ------------------------------------------------

def test_submit_many_matches_direct(world):
    cfg, params, corpus, tok, index = world
    scorer = BK.make_scorer("jit", params, cfg, buckets=(8, 64))
    rng = np.random.default_rng(1)
    q = rng.integers(0, cfg.vocab_size, (10, cfg.max_len)).astype(np.int32)
    a = rng.integers(0, cfg.vocab_size, (10, cfg.max_len)).astype(np.int32)
    f = rng.random((10, 4), np.float32)
    direct = scorer(q, a, f)
    mb = MicroBatcher(scorer, max_batch=32, max_wait_s=0.005)
    out = mb.submit_many(q, a, f).result(timeout=10)
    empty = mb.submit_many(q[:0], a[:0], f[:0]).result(timeout=10)
    mb.stop()
    np.testing.assert_allclose(out, direct, rtol=1e-5, atol=1e-6)
    assert empty.shape == (0,)


def test_submit_many_concurrent_no_lost_futures(world):
    """Many threads race sub-batches and singles through one batcher: every
    future resolves with the right scores and rows never cross sub-batches."""
    cfg, params, corpus, tok, index = world
    scorer = BK.make_scorer("jit", params, cfg, buckets=(8, 64))
    mb = MicroBatcher(scorer, max_batch=16, max_wait_s=0.005)
    rng = np.random.default_rng(2)
    results, errors = {}, []

    def client(i):
        try:
            n = 1 + (i % 5)
            q = rng.integers(0, cfg.vocab_size, (n, cfg.max_len)).astype(np.int32)
            a = rng.integers(0, cfg.vocab_size, (n, cfg.max_len)).astype(np.int32)
            f = rng.random((n, 4), np.float32)
            got = mb.submit_many(q, a, f).result(timeout=20)
            results[i] = (got, scorer(q, a, f))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    mb.stop()
    assert not errors
    assert len(results) == 16
    for got, want in results.values():
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert max(mb.batch_sizes) > 5  # sub-batches actually coalesced


def test_submit_many_exception_propagates_to_all():
    def broken(q, a, f):
        raise RuntimeError("scorer exploded")

    mb = MicroBatcher(broken, max_batch=8, max_wait_s=0.01)
    row = np.zeros((3,), np.int32)
    futs = [mb.submit_many(np.zeros((2, 3), np.int32),
                           np.zeros((2, 3), np.int32),
                           np.zeros((2, 4), np.float32)),
            mb.submit(row, row, np.zeros((4,), np.float32))]
    for fut in futs:
        with pytest.raises(RuntimeError, match="scorer exploded"):
            fut.result(timeout=10)
    mb.stop()


# --- featurization cache -----------------------------------------------------

def test_lru_cache_evicts_and_counts():
    c = LRUCache(capacity=2)
    c.put("a", 1)
    c.put("b", 2)
    assert c.get("a") == 1      # refreshes 'a'
    c.put("c", 3)               # evicts 'b' (least recent)
    assert c.get("b") is None
    assert c.get("a") == 1 and c.get("c") == 3
    assert len(c) == 2
    assert c.hits == 3 and c.misses == 1


def test_featurization_cache_matches_uncached(world):
    cfg, params, corpus, tok, index = world
    cache = FeaturizationCache(tok, corpus.idf, cfg.max_len, capacity=64)
    q = corpus.questions[0]
    for a in corpus.documents[0][:4]:
        q_row, a_row, feats = cache.featurize(q, a)
        np.testing.assert_array_equal(
            q_row, np.asarray(tok.encode(q, cfg.max_len), np.int32))
        np.testing.assert_array_equal(
            a_row, np.asarray(tok.encode(a, cfg.max_len), np.int32))
        np.testing.assert_allclose(
            feats, overlap_features(tok.words(q), tok.words(a), corpus.idf),
            rtol=0, atol=0)
    before = cache.stats()["feat_cache_hits"]
    cache.featurize(q, corpus.documents[0][0])  # fully repeated pair
    assert cache.stats()["feat_cache_hits"] > before


def test_pair_feats_many_matches_scalar_formula(world):
    """The vectorized matrix path must reproduce tokenizer.overlap_features
    (the canonical formula) to float32 rounding, cold and cached."""
    cfg, params, corpus, tok, index = world
    cache = FeaturizationCache(tok, corpus.idf, cfg.max_len, capacity=4096)
    pairs = [(q, s) for q in corpus.questions[:5]
             for d in corpus.documents[:8] for s in d]
    ref = np.stack([overlap_features(tok.words(q), tok.words(a), corpus.idf)
                    for q, a in pairs])
    np.testing.assert_allclose(cache.pair_feats_many(pairs), ref,
                               rtol=0, atol=1e-6)   # cold: matmul path
    np.testing.assert_allclose(cache.pair_feats_many(pairs), ref,
                               rtol=0, atol=1e-6)   # warm: LRU path


#: Words with an idf of their own, beside the corpus's; "zebra", "quokka"
#: and "numbat" have none (idf 0), "what", "is", "the", "of" are stopwords.
_IDF = {"cat": 2.5, "dog": 1.25, "bird": 0.7, "mat": 3.0, "the": 0.1,
        "what": 0.3, "is": 0.2, "don't": 1.7, "stop": 0.4, "cat's": 2.2,
        "w3": 1.1, "w17": 0.9, "w39": 0.6}
_LONG = " ".join(f"w{i}" for i in range(40))

#: Each case is a list of calls on one cache, each call a list of pairs.
_CASES = {
    "empty_text": [[("", "cat dog"), ("what is the cat", ""), ("", "")]],
    "stopword_only_query": [[("what is the", "the cat is on the mat"),
                             ("of the", "dog bird")]],
    "words_absent_from_idf": [[("zebra quokka cat", "quokka zebra numbat"),
                               ("numbat", "numbat numbat")]],
    "repeated_words": [[("cat cat dog cat the the", "dog dog cat bird dog"),
                        ("dog dog", "dog")]],
    "answer_past_max_len": [[("w3 w17 w39", _LONG),
                             ("w39 zebra", _LONG + " " + _LONG)]],
    "case_and_apostrophes": [[("Don't STOP the Cat's", "don't stop THE "
                               "cat's DON'T, Cat!"),
                              ("CAT'S dog", "cats' cat's o'clock")]],
    "two_queries": "corpus:2",
    "three_queries": "corpus:3",
    "hits_mixed_with_misses": "corpus:again",
}
_ENTRY_POINTS = ("featurize_many", "featurize_grouped", "pair_feats_many",
                 "featurize")


def _calls(case, corpus):
    calls = _CASES[case]
    if not isinstance(calls, str):
        return calls
    sents = [s for d in corpus.documents for s in d]
    qs = corpus.questions
    if calls == "corpus:again":   # a second call repeats half the first
        first = [(qs[0], a) for a in sents[:8]] + [(qs[1], sents[0])]
        return [first, first[4:] + [(qs[1], a) for a in sents[5:12]]
                + [(qs[0], "cat dog zebra")]]
    n = int(calls[-1])
    call = [(qs[k], a) for k in range(n) for a in sents[5 * k:5 * k + 9]]
    return [call + call[:3] + [(qs[n - 1], sents[0])]]  # repeats too


def _by_entry_point(cache, entry, call):
    if entry == "featurize_many":
        return cache.featurize_many(call)
    if entry == "featurize_grouped":
        groups = [(q, [a for _, a in g])
                  for q, g in itertools.groupby(call, key=lambda p: p[0])]
        return cache.featurize_grouped(groups)
    if entry == "pair_feats_many":
        return None, None, cache.pair_feats_many(call)
    rows = [cache.featurize(q, a) for q, a in call]
    return tuple(np.stack([r[k] for r in rows]) for k in range(3))


@pytest.mark.parametrize("entry", _ENTRY_POINTS)
@pytest.mark.parametrize("case", list(_CASES))
def test_featurization_entry_points_match_the_reference(world, case, entry):
    """Every entry point gives ``HashingTokenizer.encode``'s token rows bit
    for bit and ``overlap_features`` to float32 rounding (the sums run in
    another order), call after call on one cache."""
    cfg, params, corpus, tok, index = world
    idf = dict(corpus.idf, **_IDF)
    max_len = 16
    cache = FeaturizationCache(tok, idf, max_len, capacity=4096)
    for call in _calls(case, corpus):
        q_tok, a_tok, feats = _by_entry_point(cache, entry, call)
        ref = np.stack([overlap_features(tok.words(q), tok.words(a), idf)
                        for q, a in call])
        assert feats.dtype == np.float32
        np.testing.assert_allclose(feats, ref, rtol=1e-6, atol=1e-7)
        if q_tok is None:
            continue
        for rows, k in ((q_tok, 0), (a_tok, 1)):
            want = np.asarray([tok.encode(p[k], max_len) for p in call],
                              np.int32)
            assert rows.dtype == np.int32
            np.testing.assert_array_equal(rows, want)


def _unseen_words_calls(n_calls, seed):
    """Calls of 12 pairs whose texts are mostly words no call met before,
    each call sharing half its pairs with the next."""
    rng = np.random.default_rng(seed)

    def text(n):
        return " ".join(f"u{int(rng.integers(0, 5000))}x" for _ in range(n))
    pairs = [(text(4), text(int(rng.integers(1, 30))))
             for _ in range(6 * (n_calls + 1))]
    return [pairs[6 * c:6 * c + 12] for c in range(n_calls)]


def test_word_table_under_four_threads(world):
    """Four threads featurize overlapping calls full of unseen words on
    one cache: each result equals the one-thread result, and every word
    was interned once, with its own entries."""
    cfg, params, corpus, tok, index = world
    calls = _unseen_words_calls(48, seed=5)
    idf = {f"u{i}x": 0.01 * i for i in range(0, 5000, 3)}
    alone = FeaturizationCache(tok, idf, cfg.max_len)
    want = [alone.featurize_many(c) for c in calls]
    shared = FeaturizationCache(tok, idf, cfg.max_len, capacity=64)
    got = [None] * len(calls)

    def work(t):
        for i in range(t, len(calls), 4):
            got[i] = shared.featurize_many(calls[i])

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads)
    for g, w in zip(got, want):
        for a, b in zip(g, w):
            np.testing.assert_array_equal(a, b)
    table = shared.word_table
    ids = dict(table._ids)
    assert sorted(ids.values()) == list(range(len(ids)))
    assert set(ids) == {w for c in calls for p in c for t in p
                        for w in tok.words(t)}
    token_ids, word_idf, stop = table._arrays
    for w, i in ids.items():
        assert token_ids[i] == tok.encode(w)[0]
        assert word_idf[i] == idf.get(w, 0.0) and not stop[i]


@pytest.mark.parametrize("bound", [0, 1, 7, 60])
def test_word_table_past_its_bound(world, monkeypatch, bound):
    """Past ``MAX_WORDS`` words are hashed for the call alone: the table
    stops growing, results are unchanged, and each such word counts as a
    miss in every call that meets it."""
    cfg, params, corpus, tok, index = world
    calls = _unseen_words_calls(6, seed=9) + [_CASES["repeated_words"][0]]
    idf = dict(_IDF, **{f"u{i}x": 0.5 for i in range(0, 5000, 2)})
    want = [FeaturizationCache(tok, idf, cfg.max_len).featurize_many(c)
            for c in calls]
    monkeypatch.setattr(FZ, "MAX_WORDS", bound)
    cache = FeaturizationCache(tok, idf, cfg.max_len)
    for call, w in zip(calls, want):
        for a, b in zip(cache.featurize_many(call), w):
            np.testing.assert_array_equal(a, b)
    words = {w for c in calls for p in c for t in p for w in tok.words(t)}
    assert len(cache.word_table) == min(bound, len(words))
    tally = [0, 0]
    cache.word_table.lookup(["u1x", "u1x", "cat"], tally)
    assert sum(tally) == 3
    assert len(cache.word_table) == min(bound, len(words))


def test_featurize_span_counts_word_table_lookups(world):
    """``word_hits``/``word_misses`` count the word table's lookups of the
    words of the texts the token-row LRU missed (a miss once per word the
    call interned; a cached text's words come with its row);
    ``hits``/``misses`` still count one answer-row and one pair-feature
    lookup a pair."""
    cfg, params, corpus, tok, index = world
    cache = FeaturizationCache(tok, _IDF, cfg.max_len)
    tracer = telemetry.get_tracer()
    tracer.clear()
    first = [("a b c", "b c d d"), ("a b c", "e f")]
    cache.featurize_many(first)          # 9 words, 6 of them new
    cache.featurize_many(first)          # every row and pair cached
    cache.featurize_many([("a b c", "a g"), ("a b c", "e f")])  # "a g" new
    spans = [s.attrs for s in tracer.finished() if s.name == "featurize"]
    assert [(a["word_hits"], a["word_misses"]) for a in spans] == [
        (3, 6), (0, 0), (1, 1)]
    assert [(a["hits"], a["misses"]) for a in spans] == [
        (0, 4), (4, 0), (2, 2)]
    assert [(a["row_hits"], a["pair_hits"]) for a in spans] == [
        (0, 0), (2, 2), (1, 1)]


def test_engine_uses_cache_and_submit_many(world):
    from repro.serving.engine import ServingEngine
    cfg, params, corpus, tok, index = world
    scorer = BK.make_scorer("jit", params, cfg, buckets=(8, 64))
    eng = ServingEngine(scorer, tok, corpus.idf, cfg.max_len,
                        max_batch=8, max_wait_s=0.002)
    pairs = [(corpus.questions[0], corpus.documents[0][i % 3])
             for i in range(9)]
    out1 = eng.get_scores(pairs)
    out2 = eng.get_scores(pairs)
    eng.stop()
    np.testing.assert_allclose(out1, out2, rtol=0, atol=0)
    s = eng.stats()
    assert s["feat_cache_hit_rate"] > 0.5  # repeats hit the LRU
    assert s["mean_batch"] > 1  # rows went through as sub-batches


def test_coalesced_rerank_opens_the_pool_featurize_spans(world):
    """The batched ranker's rerank stage opens the same ``featurize`` span
    as the replica pool: ``featurize.encode`` then ``featurize.pairs``
    under it, each with ``cpu_ms``, and this call's own lookups of answer
    rows and pair features (two a candidate), counted apart per cache."""
    cfg, params, corpus, tok, index = world
    scorer = BK.make_scorer("numpy", params, cfg, buckets=(8, 64))
    stages = _stages(scorer, world, cutoff=False)
    tracer = telemetry.get_tracer()
    tracer.clear()
    results = BatchedMultiStageRanker(stages).run_batch(corpus.questions[:4])
    spans = tracer.finished()
    (rerank,) = [s for s in spans if s.name.startswith("stage.rerank")]
    (feat,) = [s for s in spans if s.name == "featurize"]
    kids = sorted((s for s in spans if s.parent_id == feat.span_id),
                  key=lambda s: s.ts_us)
    assert feat.parent_id == rerank.span_id
    assert [k.name for k in kids] == ["featurize.encode", "featurize.pairs"]
    assert min(s.attrs["cpu_ms"] for s in [feat] + kids) > 0
    rows = sum(len(t[1][0].candidates) for t in results)
    assert feat.attrs["rows"] == rows
    assert feat.attrs["hits"] + feat.attrs["misses"] == 2 * rows
    assert (feat.attrs["row_hits"] + feat.attrs["row_misses"]
            == feat.attrs["pair_hits"] + feat.attrs["pair_misses"] == rows)


# ------------------------------------------------------- retrieval spans --

@pytest.mark.parametrize("batched", [False, True])
def test_retrieval_stage_splits_into_gather_score_segment(world, batched):
    """Under each ``stage.bm25-h*`` span, ``bm25.gather``, ``bm25.score``
    and ``bm25.segment`` follow one another without overlap and cover at
    least 90% of it; the stage span carries its thread CPU time."""
    cfg, params, corpus, tok, index = world
    stage = PL.RetrievalStage(index, corpus.documents, tok, h=8)
    queries = list(corpus.questions[:6])
    if batched:
        ranker = BatchedMultiStageRanker([stage])
        run = lambda: ranker.run_batch(queries)  # noqa: E731
    else:
        ranker = PL.MultiStageRanker([stage])
        run = lambda: [ranker.run(q) for q in queries]  # noqa: E731
    run()                                   # compile outside the spans
    tracer = telemetry.get_tracer()
    tracer.clear()
    run()
    spans = tracer.finished()
    stages = [s for s in spans if s.name == "stage.bm25-h8"]
    assert len(stages) == (1 if batched else len(queries))
    covered = total = 0.0
    for st in stages:
        kids = sorted((s for s in spans if s.parent_id == st.span_id),
                      key=lambda s: s.ts_us)
        assert [k.name for k in kids] == ["bm25.gather", "bm25.score",
                                          "bm25.segment"]
        for a, b in zip(kids, kids[1:]):
            assert a.ts_us + a.dur_us <= b.ts_us
        assert st.ts_us <= kids[0].ts_us
        assert kids[-1].ts_us + kids[-1].dur_us <= st.ts_us + st.dur_us
        covered += sum(k.dur_us for k in kids)
        total += st.dur_us
        assert 0 < st.attrs["cpu_ms"]
    assert covered >= 0.9 * total


def test_retrieve_reads_lazy_terms_inside_gather(world):
    """Terms handed over lazily are encoded inside ``bm25.gather``; the
    results equal those of eager terms."""
    cfg, params, corpus, tok, index = world
    q = corpus.questions[0]
    seen = []

    def lazy():
        seen.append(telemetry.get_tracer().current_context())
        yield from tok.encode(q)

    tracer = telemetry.get_tracer()
    tracer.clear()
    eager = BM.retrieve_many(index, [tok.encode(q)], h=5)
    got = BM.retrieve_many(index, [lazy()], h=5)
    np.testing.assert_array_equal(got[0][1], eager[0][1])
    np.testing.assert_array_equal(got[0][0], eager[0][0])
    gathers = [s for s in tracer.finished() if s.name == "bm25.gather"]
    assert seen and seen[0].span_id == gathers[-1].span_id
