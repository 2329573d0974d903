"""The benchmark's corpus, index and queries: deterministic from the seed,
the same sizes for every seed, and the program's own index and tokens."""
import numpy as np
import pytest

from bench import corpus as C
from repro.core import bm25
from repro.data.tokenizer import STOPWORDS, HashingTokenizer

V = 30000
SPECS = {
    "sentences": dict(n_docs=120, sents_per_doc=8, sent_words=[10, 40],
                      vocab_words=2000, zipf_s=1.0, zipf_q=2.7),
    "passages": dict(n_docs=400, sents_per_doc=1, sent_words=[30, 90],
                     vocab_words=2000, zipf_s=1.0, zipf_q=2.7),
}


@pytest.mark.parametrize("kind", sorted(SPECS))
def test_corpus_is_deterministic_per_seed(kind):
    a = C.generate(SPECS[kind], V, 2**31 + 11)
    b = C.generate(SPECS[kind], V, 2**31 + 11)
    c = C.generate(SPECS[kind], V, 5)
    assert a.words == b.words
    assert np.array_equal(a.tokens, b.tokens)
    assert np.array_equal(a.idf_words, b.idf_words)
    assert not np.array_equal(a.tokens[:len(c.tokens)], c.tokens)
    # every seed gets the same multiset of sentence lengths
    assert np.array_equal(np.sort(np.diff(a.sent_ptr)),
                          np.sort(np.diff(c.sent_ptr)))


@pytest.mark.parametrize("kind", sorted(SPECS))
def test_index_equals_the_programs_build_index(kind):
    corpus = C.generate(SPECS[kind], V, 7)
    tok = HashingTokenizer(V)
    docs = C.Documents(corpus)
    want = bm25.build_index([tok.encode(" ".join(d)) for d in docs], V)
    got = C.build_index(corpus, V)
    for field in ("term_ptr", "post_docs", "post_tf", "idf", "doc_len"):
        g, w = getattr(got, field), getattr(want, field)
        assert g.dtype == w.dtype and np.array_equal(g, w), field
    assert got.avg_dl == want.avg_dl and got.n_docs == want.n_docs


def test_rendered_text_tokenizes_to_the_corpus_ids():
    corpus = C.generate(SPECS["sentences"], V, 3)
    tok = HashingTokenizer(V)
    for s in (0, 17, 500):
        text = corpus.sentence_text(s)
        ids = corpus.sentence_ids(s)
        assert tok.words(text) == [corpus.words[w] for w in ids]
        assert tok.encode(text) == corpus.term_of_word[ids].tolist()
    doc = C.Documents(corpus)[3]
    assert len(doc) == 8 and doc[2] == corpus.sentence_text(3 * 8 + 2)


def test_stopwords_and_hash_are_the_tokenizers():
    assert set(C.STOPWORDS) == set(STOPWORDS)
    tok = HashingTokenizer(V)
    for w in ("the", "kabuto", "zizo"):
        assert C.fnv1a_id(w, V) == tok.encode(w)[0]


def test_idf_counts_sentences():
    corpus = C.generate(SPECS["sentences"], V, 4)
    n_sents = len(corpus.sent_ptr) - 1
    w = int(corpus.tokens[0])
    df = sum(w in set(corpus.sentence_ids(s).tolist())
             for s in range(n_sents))
    want = np.log((n_sents - df + 0.5) / (df + 0.5) + 1.0)
    assert corpus.idf_words[w] == pytest.approx(want)
    assert corpus.idf[corpus.words[w]] == pytest.approx(want)


def test_queries_distinct_sized_alike_and_drawn_from_targets():
    corpus = C.generate(SPECS["passages"], V, 9)
    spec = {"words": [3, 12], "question_share": 0.5}
    n = C.QueryStream.BLOCK       # sizes are alike block by block
    a = C.make_queries(corpus, spec, n, 1)
    b = C.make_queries(corpus, spec, n, 2)
    assert len(set(a.texts)) == n
    assert sorted(map(len, a.word_ids)) == sorted(map(len, b.word_ids))
    for words, target in zip(a.word_ids, a.targets):
        sent = set(corpus.sentence_ids(int(target)).tolist())
        content = [w for w in words if not corpus.is_stop[w]]
        assert set(content) <= sent
    idx = C.build_index(corpus, V)
    prog = bm25.BM25Index(idx.term_ptr, idx.post_docs, idx.post_tf, idx.idf,
                          idx.doc_len, idx.avg_dl, idx.n_docs)
    terms = corpus.term_of_word[a.word_ids[0]]
    budget = 10**7
    docs, tf, _ = bm25.gather_query_postings(prog, terms, budget)
    assert C.postings_count(idx, terms) == budget - int((tf == 0).sum())


def test_query_stream_makes_blocks_as_drawn_and_repeats_none():
    corpus = C.generate(SPECS["passages"], V, 9)
    spec = {"words": [3, 12], "question_share": 0.5}
    n = C.QueryStream.BLOCK
    lazy = C.QueryStream(corpus, spec, 2**31 + 5)
    assert len(lazy) == 0
    assert lazy.text(n + 3) and len(lazy) == 2 * n
    ahead = C.make_queries(corpus, spec, 3 * n, 2**31 + 5)
    # query j is the same whatever number is drawn
    assert lazy.texts == ahead.texts[:2 * n]
    assert all(np.array_equal(lazy.words(j), ahead.word_ids[j])
               for j in range(2 * n))
    assert len(set(ahead.texts)) == 3 * n
