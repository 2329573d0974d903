"""Synthetic deployment corpus, query set and BM25 index, all from a seed.

Everything is vectorized numpy over token ids; text exists only when a
document is read (``Documents``). The vocabulary is Zipf-Mandelbrot
distributed with the English function words at the top ranks, so posting
lists have the length profile of real text. The BM25 index is built
directly as the program's CSR arrays (``BM25Index`` field for field), in
the tokenizer's hashed id space, and equals what ``bm25.build_index``
builds from the same token lists.

A configuration file's ``corpus`` and ``queries`` groups give the sizes;
the seed changes which words, documents and targets are drawn, never the
sizes: lengths come from fixed quantiles, permuted by the seed.
"""
from __future__ import annotations

import dataclasses
import functools
import operator
import threading
from typing import Dict, List, Sequence

import numpy as np

#: The tokenizer's stopword list, copied: overlap features filter on it,
#: and the most frequent ranks of the vocabulary are these words.
STOPWORDS = (
    "the of and to a in is was for on that with as by at from it his he be "
    "are this which or an were has have had not they its will would been "
    "can when what who how where why than if so no such these those i you "
    "your we them her do does did then could should may might must having "
    "being").split()
QUESTION_WORDS = ("what", "who", "when", "where", "why", "how")
GLUE = ("is", "the", "of", "was", "in", "a", "to", "for", "on", "and")
_SYLLABLES = ("ba be bi bo bu da de di do du ka ke ki ko ku la le li lo lu "
              "ma me mi mo mu na ne ni no nu ra re ri ro ru sa se si so su "
              "ta te ti to tu va ve vi vo vu za ze zi zo zu").split()
_SAMPLE_BITS = 24          # resolution of the inverse-CDF sampling table
N_SPECIAL = 2              # the tokenizer's PAD and UNK ids


def fnv1a_id(word: str, vocab_size: int) -> int:
    """The tokenizer's hashing rule: FNV-1a 64 into [2, vocab_size)."""
    h = 0xcbf29ce484222325
    for ch in word.encode():
        h = ((h ^ ch) * 0x100000001b3) & 0xFFFFFFFFFFFFFFFF
    return N_SPECIAL + h % (vocab_size - N_SPECIAL)


def seeded(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream): large seeds are fine."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def jax_key_bits(seed: int) -> int:
    """The 32-bit value both the program's weights and the reference's
    are drawn from (``PRNGKey`` of it)."""
    return int(np.random.SeedSequence([int(seed), 7]).generate_state(1)[0])


def stratified_ints(lo: int, hi: int, n: int,
                    rng: np.random.Generator) -> np.ndarray:
    """n integers spread evenly over [lo, hi], in an order from ``rng``:
    every seed gets the same multiset of sizes."""
    vals = lo + np.floor((np.arange(n) + 0.5) / n * (hi - lo + 1))
    return rng.permutation(vals.astype(np.int64))


def _make_words(n: int, rng: np.random.Generator) -> List[str]:
    """n distinct lowercase syllable words (2-4 syllables), not stopwords."""
    s = len(_SYLLABLES)
    stop = set(STOPWORDS)
    words: List[str] = []
    seen = set()
    while len(words) < n:
        need = n - len(words)
        n_syl = rng.integers(2, 5, size=2 * need)
        syl = rng.integers(0, s, size=(2 * need, 4))
        for k, row in zip(n_syl, syl):
            w = "".join(_SYLLABLES[j] for j in row[:k])
            if w not in seen and w not in stop:
                seen.add(w)
                words.append(w)
                if len(words) == n:
                    break
    return words


def _zipf_table(n_words: int, s: float, q: float) -> np.ndarray:
    """Inverse-CDF table: a uniform index into it draws a rank with
    probability proportional to 1 / (rank + q) ** s."""
    p = 1.0 / (np.arange(n_words) + q) ** s
    size = 1 << _SAMPLE_BITS
    counts = np.floor(p / p.sum() * size).astype(np.int64)
    counts = np.maximum(counts, 1)
    counts[0] += size - counts.sum()     # rank 0 absorbs the rounding
    return np.repeat(np.arange(n_words, dtype=np.int32), counts)


@dataclasses.dataclass
class Corpus:
    """Token ids of every sentence of every document, plus the vocabulary.

    ``tokens[sent_ptr[s]:sent_ptr[s+1]]`` are sentence ``s``'s word ids;
    document ``d`` holds sentences ``d*sents_per_doc`` onward.
    """
    words: List[str]              # word id -> text
    term_of_word: np.ndarray      # word id -> tokenizer (hashed) id
    is_stop: np.ndarray           # word id -> in STOPWORDS
    tokens: np.ndarray            # (n_tokens,) int32 word ids
    sent_ptr: np.ndarray          # (n_sents + 1,) int64
    sents_per_doc: int
    n_docs: int
    idf_words: np.ndarray         # word id -> idf over sentences (float64)

    def sentence_ids(self, s: int) -> np.ndarray:
        return self.tokens[self.sent_ptr[s]:self.sent_ptr[s + 1]]

    def sentence_text(self, s: int) -> str:
        ids = self.sentence_ids(s).tolist()
        if len(ids) == 1:
            return self.words[ids[0]]
        return " ".join(operator.itemgetter(*ids)(self.words))

    @property
    def idf(self) -> Dict[str, float]:
        """word text -> idf, the featurizer's input."""
        return {w: float(v) for w, v in zip(self.words, self.idf_words)
                if v > 0.0}


def generate(spec: Dict, vocab_size: int, seed: int) -> Corpus:
    """The configuration's corpus group -> a ``Corpus`` drawn from ``seed``.

    spec keys: n_docs, sents_per_doc, sent_words [lo, hi], vocab_words,
    zipf_s, zipf_q."""
    rng = seeded(seed, 1)
    n_docs, per_doc = int(spec["n_docs"]), int(spec["sents_per_doc"])
    n_sents = n_docs * per_doc
    n_words = int(spec["vocab_words"])
    words = list(STOPWORDS) + _make_words(n_words - len(STOPWORDS), rng)
    # Stopwords keep the top ranks in list order; content words are
    # ranked in an order drawn from the seed.
    order = np.concatenate([np.arange(len(STOPWORDS)),
                            len(STOPWORDS) + rng.permutation(
                                n_words - len(STOPWORDS))])
    words = [words[i] for i in order]
    lo, hi = spec["sent_words"]
    lens = stratified_ints(int(lo), int(hi), n_sents, rng)
    sent_ptr = np.zeros(n_sents + 1, np.int64)
    np.cumsum(lens, out=sent_ptr[1:])
    table = _zipf_table(n_words, float(spec["zipf_s"]), float(spec["zipf_q"]))
    tokens = table[rng.integers(0, len(table), size=int(sent_ptr[-1]),
                                dtype=np.int32)]
    term_of_word = np.asarray([fnv1a_id(w, vocab_size) for w in words],
                              np.int32)
    is_stop = np.zeros(n_words, bool)
    is_stop[:len(STOPWORDS)] = True
    # idf over sentences, as the QA corpus defines it: df counts the
    # sentences a word occurs in at least once (rows sorted in place of a
    # global sort: sentences are short).
    rows = np.full((n_sents, int(hi)), n_words, np.int32)
    rows[np.arange(int(hi))[None, :] < lens[:, None]] = tokens
    rows.sort(axis=1)
    first = np.ones(rows.shape, bool)
    first[:, 1:] = rows[:, 1:] != rows[:, :-1]
    df = np.bincount(rows[first], minlength=n_words + 1)[:n_words]
    del rows, first
    df = df.astype(np.float64)
    idf = np.where(df > 0, np.log((n_sents - df + 0.5) / (df + 0.5) + 1.0),
                   0.0)
    return Corpus(words, term_of_word, is_stop, tokens, sent_ptr, per_doc,
                  n_docs, idf)


@dataclasses.dataclass
class Index:
    """The program's ``BM25Index`` fields (hashed term id space)."""
    term_ptr: np.ndarray
    post_docs: np.ndarray
    post_tf: np.ndarray
    idf: np.ndarray
    doc_len: np.ndarray
    avg_dl: float
    n_docs: int


def build_index(corpus: Corpus, vocab_size: int) -> Index:
    """Doc-level postings over hashed ids, docs ascending within a term;
    the same arrays and formulas as the program's ``build_index``."""
    n_docs = corpus.n_docs
    n_tok_doc = np.diff(corpus.sent_ptr[::corpus.sents_per_doc])
    doc_of_tok = np.repeat(np.arange(n_docs, dtype=np.int64), n_tok_doc)
    terms = corpus.term_of_word[corpus.tokens].astype(np.int64)
    key = terms * n_docs + doc_of_tok
    key.sort()
    starts = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
    uniq = key[starts]
    tf = np.diff(np.r_[starts, len(key)]).astype(np.float32)
    del key
    post_terms = uniq // n_docs
    post_docs = (uniq % n_docs).astype(np.int32)
    term_ptr = np.zeros(vocab_size + 1, np.int64)
    np.cumsum(np.bincount(post_terms, minlength=vocab_size), out=term_ptr[1:])
    df = np.diff(term_ptr).astype(np.float32)
    idf = np.log((n_docs - df + 0.5) / (df + 0.5) + 1.0).astype(np.float32)
    doc_len = n_tok_doc.astype(np.float32)
    return Index(term_ptr, post_docs, tf, idf, doc_len,
                 float(doc_len.mean() or 1.0), n_docs)


class Document(Sequence):
    """One document's sentences, rendered to text when read."""

    __slots__ = ("_corpus", "_first", "_n")

    def __init__(self, corpus: Corpus, doc_id: int):
        self._corpus = corpus
        self._first = doc_id * corpus.sents_per_doc
        self._n = corpus.sents_per_doc

    def __len__(self) -> int:
        return self._n

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self[j] for j in range(*i.indices(self._n))]
        if not -self._n <= i < self._n:
            raise IndexError(i)
        return self._corpus.sentence_text(self._first + (i % self._n))


class Documents(Sequence):
    """doc id -> ``Document``: what the program's ``documents`` binding
    reads, without holding the text of a million documents."""

    def __init__(self, corpus: Corpus):
        self._corpus = corpus

    def __len__(self) -> int:
        return self._corpus.n_docs

    def __getitem__(self, d):
        if isinstance(d, slice):
            return [self[j] for j in range(*d.indices(len(self)))]
        if not -len(self) <= d < len(self):
            raise IndexError(d)
        return Document(self._corpus, d % len(self))

    def __iter__(self):
        return map(functools.partial(Document, self._corpus),
                   range(len(self)))


@dataclasses.dataclass
class QuerySet:
    texts: List[str]
    word_ids: List[np.ndarray]
    targets: np.ndarray           # sentence each query was drawn from


class QueryStream:
    """A run's queries, made in blocks of ``BLOCK`` as they are drawn, so
    a closed loop builds only what it sends. Query j is the same whatever
    number is drawn, and no text repeats.

    spec keys: words [lo, hi] (query length), question_share (share that
    open with a question word and glue words, as "what is the ... of ...").
    Each query is drawn from the words of a target sentence: the rest take
    content words of the target, then any of its words. Every block has
    the same multiset of lengths and question share."""

    BLOCK = 256

    def __init__(self, corpus: Corpus, spec: Dict, seed: int):
        self._corpus, self._spec, self._seed = corpus, spec, int(seed)
        self._lock = threading.Lock()
        self._seen: set = set()
        self.texts: List[str] = []
        self.word_ids: List[np.ndarray] = []
        self.targets: List[int] = []

    def __len__(self) -> int:
        return len(self.texts)

    def ensure(self, n: int) -> None:
        """Make blocks until at least ``n`` queries exist."""
        with self._lock:
            while len(self.texts) < n:
                self._block(len(self.texts) // self.BLOCK)

    def text(self, j: int) -> str:
        self.ensure(j + 1)
        return self.texts[j]

    def words(self, j: int) -> np.ndarray:
        self.ensure(j + 1)
        return self.word_ids[j]

    def _block(self, k: int) -> None:
        corpus, n = self._corpus, self.BLOCK
        rng = seeded(self._seed, 2, k)
        lo, hi = self._spec["words"]
        share = float(self._spec["question_share"])
        n_sents = len(corpus.sent_ptr) - 1
        lens = stratified_ints(int(lo), int(hi), n, rng)
        asks = rng.permutation(np.arange(n) < round(share * n))
        made, i = 0, 0
        while made < n:
            length, ask = int(lens[i % n]), bool(asks[i % n])
            i += 1
            s = int(rng.integers(n_sents))
            sent = corpus.sentence_ids(s)
            head: List[int] = []
            if ask:
                head.append(STOPWORDS.index(
                    QUESTION_WORDS[rng.integers(len(QUESTION_WORDS))]))
                for g in rng.choice(len(GLUE), size=min(2, length - 2),
                                    replace=False):
                    head.append(STOPWORDS.index(GLUE[g]))
            need = max(length - len(head), 1)
            content = sent[~corpus.is_stop[sent]]
            pool = np.unique(content) if len(content) else np.unique(sent)
            body = list(rng.permutation(pool)[:need])
            while len(body) < need:
                body.append(int(sent[rng.integers(len(sent))]))
            q = np.asarray(head + body, np.int32)
            text = " ".join(corpus.words[w] for w in q)
            if text in self._seen:
                continue
            self._seen.add(text)
            self.texts.append(text)
            self.word_ids.append(q)
            self.targets.append(s)
            made += 1


def make_queries(corpus: Corpus, spec: Dict, n: int, seed: int) -> QuerySet:
    """The first n queries of the seed's ``QueryStream``."""
    stream = QueryStream(corpus, spec, seed)
    stream.ensure(n)
    return QuerySet(stream.texts[:n], stream.word_ids[:n],
                    np.asarray(stream.targets[:n]))


def postings_count(index: Index, terms: Sequence[int]) -> int:
    """Postings a query's terms hold in total (the program keeps the first
    16,384 of them, in query-term order)."""
    t = np.asarray(terms, np.int64)
    return int((index.term_ptr[t + 1] - index.term_ptr[t]).sum())

