"""Cached (query, answer) featurization shared by serving and batch ranking.

The sequential ``RerankStage`` re-tokenizes the query once PER CANDIDATE and
the serving engine re-featurizes every (question, answer) pair on every
request. Both are pure functions of their string inputs, so this module
memoizes them: query/answer token rows by text, overlap features by pair.
Bounded LRU (``OrderedDict`` recency order) keeps steady-state serving memory
flat under heavy repeated traffic.

What the LRUs miss is computed for the whole call at once. Each new text's
words become dense ids through a ``WordTable`` (a word is hashed once, when
it is first met), and its token row is a numpy gather and scatter over the
call's flattened word ids; the row's LRU entry keeps the ids and their set.
Overlap features take a set lookup of each query word in each answer and
numpy ``reduceat`` sums over the call's pairs.

``FeaturizationCache.featurize_many`` (the replica pool and the engine) and
``featurize_grouped`` (the batched ranker) are the served paths' entry
points: one ``featurize`` span per call, with ``featurize.encode`` (word ids
and token rows) and ``featurize.pairs`` (overlap features) under it.
"""
from __future__ import annotations

import contextlib
import itertools
from collections import OrderedDict
from typing import Dict, Iterator, List, Optional, Sequence, Tuple
import threading

import numpy as np

from repro.data.tokenizer import STOPWORDS, HashingTokenizer
from repro.serving import telemetry

#: Most word types a ``WordTable`` interns: about ten times the
#: vocabularies served. Past it a new word is hashed each time it is met.
MAX_WORDS = 1 << 20

#: The tracer of the entry points that open no spans of their own.
_UNTRACED = telemetry.Tracer(enabled=False)


class LRUCache:
    """Minimal LRU map; hits/misses counters for serving stats. Thread-safe:
    ServingEngine serves concurrent clients through one shared cache."""

    def __init__(self, capacity: int):
        self.capacity = capacity
        self._d: "OrderedDict" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def get(self, key, tally: Optional[List[int]] = None):
        """The cached value or None. ``tally`` ([hits, misses]) also counts
        this lookup for the caller alone: the shared counters mix every
        thread's lookups."""
        return self.get_many([key], tally)[0]

    def get_many(self, keys: Sequence, tally: Optional[List[int]] = None,
                 repeats: int = 0) -> List:
        """``get`` of each of the distinct ``keys``, under one acquisition
        of the lock. ``repeats`` counts further lookups of them by the same
        caller as hits: the first lookup of a missing key is followed by
        its ``put``."""
        with self._lock:
            out = [self._d.get(key) for key in keys]
            for key, value in zip(keys, out):
                if value is not None:
                    self._d.move_to_end(key)
            hits = sum(v is not None for v in out) + repeats
            misses = len(out) + repeats - hits
            self.hits += hits
            self.misses += misses
        if tally is not None:
            tally[0] += hits
            tally[1] += misses
        return out

    def put(self, key, value):
        self.put_many([(key, value)])

    def put_many(self, items: Sequence[Tuple]) -> None:
        with self._lock:
            for key, value in items:
                self._d[key] = value
                self._d.move_to_end(key)
            while len(self._d) > self.capacity:
                self._d.popitem(last=False)

    def __len__(self) -> int:
        with self._lock:
            return len(self._d)


class _Ids(dict):
    """word -> id; -1 for a word not in it."""

    def __missing__(self, word: str) -> int:
        return -1


class WordTable:
    """Word string -> dense word id, and three arrays indexed by that id:
    the tokenizer's id of the word, its idf (0 where ``idf`` lacks it) and
    whether it is a stopword. Filled lazily, under a lock; a reader reads
    ids first and the arrays after, so every id it holds has its entries
    written (the arrays grow by copying into new ones, never in place)."""

    def __init__(self, tokenizer: HashingTokenizer, idf: Dict[str, float]):
        self.tok = tokenizer
        self.idf = idf
        self.max_words = MAX_WORDS
        self._ids = _Ids()
        self._lock = threading.Lock()
        self._arrays = self._alloc(min(4096, self.max_words))

    @staticmethod
    def _alloc(n: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        return (np.zeros(n, np.int32), np.zeros(n, np.float64),
                np.zeros(n, bool))

    def _entry(self, w: str) -> Tuple[int, float, bool]:
        return self.tok._hash(w), self.idf.get(w, 0.0), w in STOPWORDS

    def __len__(self) -> int:
        return len(self._ids)

    def lookup(self, words: Sequence[str], tally: List[int]
               ) -> Tuple[np.ndarray, Tuple[np.ndarray, ...], bool]:
        """(ids of ``words``, the (token id, idf, is-stopword) arrays that
        cover them and every id handed out before, whether all the ids are
        the table's). Past the table's bound a new word gets an id of this
        call alone, past the table's arrays. ``tally`` ([hits, misses])
        counts one miss for each distinct word interned or given such an
        id; every other lookup is a hit."""
        ids = np.fromiter(map(self._ids.__getitem__, words), np.int64,
                          len(words))
        new = np.flatnonzero(ids < 0)
        interned = 0
        if len(new):
            with self._lock:
                for k in new:
                    w = words[k]
                    i = self._ids[w]
                    if i < 0 and len(self._ids) < self.max_words:
                        i = self._intern(w)
                        interned += 1
                    ids[k] = i
                arrays = self._arrays
        else:
            arrays = self._arrays
        extra: Dict[str, int] = {}
        for k in np.flatnonzero(ids < 0):
            ids[k] = len(arrays[0]) + extra.setdefault(words[k], len(extra))
        misses = interned + len(extra)
        tally[0] += len(words) - misses
        tally[1] += misses
        if extra:
            arrays = tuple(np.concatenate([a, np.asarray(col, a.dtype)])
                           for a, col in zip(arrays, zip(*map(self._entry,
                                                              extra))))
        return ids, arrays, not extra

    def _intern(self, w: str) -> int:
        """Give ``w`` the next id; the caller holds the lock."""
        i = len(self._ids)
        arrays = self._arrays
        if i == len(arrays[0]):
            grown = self._alloc(min(2 * i, self.max_words))
            for new, old in zip(grown, arrays):
                new[:i] = old
            self._arrays = arrays = grown
        arrays[0][i], arrays[1][i], arrays[2][i] = self._entry(w)
        self._ids[w] = i
        return i


class FeaturizationCache:
    """Memoized tokenization + overlap features over a fixed tokenizer/idf.

    ``query_row``/``answer_row`` return the padded int32 token row for a text
    (encoded once, reused across every candidate / request);
    ``pair_feats_many`` returns the 4 overlap features of each (query,
    answer) pair; ``featurize``/``featurize_many`` return both.
    """

    def __init__(self, tokenizer: HashingTokenizer, idf: Dict[str, float],
                 max_len: int, capacity: int = 8192):
        self.tok = tokenizer
        self.idf = idf
        self.max_len = max_len
        self.word_table = WordTable(tokenizer, idf)
        self._tok_cache = LRUCache(capacity)
        self._pair_cache = LRUCache(capacity)

    def _row(self, text: str, tally: Optional[List[int]] = None
             ) -> np.ndarray:
        entry = self._tok_cache.get(text, tally)
        if entry is None:
            (entry,), _ = self._encode([text], [0, 0])
        return entry[0]

    query_row = _row
    answer_row = _row

    def _encode(self, texts: Sequence[str], tally: List[int]):
        """(an entry of each text, the word table's arrays that cover its
        word ids), cached in the token-row LRU unless a word id is this
        call's alone. An entry is (token row, the text's word ids, their
        set). ``tally`` counts the word table's lookups."""
        words = [self.tok.words(t) for t in texts]
        lens = np.fromiter(map(len, words), np.int64, len(words))
        ids, arrays, stable = self.word_table.lookup(
            list(itertools.chain.from_iterable(words)), tally)
        ends = np.cumsum(lens)
        rows = _token_rows(ids, lens, arrays[0], self.max_len)
        flat = ids.tolist()
        # copies: a cached view would keep the whole block alive
        entries = [(row.copy(), ids[e - k:e].copy(), frozenset(flat[e - k:e]))
                   for row, e, k in zip(rows, ends.tolist(), lens.tolist())]
        if stable:
            self._tok_cache.put_many(list(zip(texts, entries)))
        return entries, arrays

    def featurize(self, query: str, answer: str
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        q_tok, a_tok, feats = self._featurize([(query, answer)],
                                              [0, 0], [0, 0], [0, 0])
        return q_tok[0], a_tok[0], feats[0]

    @contextlib.contextmanager
    def _span(self, rows: int) -> Iterator[Tuple[object, List[int],
                                                  List[int], List[int]]]:
        """One call's ``featurize`` span, with ``rows`` and ``cpu_ms``.
        Yields the tracer and three [hits, misses] tallies, which the
        caller passes to its own lookups: answer token rows and pair
        features (one lookup of each a pair, in their LRUs), and words (the
        word table's lookups of the words of the texts those missed). On
        exit they become ``row_hits``/``row_misses``,
        ``pair_hits``/``pair_misses``, their sums ``hits``/``misses``, and
        ``word_hits``/``word_misses``. A query's token row is looked up
        once a call and not tallied."""
        tracer = telemetry.get_tracer()
        row_tally, pair_tally, word_tally = [0, 0], [0, 0], [0, 0]
        with tracer.span("featurize", rows=rows, cpu=True) as span:
            yield tracer, row_tally, pair_tally, word_tally
            span.set_attr("row_hits", row_tally[0])
            span.set_attr("row_misses", row_tally[1])
            span.set_attr("pair_hits", pair_tally[0])
            span.set_attr("pair_misses", pair_tally[1])
            span.set_attr("hits", row_tally[0] + pair_tally[0])
            span.set_attr("misses", row_tally[1] + pair_tally[1])
            span.set_attr("word_hits", word_tally[0])
            span.set_attr("word_misses", word_tally[1])

    def featurize_many(self, pairs: Sequence[Tuple[str, str]]
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Stacked (query rows, answer rows, overlap features) of a pair
        list, as ``featurize`` gives them pair by pair.

        One ``featurize`` span a call (see ``_span``) with two children,
        each with ``cpu_ms``: ``featurize.encode`` (cache lookups, word ids
        and token rows) and ``featurize.pairs`` (overlap features). No span
        is opened per pair."""
        with self._span(len(pairs)) as (tracer, *tallies):
            return self._featurize(pairs, *tallies, tracer=tracer)

    def featurize_grouped(self, groups: Sequence[Tuple[str, Sequence[str]]]
                          ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """``featurize_many`` of every (query, answer) of ``groups`` (a
        query and its answers each), in order."""
        return self.featurize_many([(q, a) for q, answers in groups
                                    for a in answers])

    def pair_feats_many(self, pairs: Sequence[Tuple[str, str]],
                        tally: Optional[List[int]] = None) -> np.ndarray:
        """Overlap features of each pair: from the pair LRU, or computed in
        one pass over the call's misses. ``tally`` counts the lookups."""
        return self._featurize(pairs, [0, 0], tally or [0, 0], [0, 0])[2]

    def _featurize(self, pairs: Sequence[Tuple[str, str]],
                   row_tally: List[int], pair_tally: List[int],
                   word_tally: List[int],
                   tracer: telemetry.Tracer = _UNTRACED
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The one pass behind every entry point. Each distinct text and
        pair of the call is looked up once in its LRU; every pair counts as
        one lookup of each (see ``LRUCache.get_many``). A text's LRU entry
        holds its token row and its words, so a pair that missed reads the
        words of cached texts from there."""
        pairs = list(map(tuple, pairs))     # pairs may come as lists
        n, width = len(pairs), self.max_len
        with tracer.span("featurize.encode", cpu=True):
            uniq = list(dict.fromkeys(pairs))
            feats = self._pair_cache.get_many(uniq, pair_tally,
                                              n - len(uniq))
            queries = list(dict.fromkeys(q for q, _ in uniq))
            answers = list(dict.fromkeys(a for _, a in uniq))
            entry = dict(zip(queries, self._tok_cache.get_many(
                queries, None, n - len(queries))))
            entry.update(zip(answers, self._tok_cache.get_many(
                answers, row_tally, n - len(answers))))
            missed = [t for t, e in entry.items() if e is None]
            fresh, arrays = self._encode(missed, word_tally)
            entry.update(zip(missed, fresh))
        with tracer.span("featurize.pairs", cpu=True):
            miss = [i for i, f in enumerate(feats) if f is None]
            if miss:
                slot: Dict[str, int] = {}
                pair_q = [slot.setdefault(uniq[i][0], len(slot)) for i in miss]
                block = _overlap(
                    [tuple(dict.fromkeys(entry[q][1].tolist())) for q in slot],
                    pair_q, [entry[uniq[i][1]][2] for i in miss], arrays)
                rows = [f.copy() for f in block]   # apart, as the rows
                for i, f in zip(miss, rows):
                    feats[i] = f
                self._pair_cache.put_many(
                    [(uniq[i], f) for i, f in zip(miss, rows)])
            at = {p: i for i, p in enumerate(uniq)}
            out = _stack(feats, 4, np.float32)[[at[p] for p in pairs]]
        return (_stack([entry[q][0] for q, _ in pairs], width, np.int32),
                _stack([entry[a][0] for _, a in pairs], width, np.int32),
                out)

    def stats(self) -> Dict[str, float]:
        h = self._tok_cache.hits + self._pair_cache.hits
        m = self._tok_cache.misses + self._pair_cache.misses
        return {"feat_cache_hits": float(h), "feat_cache_misses": float(m),
                "feat_cache_hit_rate": float(h) / max(h + m, 1)}


def _stack(rows: List[np.ndarray], width: int, dtype) -> np.ndarray:
    return np.array(rows) if rows else np.zeros((0, width), dtype)


def _token_rows(ids: np.ndarray, lens: np.ndarray, token_ids: np.ndarray,
                width: int) -> np.ndarray:
    """``HashingTokenizer.encode(text, width)`` of texts whose word ids
    follow one another in ``ids``, ``lens`` of them each, bit for bit, as an
    int32 block: each text's first ``width`` token ids, zero (PAD) padded."""
    take = np.minimum(lens, width)
    text = np.repeat(np.arange(len(lens)), take)
    pos = np.arange(int(take.sum())) - np.repeat(np.cumsum(take) - take, take)
    word = np.repeat(np.cumsum(lens) - lens, take) + pos
    out = np.zeros(len(lens) * width, np.int32)
    # one flat index: a scatter on two index arrays gives up the lock
    out[text * width + pos] = token_ids[ids[word]]
    return out.reshape(len(lens), width)


def _overlap(q_words: List[Tuple[int, ...]], pair_q: List[int],
             a_words: List[frozenset], arrays: Tuple[np.ndarray, ...]
             ) -> np.ndarray:
    """``tokenizer.overlap_features`` of each pair p, the query of distinct
    word ids ``q_words[pair_q[p]]`` (in order of first occurrence) against
    the answer whose set of word ids is ``a_words[p]``, as a float32 block
    of (P, 4).

    A pair's hits are its query's words found in its answer, in the
    query's order. Each sum, over a query's words or a pair's hits, is a
    float64 ``reduceat`` of its own segment, and the ratios are cast last,
    as in the reference: a pair's features depend on its two texts alone.
    The reference sums in set order: the two agree to float32 rounding.
    Set lookups and ``reduceat`` hold the interpreter lock; ``bincount``,
    sorts and searches give it up, and a served call that gives it up
    waits for another thread to hand it back."""
    _, idf, stop = arrays
    hits = [[c for c, w in enumerate(q_words[q]) if w in a]
            for q, a in zip(pair_q, a_words)]
    q_lens = np.fromiter(map(len, q_words), np.int64, len(q_words))
    n_hits = np.fromiter(map(len, hits), np.int64, len(hits))
    word = np.fromiter(itertools.chain.from_iterable(q_words), np.int64,
                       int(q_lens.sum()))
    pair_q = np.asarray(pair_q, np.int64)
    hit_at = np.repeat((np.cumsum(q_lens) - q_lens)[pair_q], n_hits) + \
        np.fromiter(itertools.chain.from_iterable(hits), np.int64,
                    int(n_hits.sum()))
    keep, word_idf = ~stop[word], idf[word]
    out = np.empty((len(hits), 4), np.float32)
    # per word: 1 and its idf, then both again for non-stopwords only
    for j, term in enumerate((np.ones(len(word)), word_idf, keep,
                              np.where(keep, word_idf, 0.0))):
        den = _segment_sums(term, q_lens)
        den[den == 0.0] = 1.0   # as the reference: max(count, 1), idf or 1
        out[:, j] = _segment_sums(term[hit_at], n_hits) / den[pair_q]
    return out


def _segment_sums(values: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """float64 sums of the consecutive segments of ``values`` whose lengths
    are ``lens`` (0 for an empty one), each from its own values alone."""
    sums = np.add.reduceat(np.append(values, 0.0), np.cumsum(lens) - lens)
    return np.where(lens > 0, sums, 0.0)
