"""Plain reference of the served cascade, and the comparison that decides
``correct``.

Nothing here imports the program. BM25 is scored in float64 over the
index arrays; the reranker's logits come from the model family's own
reference (``bench/families/<family>.py``: ``reference_logits`` and
``served_logit``), which draws its own weights from the seed.

Retrieval keeps the program's documented semantics: a query's postings are
concatenated in query-term order and only the first ``budget`` are scored.
That departs from Anserini's BM25 and is stated in each configuration.

``check_query`` holds each sampled served ranking to the reference, and
``numbers`` gives the two compared numbers:

  misses     served items the reference would not serve: documents outside
             its top-h (and dynamic-cutoff) set up to float32 ties, a
             better candidate passed over by more than a tie, a short
             list, or two items out of the reference's order;
  score_gap  the widest gap between a served score and the reference's,
             as logits (log p / (1 - p)).

The two kinds of miss are counted apart and logged, and compared as one
number. Neither alone has an upper reading: the bf16 control misses
retrieval on some seeds, rank on others, and on some msmarco seeds
neither, where ``score_gap`` fails it. ``misses`` is there for faults the
gap cannot see, such as a shifted retrieval (tests); sound runs read 0.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

K1 = 0.9
B = 0.4
#: Relative tie width for BM25 scores. The program sums at most a few
#: dozen float32 contributions per document in some order; its rounding is
#: under 1e-6 of the score. Documents whose reference scores lie within
#: 1e-4 of the top score of each other are a tie, and either may be kept.
BM25_TIE_REL = 1e-4


def bm25(index, terms: Sequence[int], budget: int) -> np.ndarray:
    """float64 BM25 of every document over the query's first ``budget``
    postings in query-term order (the program's semantics)."""
    docs, tfs, idfs = [], [], []
    for t in terms:
        s, e = int(index.term_ptr[t]), int(index.term_ptr[t + 1])
        docs.append(index.post_docs[s:e])
        tfs.append(index.post_tf[s:e])
        idfs.append(np.full(e - s, index.idf[t], np.float64))
    docs = np.concatenate(docs)[:budget]
    tf = np.concatenate(tfs)[:budget].astype(np.float64)
    idf = np.concatenate(idfs)[:budget]
    norm = K1 * (1.0 - B + B * index.doc_len[docs].astype(np.float64)
                 / index.avg_dl)
    contrib = idf * tf * (K1 + 1.0) / (tf + norm)
    return np.bincount(docs, weights=contrib, minlength=index.n_docs)


@dataclasses.dataclass
class Expected:
    """The reference's retrieval for one query: documents that may be
    returned (ties included) and those that must be ranked."""
    may: np.ndarray        # sorted doc ids
    must: np.ndarray       # sorted doc ids


def expected_docs(scores: np.ndarray, h: int,
                  cutoff: Optional[float]) -> Expected:
    """Top-h by score (positive scores only), then the dynamic cutoff:
    documents more than ``cutoff`` below the best are dropped. Documents
    within ``BM25_TIE_REL`` of a boundary may fall either way."""
    top = float(scores.max())
    eps = BM25_TIE_REL * max(top, 1e-12)
    h = min(h, len(scores))
    t = float(np.partition(scores, len(scores) - h)[len(scores) - h])
    lo_may, lo_must = t - eps, t + eps
    if cutoff is not None:
        lo_may = max(lo_may, top - cutoff - eps)
        lo_must = max(lo_must, top - cutoff + eps)
    pos = scores > 0
    may = np.flatnonzero(pos & (scores >= lo_may))
    must = np.flatnonzero(pos & (scores > lo_must))
    if len(must) == 0 and top > 0:
        must = np.flatnonzero(scores >= top - eps)[:1]
    return Expected(may, must)


@dataclasses.dataclass
class Check:
    retrieval_misses: int = 0
    rank_misses: int = 0
    score_gap: float = 0.0
    queries: int = 0
    items: int = 0


def check_query(check: Check, corpus, index, pipe: Dict, family, W,
                model: Dict, query_words: np.ndarray,
                served: Sequence[Tuple[int, int, float]],
                tie: float) -> None:
    """Fold one served ranking into ``check``; ``family`` gives the
    reranker's reference logits (from its weights ``W``) and turns a
    served score into a logit."""
    terms = corpus.term_of_word[query_words]
    exp = expected_docs(bm25(index, terms, pipe["postings_budget"]),
                        pipe["retrieve_h"], pipe.get("dynamic_cutoff"))
    per = corpus.sents_per_doc
    may = set(exp.may.tolist())
    served = list(served)
    check.queries += 1
    check.items += len(served)
    ok = []
    for d, s, _ in served:
        if d not in may or not 0 <= s < per:
            check.retrieval_misses += 1
        else:
            ok.append((d, s, _))
    cand = [(int(d), s) for d in exp.must for s in range(per)]
    ids = [d * per + s for d, s, _ in ok] + [d * per + s for d, s in cand]
    ref = family.reference_logits(W, corpus, query_words, ids, model)
    ref_served, ref_cand = ref[:len(ok)], ref[len(ok):]
    if ok:
        gap = np.abs(family.served_logit([x[2] for x in ok])
                     - ref_served).max()
        check.score_gap = max(check.score_gap, float(gap))
    k = pipe["rerank_k"]
    if len(served) < min(k, len(cand)):
        check.rank_misses += 1
    # A better must-candidate left out (or any, when the list is short),
    # and served candidates out of the reference's order by more than a tie.
    served_keys = {(d, s) for d, s, _ in ok}
    floor = ref_served.min() if len(ok) else -np.inf
    full = len(served) >= k
    for (d, s), r in zip(cand, ref_cand):
        if (d, s) not in served_keys and (not full or r > floor + tie):
            check.rank_misses += 1
    check.rank_misses += int(np.sum(np.diff(ref_served) > tie))


def numbers(check: Check, limits: Dict[str, float]) -> List[Dict]:
    """Each compared number beside its limit, for printing."""
    values = {"misses": check.retrieval_misses + check.rank_misses,
              "score_gap": check.score_gap}
    return [{"name": name, "value": float(v), "limit": float(limits[name])}
            for name, v in values.items()]


def passes(rows: List[Dict]) -> bool:
    return all(r["value"] <= r["limit"] for r in rows)
