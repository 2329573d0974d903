"""Plain reference of the served cascade, and the comparison that decides
``correct``.

Nothing here imports the program. The reference draws its own sm-cnn
weights from the seed (the same draws as the model's initializer), builds
its own features from the corpus's word ids, scores BM25 in float64 over
the index arrays, and runs sm-cnn in float32 numpy (exact float32 products
and sums, what ``highest`` matmul precision means on the chip).

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
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

K1 = 0.9
B = 0.4
#: Relative tie width for BM25 scores. The program sums at most a few
#: dozen float32 contributions per document in some order; its rounding is
#: under 1e-6 of the score. Documents whose reference scores lie within
#: 1e-4 of the top score of each other are a tie, and either may be kept.
BM25_TIE_REL = 1e-4


@dataclasses.dataclass
class Weights:
    embed: np.ndarray
    conv_q_w: np.ndarray
    conv_q_b: np.ndarray
    conv_a_w: np.ndarray
    conv_a_b: np.ndarray
    join_w: np.ndarray
    join_b: np.ndarray
    out_w: np.ndarray
    out_b: np.ndarray
    width: int


def init_weights(key_bits: int, m: Dict) -> Weights:
    """sm-cnn's initializer, restated: split the key five ways; normal
    embeddings times 0.02; dense layers normal over sqrt(fan-in); zero
    biases. Drawn on the CPU backend where there is one."""
    import jax
    import jax.numpy as jnp
    try:
        device = jax.devices("cpu")[0]
    except RuntimeError:
        device = None
    with jax.default_device(device):
        ke, kq, ka, kj, ko = jax.random.split(jax.random.PRNGKey(key_bits), 5)
        w, d, f = m["filter_width"], m["embed_dim"], m["conv_filters"]
        j_in = 2 * f + m["n_extra_feats"]

        def dense(k, d_in, d_out):
            return np.asarray(jax.random.normal(k, (d_in, d_out), jnp.float32)
                              * (1.0 / math.sqrt(d_in)))

        embed = np.asarray(jax.random.normal(ke, (m["vocab_size"], d),
                                             jnp.float32) * 0.02)
        return Weights(
            embed=embed,
            conv_q_w=dense(kq, w * d, f), conv_q_b=np.zeros(f, np.float32),
            conv_a_w=dense(ka, w * d, f), conv_a_b=np.zeros(f, np.float32),
            join_w=dense(kj, j_in, m["n_hidden"]),
            join_b=np.zeros(m["n_hidden"], np.float32),
            out_w=dense(ko, m["n_hidden"], 2), out_b=np.zeros(2, np.float32),
            width=w)


def _conv_arm(x: np.ndarray, w: np.ndarray, b: np.ndarray,
              width: int) -> np.ndarray:
    """Wide conv (pad width-1 both sides), tanh, max over windows."""
    n, s, d = x.shape
    xp = np.zeros((n, s + 2 * (width - 1), d), x.dtype)
    xp[:, width - 1:width - 1 + s] = x
    n_win = s + width - 1
    cols = np.concatenate([xp[:, i:i + n_win] for i in range(width)], axis=-1)
    return np.tanh(cols @ w + b).max(axis=1)


def logits(W: Weights, q_tok: np.ndarray, a_tok: np.ndarray,
           feats: np.ndarray, block: int = 256) -> np.ndarray:
    """sm-cnn's relevance logit (l1 - l0) per pair, float32 throughout.
    The pad id (0) gathers embedding row 0 like any other id, as every
    backend of the model computes it."""
    out = []
    for i in range(0, len(q_tok), block):
        q, a = q_tok[i:i + block], a_tok[i:i + block]
        xq = _conv_arm(W.embed[q], W.conv_q_w, W.conv_q_b, W.width)
        xa = _conv_arm(W.embed[a], W.conv_a_w, W.conv_a_b, W.width)
        xj = np.concatenate([xq, xa, feats[i:i + block].astype(np.float32)],
                            axis=-1)
        h = np.tanh(xj @ W.join_w + W.join_b)
        lg = h @ W.out_w + W.out_b
        out.append(lg[:, 1].astype(np.float64) - lg[:, 0])
    return np.concatenate(out) if out else np.zeros(0)


def logit_of_score(p: np.ndarray) -> np.ndarray:
    """A served score P(relevant) as a logit; exact enough for P in
    float32 away from 0 and 1 (the random-weight model sits near 0.5)."""
    p = np.clip(np.asarray(p, np.float64), 1e-12, 1 - 1e-12)
    return np.log(p) - np.log1p(-p)


def features(corpus, query_words: np.ndarray, sent_ids: Sequence[int],
             max_len: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Token rows and the four overlap features (word overlap and idf-
    weighted overlap, all words and non-stopwords) for one query against
    each sentence."""
    n = len(sent_ids)
    q_row = np.zeros(max_len, np.int32)
    qt = corpus.term_of_word[query_words][:max_len]
    q_row[:len(qt)] = qt
    a_tok = np.zeros((n, max_len), np.int32)
    feats = np.zeros((n, 4), np.float32)
    idf = corpus.idf_words
    q_sets = []
    for filt in (False, True):
        qs = {int(w) for w in query_words if not (filt and corpus.is_stop[w])}
        q_sets.append((qs, sum(idf[w] for w in qs) or 1.0))
    for i, s in enumerate(sent_ids):
        words = corpus.sentence_ids(int(s))
        at = corpus.term_of_word[words][:max_len]
        a_tok[i, :len(at)] = at
        for j, filt in enumerate((False, True)):
            qs, denom = q_sets[j]
            as_ = {int(w) for w in words if not (filt and corpus.is_stop[w])}
            inter = qs & as_
            feats[i, 2 * j] = len(inter) / max(len(qs), 1)
            feats[i, 2 * j + 1] = sum(idf[w] for w in inter) / denom
    return np.broadcast_to(q_row, (n, max_len)), a_tok, feats


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


def check_query(check: Check, corpus, index, W: Weights, pipe: Dict,
                max_len: int, query_words: np.ndarray,
                served: Sequence[Tuple[int, int, float]],
                tie: float) -> None:
    """Fold one served ranking into ``check``."""
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
    q_tok, a_tok, feats = features(corpus, query_words, ids, max_len)
    ref = logits(W, q_tok, a_tok, feats)
    ref_served, ref_cand = ref[:len(ok)], ref[len(ok):]
    if ok:
        gap = np.abs(logit_of_score([x[2] for x in ok]) - ref_served).max()
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
