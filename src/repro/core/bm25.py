"""Stage-1 candidate generation: BM25 over a packed doc-term index, in JAX.

Index construction is host-side numpy (inverted lists are inherently ragged);
scoring is device-side JAX over the query's concatenated postings:
``score contributions = idf * tf_saturation``, combined per document with
``jax.ops.segment_sum`` and cut to top-h with ``jax.lax.top_k`` — the same
gather/segment substrate the GNN and recsys layers use.

Postings for a query are padded to a fixed budget so the scoring function is
jit-stable across queries (one compiled entry per budget bucket).

``retrieve``/``retrieve_many`` split their time into two spans: ``bm25.gather``
(reading the query terms, the postings gather, padding and stacking) and
``bm25.score`` (the call into the jitted program, the ``doc_len`` and
postings transfer, and the read-back of top-h).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Iterable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

K1 = 0.9
B = 0.4


@dataclasses.dataclass
class BM25Index:
    term_ptr: np.ndarray      # (V+1,) CSR pointer into postings
    post_docs: np.ndarray     # (nnz,) doc ids
    post_tf: np.ndarray       # (nnz,) term frequencies
    idf: np.ndarray           # (V,)
    doc_len: np.ndarray       # (N,)
    avg_dl: float
    n_docs: int

    @property
    def vocab_size(self) -> int:
        return len(self.term_ptr) - 1


def build_index(docs_tokens: Sequence[Sequence[int]], vocab_size: int) -> BM25Index:
    n_docs = len(docs_tokens)
    doc_len = np.asarray([len(d) for d in docs_tokens], np.float32)
    # term -> [(doc, tf)]
    postings: Dict[int, Dict[int, int]] = {}
    for di, toks in enumerate(docs_tokens):
        for t in toks:
            postings.setdefault(int(t), {})
            postings[int(t)][di] = postings[int(t)].get(di, 0) + 1
    term_ptr = np.zeros((vocab_size + 1,), np.int64)
    for t, plist in postings.items():
        term_ptr[t + 1] = len(plist)
    term_ptr = np.cumsum(term_ptr)
    nnz = int(term_ptr[-1])
    post_docs = np.zeros((nnz,), np.int32)
    post_tf = np.zeros((nnz,), np.float32)
    for t, plist in postings.items():
        s = term_ptr[t]
        for i, (di, tf) in enumerate(sorted(plist.items())):
            post_docs[s + i] = di
            post_tf[s + i] = tf
    df = np.diff(term_ptr).astype(np.float32)
    idf = np.log((n_docs - df + 0.5) / (df + 0.5) + 1.0).astype(np.float32)
    return BM25Index(term_ptr, post_docs, post_tf, idf, doc_len,
                     float(doc_len.mean() or 1.0), n_docs)


def gather_query_postings(index: BM25Index, query_terms: Iterable[int],
                          budget: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side ragged gather -> fixed-size (docs, tf, idf_per_posting)."""
    docs, tfs, idfs = [], [], []
    for t in query_terms:
        if t < 0 or t >= index.vocab_size:
            continue
        s, e = int(index.term_ptr[t]), int(index.term_ptr[t + 1])
        docs.append(index.post_docs[s:e])
        tfs.append(index.post_tf[s:e])
        idfs.append(np.full((e - s,), index.idf[t], np.float32))
    if docs:
        docs = np.concatenate(docs)[:budget]
        tfs = np.concatenate(tfs)[:budget]
        idfs = np.concatenate(idfs)[:budget]
    else:
        docs = np.zeros((0,), np.int32)
        tfs = np.zeros((0,), np.float32)
        idfs = np.zeros((0,), np.float32)
    pad = budget - len(docs)
    # padding postings point at doc 0 with idf 0 -> zero contribution
    docs = np.concatenate([docs, np.zeros((pad,), np.int32)])
    tfs = np.concatenate([tfs, np.zeros((pad,), np.float32)])
    idfs = np.concatenate([idfs, np.zeros((pad,), np.float32)])
    return docs.astype(np.int32), tfs, idfs


@functools.partial(jax.jit, static_argnames=("h",))
def _score_postings(post_docs, post_tf, post_idf, doc_len, avg_dl, h):
    norm = K1 * (1.0 - B + B * doc_len[post_docs] / avg_dl)
    contrib = post_idf * post_tf * (K1 + 1.0) / (post_tf + norm)
    scores = jax.ops.segment_sum(contrib, post_docs,
                                 num_segments=doc_len.shape[0])
    return jax.lax.top_k(scores, h)


def retrieve(index: BM25Index, query_terms: Iterable[int], h: int,
             budget: int = 16384) -> Tuple[np.ndarray, np.ndarray]:
    """Top-h (scores, doc_ids) for a query. ``query_terms`` is read inside
    the ``bm25.gather`` span, so lazy terms put their encoding there."""
    from repro.serving import telemetry
    tracer = telemetry.get_tracer()
    with tracer.span("bm25.gather"):
        docs, tfs, idfs = gather_query_postings(index, query_terms, budget)
    with tracer.span("bm25.score"):
        scores, ids = _score_postings(docs, tfs, idfs,
                                      jnp.asarray(index.doc_len),
                                      index.avg_dl, h)
        return np.asarray(scores), np.asarray(ids)


@functools.partial(jax.jit, static_argnames=("h",))
def _score_postings_many(post_docs, post_tf, post_idf, doc_len, avg_dl, h):
    """(Q, P) postings -> per-query top-h. One segment_sum over a flattened
    (query, doc) segment id space instead of Q separate dispatches."""
    q, p = post_docs.shape
    n_docs = doc_len.shape[0]
    norm = K1 * (1.0 - B + B * doc_len[post_docs] / avg_dl)
    contrib = post_idf * post_tf * (K1 + 1.0) / (post_tf + norm)
    seg = (post_docs + jnp.arange(q, dtype=post_docs.dtype)[:, None] * n_docs)
    scores = jax.ops.segment_sum(contrib.reshape(-1), seg.reshape(-1),
                                 num_segments=q * n_docs).reshape(q, n_docs)
    return jax.lax.top_k(scores, h)


def _pad_bucket(n: int, lo: int = 256) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def retrieve_many(index: BM25Index, queries_terms: Sequence[Iterable[int]],
                  h: int, budget: int = 16384
                  ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Batched ``retrieve``: same per-query (scores, doc_ids), one padded
    (Q, P) scoring call. Both dims are bucketed to powers of two so jit
    entries are shared across batch sizes (all-zero padding rows/columns
    contribute nothing and padded-query results are discarded). Each
    query's terms are read inside the ``bm25.gather`` span, so lazy terms
    put their encoding there."""
    if not queries_terms:
        return []
    from repro.serving import telemetry
    tracer = telemetry.get_tracer()
    with tracer.span("bm25.gather"):
        gathered = [gather_query_postings(index, t, budget)
                    for t in queries_terms]
        # gather pads each to `budget`; trim to the batch max, then
        # re-bucket (real postings always have tf > 0, padding is all-zero)
        nnz = [int(np.count_nonzero(g[1])) for g in gathered]
        p = min(budget, _pad_bucket(max(max(nnz), 1)))
        qb = _pad_bucket(len(gathered), lo=8)
        pad_rows = [(np.zeros((p,), np.int32), np.zeros((p,), np.float32),
                     np.zeros((p,), np.float32))] * (qb - len(gathered))
        docs = np.stack([g[0][:p] for g in gathered + pad_rows])
        tfs = np.stack([g[1][:p] for g in gathered + pad_rows])
        idfs = np.stack([g[2][:p] for g in gathered + pad_rows])
    with tracer.span("bm25.score"):
        scores, ids = _score_postings_many(docs, tfs, idfs,
                                           jnp.asarray(index.doc_len),
                                           index.avg_dl, h)
        scores, ids = np.asarray(scores), np.asarray(ids)
    return [(scores[i], ids[i]) for i in range(len(gathered))]
