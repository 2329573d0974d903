"""The control of the correctness check: the reference cascade put in the
program's place and computed in bfloat16, the step below the float32 the
configuration states. Its rankings go through the same comparison as the
program's, which must call them not correct. BM25 in bf16 is here; the
reranker's bf16 model is its family's (``bench/families/<family>.py``).

  python3 bench/control.py --workload msmarco-bulk --seeds 5,6,7

Builds the cell's corpus, index and queries from each seed as a run does,
serves the check's sample of queries with the bf16 cascade (BM25 summed in
bf16, the reranker with bf16 weights and activations, on the default
device), and prints each compared number beside the cell's limit. It
needs no measured window: it checks as many queries as a run does, drawn
as a run draws them.
"""
import argparse
import functools
import json
import sys
from pathlib import Path
from typing import Dict, List, Sequence

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import reference as R  # noqa: E402


def bf16_bm25_top(index, terms: Sequence[int], budget: int, h: int):
    """Top-h (scores, docs) with every contribution and sum in bf16."""
    docs, tfs, idfs = [], [], []
    for t in terms:
        s, e = int(index.term_ptr[t]), int(index.term_ptr[t + 1])
        docs.append(index.post_docs[s:e])
        tfs.append(index.post_tf[s:e])
        idfs.append(np.full(e - s, index.idf[t], np.float32))

    def padded(parts, dtype):     # the first ``budget``, zero-padded to it
        x = np.concatenate(parts)[:budget].astype(dtype)
        return np.pad(x, (0, budget - len(x)))

    top, ids = _bm25_bf16(padded(docs, np.int32), padded(tfs, np.float32),
                          padded(idfs, np.float32), index.doc_len,
                          float(index.avg_dl), min(h, index.n_docs))
    return np.asarray(top, np.float32), np.asarray(ids)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _bm25_bf16(docs, tf, idf, doc_len, avg_dl, h):
    bf = jnp.bfloat16
    tf, idf = tf.astype(bf), idf.astype(bf)
    dl = doc_len[docs].astype(bf)
    norm = bf(R.K1) * (bf(1.0 - R.B) + bf(R.B) * dl / bf(avg_dl))
    contrib = idf * tf * bf(R.K1 + 1.0) / (tf + norm)
    scores = jnp.zeros(doc_len.shape[0], bf).at[docs].add(contrib)
    return jax.lax.top_k(scores, h)


def serve_bf16(corpus, index, family, W, w16, pipe: Dict, model: Dict,
               query_words: np.ndarray) -> List:
    """One query through the bf16 cascade: retrieve, cutoff, rerank."""
    terms = corpus.term_of_word[query_words]
    top, docs = bf16_bm25_top(index, terms, pipe["postings_budget"],
                              pipe["retrieve_h"])
    keep = top > 0
    top, docs = top[keep], docs[keep]
    if pipe.get("dynamic_cutoff") is not None and len(top):
        keep = top[0] - top <= pipe["dynamic_cutoff"]
        top, docs = top[keep], docs[keep]
    per = corpus.sents_per_doc
    cand = [(int(d), s) for d in docs for s in range(per)]
    if not cand:
        return []
    p = family.control_scores(w16, W, corpus, query_words,
                              [d * per + s for d, s in cand], model)
    order = np.argsort(-p, kind="stable")[:pipe["rerank_k"]]
    return [(cand[i][0], cand[i][1], float(p[i])) for i in order]


def control_numbers(cell, seed: int, n_queries: int) -> List[Dict]:
    """The compared numbers of the bf16 control on a run's check sample."""
    from bench import corpus as C
    from bench import families
    config = cell.config
    m = dict(config["model"])
    family = families.load(cell.root, config)
    corpus = C.generate(config["corpus"], m["vocab_size"], seed)
    idx = C.build_index(corpus, m["vocab_size"])
    qs = C.make_queries(corpus, config["queries"], n_queries, seed)
    W = family.reference_weights(C.jax_key_bits(seed), m)
    w16 = family.control_weights(W)
    limits = config["check"]["limits"]
    check = R.Check()
    for words in qs.word_ids:
        served = serve_bf16(corpus, idx, family, W, w16, config["pipeline"],
                            m, words)
        R.check_query(check, corpus, idx, config["pipeline"], family, W, m,
                      words, served, 2.0 * limits["score_gap"])
    return R.numbers(check, limits)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    from bench import harness
    cell = harness.load_cell(ROOT, args.workload)
    n = int(cell.config["check"]["queries"]) + 1
    for seed in (int(s) for s in args.seeds.split(",")):
        rows = control_numbers(cell, seed, n)
        print(json.dumps({"seed": seed, "control": "bf16",
                          "compared": {r["name"]: r["value"] for r in rows},
                          "correct": harness.reference.passes(rows)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
