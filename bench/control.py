"""The control of the correctness check: the reference cascade put in the
program's place and computed in bfloat16, the step below the float32 the
configuration states. Its rankings go through the same comparison as the
program's, which must call them not correct.

  python3 bench/control.py --workload msmarco-bulk --seeds 5,6,7

Builds the cell's corpus, index and queries from each seed as a run does,
serves the check's sample of queries with the bf16 cascade (BM25 summed in
bf16, sm-cnn with bf16 weights and activations, on the default device),
and prints each compared number beside the cell's limit. It needs no
measured window: it checks as many queries as a run does, drawn as a
run draws them.
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


def bf16_weights(W) -> Dict:
    return {k: jnp.asarray(getattr(W, k), jnp.bfloat16) for k in (
        "embed", "conv_q_w", "conv_q_b", "conv_a_w", "conv_a_b", "join_w",
        "join_b", "out_w", "out_b")}


def bf16_scores(w: Dict, width: int, q_tok, a_tok, feats) -> np.ndarray:
    """P(relevant) from sm-cnn run in bf16 end to end (rows padded to a
    power of two so the program compiles a few times only)."""
    n = len(q_tok)
    rows = 1 << max(n - 1, 0).bit_length()

    def padded(x):
        return np.pad(np.asarray(x), ((0, rows - n), (0, 0)))

    p = _sm_cnn_bf16(w, padded(q_tok), padded(a_tok), padded(feats), width)
    return np.asarray(p, np.float32)[:n]


@functools.partial(jax.jit, static_argnums=(4,))
def _sm_cnn_bf16(w, q_tok, a_tok, feats, width):
    bf = jnp.bfloat16

    def arm(x, cw, cb):
        pad = width - 1
        xp = jnp.pad(x, ((0, 0), (pad, pad), (0, 0)))
        cols = jnp.concatenate([xp[:, i:i + x.shape[1] + pad]
                                for i in range(width)], axis=-1)
        return jnp.max(jnp.tanh(cols @ cw + cb), axis=1)

    xq = arm(w["embed"][q_tok], w["conv_q_w"], w["conv_q_b"])
    xa = arm(w["embed"][a_tok], w["conv_a_w"], w["conv_a_b"])
    xj = jnp.concatenate([xq, xa, feats.astype(bf)], axis=-1)
    hdn = jnp.tanh(xj @ w["join_w"] + w["join_b"])
    lg = hdn @ w["out_w"] + w["out_b"]
    return jnp.exp(jax.nn.log_softmax(lg, axis=-1))[:, 1]


def serve_bf16(corpus, index, W, w16: Dict, pipe: Dict, max_len: int,
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
    q_tok, a_tok, feats = R.features(corpus, query_words,
                                     [d * per + s for d, s in cand], max_len)
    p = bf16_scores(w16, W.width, np.asarray(q_tok), a_tok, feats)
    order = np.argsort(-p, kind="stable")[:pipe["rerank_k"]]
    return [(cand[i][0], cand[i][1], float(p[i])) for i in order]


def control_numbers(cell, seed: int, n_queries: int) -> List[Dict]:
    """The compared numbers of the bf16 control on a run's check sample."""
    from bench import corpus as C
    from bench.harness import model_config
    config = cell.config
    cfg = model_config(config)
    corpus = C.generate(config["corpus"], cfg.vocab_size, seed)
    idx = C.build_index(corpus, cfg.vocab_size)
    qs = C.make_queries(corpus, config["queries"], n_queries, seed)
    m = dict(config["model"])
    W = R.init_weights(C.jax_key_bits(seed), m)
    w16 = bf16_weights(W)
    limits = config["check"]["limits"]
    check = R.Check()
    for words in qs.word_ids:
        served = serve_bf16(corpus, idx, W, w16, config["pipeline"],
                            m["max_len"], words)
        R.check_query(check, corpus, idx, W, config["pipeline"],
                      m["max_len"], words, served,
                      2.0 * limits["score_gap"])
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
