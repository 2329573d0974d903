"""Model families: each configuration's CPU size comes from its family,
and sm-cnn's reference and bf16 control, moved into its family module,
still compute what the formulas say."""
import json
import math
import shutil

import numpy as np

from bench import corpus as C
from bench import families

from benchtree import ROOT, tiny_tree

TOY_FAMILY = '''"""A family with other model keys."""
TINY = {"vocab_size": 64, "toy_width": 3}
'''


def test_tiny_tree_sizes_each_configuration_by_its_family(tmp_path):
    src = tmp_path / "src"
    shutil.copytree(ROOT / "bench", src / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (src / "bench/families/toy_pair.py").write_text(TOY_FAMILY)
    conf = json.loads((ROOT / "bench/configs/sm-cnn-trecqa.json").read_text())
    conf.update(family="toy-pair", model={"vocab_size": 30000,
                                          "toy_width": 1024})
    (src / "bench/configs/toy.json").write_text(json.dumps(conf))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy", "source": "test",
                            "file": "bench/configs/toy.json", "reduced": [],
                            "why": "test"})
    (src / "BENCHMARK.json").write_text(json.dumps(spec))

    dst = tiny_tree(tmp_path / "dst", src)
    toy = json.loads((dst / "bench/configs/toy.json").read_text())
    assert toy["model"] == {"vocab_size": 64, "toy_width": 3}
    trecqa = json.loads((dst / "bench/configs/sm-cnn-trecqa.json").read_text())
    sm_cnn = families.load(dst, trecqa)
    assert sm_cnn.__file__ == str(dst / "bench/families/sm_cnn.py")
    assert {k: trecqa["model"][k] for k in sm_cnn.TINY} == sm_cnn.TINY
    assert trecqa["model"]["filter_width"] == 5


def _inline_logits(W, corpus, query_words, ids, max_len):
    """sm-cnn's features and forward pass written out pair by pair, in
    float32: the formulas the family's reference must keep."""
    idf = corpus.idf_words
    q_all = set(int(w) for w in query_words)
    q_content = set(int(w) for w in query_words if not corpus.is_stop[w])
    q_row = np.zeros(max_len, np.int32)
    qt = corpus.term_of_word[query_words][:max_len]
    q_row[:len(qt)] = qt
    pad = W.width - 1

    def arm(tokens, w, b):
        x = np.zeros((max_len + 2 * pad, W.embed.shape[1]), np.float32)
        x[pad:pad + max_len] = W.embed[tokens]
        cols = np.stack([np.concatenate(x[i:i + W.width])
                         for i in range(max_len + pad)])
        return np.tanh(cols @ w + b).max(axis=0)

    out = []
    for s in ids:
        words = corpus.sentence_ids(int(s))
        a_row = np.zeros(max_len, np.int32)
        at = corpus.term_of_word[words][:max_len]
        a_row[:len(at)] = at
        feats = []
        for qs in (q_all, q_content):
            as_ = (set(int(w) for w in words) if qs is q_all else
                   set(int(w) for w in words if not corpus.is_stop[w]))
            inter = qs & as_
            feats += [len(inter) / max(len(qs), 1),
                      sum(idf[w] for w in inter)
                      / (sum(idf[w] for w in qs) or 1.0)]
        xj = np.concatenate([arm(q_row, W.conv_q_w, W.conv_q_b),
                             arm(a_row, W.conv_a_w, W.conv_a_b),
                             np.asarray(feats, np.float32)])
        lg = np.tanh(xj @ W.join_w + W.join_b) @ W.out_w + W.out_b
        out.append(float(lg[1]) - float(lg[0]))
    return np.asarray(out)


def _tiny_world(tiny, seed):
    conf = json.loads((tiny / "bench/configs/sm-cnn-trecqa.json").read_text())
    m = conf["model"]
    family = families.load(tiny, conf)
    corpus = C.generate(conf["corpus"], m["vocab_size"], seed)
    qs = C.make_queries(corpus, conf["queries"], 3, seed)
    W = family.reference_weights(C.jax_key_bits(seed), m)
    return family, m, corpus, qs, W


def test_reference_logits_follow_the_formulas(tiny):
    family, m, corpus, qs, W = _tiny_world(tiny, 2**31 + 41)
    per = corpus.sents_per_doc
    for j, words in enumerate(qs.word_ids):
        target = int(qs.targets[j])
        ids = [target] + [(target // per + 1) * per + s for s in range(per)]
        got = family.reference_logits(W, corpus, words, ids, m)
        want = _inline_logits(W, corpus, words, ids, m["max_len"])
        assert got.dtype == np.float64 and got.shape == (len(ids),)
        # the same float32 products (logits of about 0.1 here); a blocked
        # product may sum in another order, some 1e-8 apart
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    p = np.asarray([0.25, 0.5, 0.9], np.float32)
    np.testing.assert_allclose(family.served_logit(p),
                               [math.log(x / (1 - x)) for x in p.tolist()],
                               rtol=1e-12)


def test_bf16_control_scores_depart_from_the_reference(tiny):
    family, m, corpus, qs, W = _tiny_world(tiny, 43)
    w16 = family.control_weights(W)
    ids = list(range(64))
    gaps = [np.abs(family.served_logit(family.control_scores(
        w16, W, corpus, words, ids, m))
        - family.reference_logits(W, corpus, words, ids, m)).max()
        for words in qs.word_ids]
    assert max(gaps) > 0.004      # the cells' score_gap limit
