"""sm-cnn (Severyn & Moschitti 2015, as simplified by Rao et al. 2017):
the program's side of a run, its plain reference, and its bf16 control.

The reference imports nothing of the program. It draws its own weights
from the seed (the same draws as the model's initializer), builds its own
features from the corpus's word ids, and runs sm-cnn in float32 numpy
(exact float32 products and sums, what ``highest`` matmul precision means
on the chip). The control is the same model with bf16 weights and
activations, on the default device. Operation and byte counts are in
``bench/flops.py``.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: sm-cnn at a size a CPU test run holds; the cells' own files give the rest.
TINY = {"vocab_size": 500, "embed_dim": 8, "conv_filters": 12,
        "n_hidden": 28, "max_len": 16}


# --- the program's side ----------------------------------------------------

def program_config(config: Dict):
    from repro.configs.base import TextPairConfig
    return TextPairConfig(name=config["name"], **config["model"])


def init_params(key, cfg) -> Dict:
    from repro.models import sm_cnn
    return sm_cnn.init_sm_cnn(key, cfg)


def warm(pool, cfg, buckets) -> None:
    for rep in pool.replicas:
        for b in buckets:
            rep.batcher.scorer(np.zeros((b, cfg.max_len), np.int32),
                               np.zeros((b, cfg.max_len), np.int32),
                               np.zeros((b, cfg.n_extra_feats), np.float32))


# --- the plain reference ---------------------------------------------------

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


def reference_weights(key_bits: int, m: Dict) -> Weights:
    """sm-cnn's initializer, restated: split the key five ways; normal
    embeddings times 0.02; dense layers normal over sqrt(fan-in); zero
    biases. Drawn on the CPU backend where there is one."""
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


def served_logit(p: np.ndarray) -> np.ndarray:
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


def reference_logits(W: Weights, corpus, query_words: np.ndarray,
                     ids: Sequence[int], m: Dict) -> np.ndarray:
    """The reference's logit of each sentence in ``ids`` for the query."""
    return logits(W, *features(corpus, query_words, ids, m["max_len"]))


# --- the bf16 control ------------------------------------------------------

def control_weights(W: Weights) -> Dict:
    return {k: jnp.asarray(getattr(W, k), jnp.bfloat16) for k in (
        "embed", "conv_q_w", "conv_q_b", "conv_a_w", "conv_a_b", "join_w",
        "join_b", "out_w", "out_b")}


def control_scores(w: Dict, W: Weights, corpus, query_words: np.ndarray,
                   ids: Sequence[int], m: Dict) -> np.ndarray:
    """P(relevant) of each sentence in ``ids`` from the bf16 model."""
    q_tok, a_tok, feats = features(corpus, query_words, ids, m["max_len"])
    return bf16_scores(w, W.width, np.asarray(q_tok), a_tok, feats)


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
