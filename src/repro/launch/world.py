"""Canonical demo-world builder: corpus + BM25 index + trained sm-cnn."""
from __future__ import annotations

import functools
import time
from typing import Dict, List, Tuple

import jax
import numpy as np

from repro.configs import get_config
from repro.core import bm25 as BM
from repro.data import qa as QA
from repro.data.tokenizer import HashingTokenizer
from repro.models import sm_cnn
from repro.training.optimizer import adamw
from repro.training.train_loop import Trainer


def build_world(train_steps: int = 60, seed: int = 0, cfg=None):
    """Returns (cfg, params, corpus, tokenizer, index, eval_pairs).

    ``cfg`` defaults to the reduced sm-cnn that tests and examples run;
    pass ``get_config("sm-cnn")`` for the published widths. Raises
    ``FloatingPointError`` if the last training loss is not finite."""
    cfg = cfg or get_config("sm-cnn-smoke")
    corpus = QA.generate_corpus(n_docs=80, n_questions=60, seed=seed)
    tok = HashingTokenizer(cfg.vocab_size)
    index = BM.build_index([tok.encode(" ".join(d)) for d in corpus.documents],
                           cfg.vocab_size)
    params = sm_cnn.init_sm_cnn(jax.random.PRNGKey(seed), cfg)
    tr = Trainer(functools.partial(sm_cnn.loss_fn, cfg=cfg), adamw(3e-3), params)

    def stream():
        ep = 0
        while True:
            yield from QA.pair_batches(corpus, tok, cfg.max_len, 64, seed=ep)
            ep += 1

    last = tr.run(stream(), max_steps=train_steps, log_every=0)
    if not np.isfinite(last.get("loss", 0.0)):
        raise FloatingPointError(f"{cfg.name}: training loss "
                                 f"{last['loss']} after {tr.step} steps")
    eval_pairs = [p for i, p in enumerate(corpus.pairs) if i % 10 == 0]
    return cfg, tr.params, corpus, tok, index, eval_pairs


def eval_batches(corpus, tok, cfg, pairs, batch: int
                 ) -> List[Dict[str, np.ndarray]]:
    out = []
    for i in range(0, len(pairs) - batch + 1, batch):
        out.append(QA.make_batch(corpus, tok, cfg.max_len,
                                 pairs[i:i + batch]))
    return out


def percentile_stats(latencies_s: List[float]) -> Tuple[float, float]:
    arr = np.sort(np.asarray(latencies_s))
    p50 = float(arr[int(0.50 * (len(arr) - 1))])
    p99 = float(arr[int(0.99 * (len(arr) - 1))])
    return p50, p99


def timed(fn, *args) -> float:
    t0 = time.perf_counter()
    fn(*args)
    return time.perf_counter() - t0
