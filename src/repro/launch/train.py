"""Training launcher: ``--arch <id>`` selects any registered architecture.

Runs REDUCED configs end-to-end on this host (full configs are exercised via
launch.dryrun; on a real pod the same code path runs them by passing
--full). Includes checkpoint/resume, straggler accounting, and the
fault-tolerant step loop.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --steps 30
  PYTHONPATH=src python -m repro.launch.train --arch dlrm-mlperf --steps 50
"""
from __future__ import annotations

import argparse
import functools

import jax
import numpy as np

from repro.configs import ASSIGNED_ARCHS, get_config, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.training.optimizer import adamw, warmup_cosine_schedule
from repro.training.train_loop import Trainer


def build(arch: str, full: bool, batch: int, seq_len: int):
    cfg = get_config(arch)
    if not full:
        cfg = reduced(cfg)
    fam = cfg.family
    key = jax.random.PRNGKey(0)

    if fam == "lm":
        from repro.data.lm import token_batches
        from repro.models import transformer as tfm
        params = tfm.init_lm(key, cfg)
        loss = functools.partial(tfm.loss_fn, cfg=cfg)
        data = token_batches(cfg.vocab_size, batch, seq_len)
        return cfg, params, loss, data

    if fam == "gnn":
        from repro.data.graph import graph_batch
        from repro.models import gnn as gnn_lib
        d_feat = 16
        params = gnn_lib.init_gnn(key, cfg, d_feat)
        loss = functools.partial(gnn_lib.loss_fn, cfg=cfg)

        def graphs():
            i = 0
            while True:
                yield graph_batch(200, 800, d_feat=d_feat, d_out=cfg.d_out,
                                  seed=i)
                i += 1
        return cfg, params, loss, graphs()

    if fam == "recsys":
        from repro.data.recsys import batches
        from repro.models import recsys as rec_lib
        params = rec_lib.init_model(key, cfg)
        loss = functools.partial(rec_lib.loss_fn, cfg=cfg)
        return cfg, params, loss, batches(cfg, batch)

    # textpair (sm-cnn)
    from repro.data import qa as QA
    from repro.data.tokenizer import HashingTokenizer
    from repro.models import sm_cnn
    corpus = QA.generate_corpus(n_docs=80, n_questions=60, seed=0)
    tok = HashingTokenizer(cfg.vocab_size)
    params = sm_cnn.init_sm_cnn(key, cfg)
    loss = functools.partial(sm_cnn.loss_fn, cfg=cfg)

    def pairs():
        ep = 0
        while True:
            yield from QA.pair_batches(corpus, tok, cfg.max_len, batch, seed=ep)
            ep += 1
    return cfg, params, loss, pairs()


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    choices=list(ASSIGNED_ARCHS) + ["sm-cnn"])
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--full", action="store_true",
                    help="full config (pod-scale; use under a real mesh)")
    args = ap.parse_args()

    cfg, params, loss, data = build(args.arch, args.full, args.batch,
                                    args.seq_len)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"arch={args.arch} family={cfg.family} params={n_params:,}")
    opt = adamw(warmup_cosine_schedule(args.lr, 10, args.steps))
    tr = Trainer(loss, opt, params, ckpt_dir=args.ckpt_dir, ckpt_every=50)
    if args.ckpt_dir and tr.restore():
        print(f"resumed at step {tr.step}")
    metrics = tr.run(data, max_steps=args.steps, log_every=10)
    print("final:", {k: round(v, 4) for k, v in metrics.items()})


if __name__ == "__main__":
    main()
