"""Micro-batcher (serving/batcher.py): mean rows per coalesced batch, the
program's ``batcher_batch_rows`` histogram over the window."""
from bench import spans as S


def read(run):
    return S.hist_mean(run.registry, "batcher_batch_rows")
