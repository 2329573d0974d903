"""Micro-batcher (serving/batcher.py): mean of the program's
``batcher_queue_wait_ms`` histogram over the window."""
from bench import spans as S


def read(run):
    return S.hist_mean(run.registry, "batcher_queue_wait_ms")
