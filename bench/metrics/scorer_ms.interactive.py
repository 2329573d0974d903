"""Scorer (core/backends.py): mean host time of a scorer call (padding,
transfer, device, blocking read-back), the program's ``scorer_batch_ms``
histogram over the window. Not device time."""
from bench import spans as S


def read(run):
    return S.hist_mean(run.registry, "scorer_batch_ms")
