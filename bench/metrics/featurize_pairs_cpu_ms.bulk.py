"""Featurization: thread CPU time (``cpu_ms``) inside the
``featurize.pairs`` spans (overlap features of every pair) per query
served; beside ``featurize_pairs_ms.bulk``, the part of that wall time
the thread worked."""
from bench import splits


def read(run):
    return splits.named_cpu_ms(run, "featurize.pairs")
