"""Featurization (data/featurize.py through the replica pool): the
``featurize.encode`` spans (token rows of every pair), time per query
served."""
from bench import splits


def read(run):
    return splits.named_ms(run, "featurize.encode")
