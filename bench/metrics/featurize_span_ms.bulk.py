"""Featurization (data/featurize.py through the replica pool): the
``featurize`` spans (token rows and overlap features of every pair), time
per query served."""
from bench import splits


def read(run):
    return splits.named_ms(run, "featurize")
