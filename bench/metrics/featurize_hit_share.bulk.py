"""Featurization: the share of the ``featurize`` spans' own lookups of
answer token rows and pair features (their ``hits`` and ``misses``) that
hit the pool's LRUs, in percent. The query's token row, looked up once a
pair, is not counted."""
from bench import splits


def read(run):
    return splits.hit_share(splits.named(run.spans, "featurize"))
