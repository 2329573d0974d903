"""Featurization (data/featurize.py through the replica pool): the self
time of ``pool.get_scores`` per query (``spans.featurize_ms``)."""
from bench.spans import featurize_ms as read  # noqa: F401
