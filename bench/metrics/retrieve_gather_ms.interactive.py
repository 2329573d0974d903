"""BM25 retrieval (core/bm25.py via RetrievalStage): the ``bm25.gather``
spans under the retrieval stage (query encoding, the postings gather,
padding and stacking), time per query served."""
from bench import splits


def read(run):
    return splits.named_ms(run, "bm25.gather")
