"""BM25 retrieval (core/bm25.py via RetrievalStage): the ``bm25.score``
spans under the retrieval stage (the call into the jitted program, the
``doc_len`` and postings transfer, the read-back of top-h), time per query
served."""
from bench import splits


def read(run):
    return splits.named_ms(run, "bm25.score")
