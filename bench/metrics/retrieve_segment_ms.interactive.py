"""BM25 retrieval (core/pipeline.py's RetrievalStage): the
``bm25.segment`` spans under the retrieval stage (sentence expansion and
reading the candidate texts), time per query served."""
from bench import splits


def read(run):
    return splits.named_ms(run, "bm25.segment")
