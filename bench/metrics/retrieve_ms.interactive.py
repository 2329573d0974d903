"""BM25 retrieval (core/bm25.py via RetrievalStage): the retrieval stage
span's time per query (``spans.retrieve_ms``)."""
from bench.spans import retrieve_ms as read  # noqa: F401
