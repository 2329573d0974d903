"""BM25 retrieval: thread CPU time over wall time of the retrieval stage
spans (``stage.bm25-h*``, their ``cpu_ms``), in percent. Well under 100,
the serving thread waited inside retrieval (the GIL, the device, a lock)."""
from bench import spans as S
from bench import splits


def read(run):
    return splits.cpu_share(S.by_name(run.spans, "stage.bm25"))
