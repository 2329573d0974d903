"""Arithmetic over the spans inside a layer: the retrieval stage's
``bm25.gather``, ``bm25.score`` and ``bm25.segment`` children, the replica
pool's ``featurize`` span with its ``featurize.encode`` and
``featurize.pairs`` children, and the ``cpu_ms``, ``hits`` and ``misses``
those spans carry. Where the program opens no such span, or its spans carry
no such attribute, each reads None."""
from __future__ import annotations

from typing import Iterable, List, Optional

from bench import spans as S


def named(spans: Iterable, name: str) -> List:
    """Spans called exactly ``name``."""
    return [s for s in spans if s.name == name]


def named_ms(run, name: str) -> Optional[float]:
    """Time in the spans called ``name`` per query served."""
    found = named(run.spans, name)
    if not found:
        return None
    return S.per_query_ms(sum(s.dur_us for s in found),
                          S.queries_served(run.spans))


def named_cpu_ms(run, name: str) -> Optional[float]:
    """Thread CPU time (``cpu_ms``) in the spans called ``name`` per query
    served."""
    found = [s for s in named(run.spans, name) if "cpu_ms" in s.attrs]
    if not found:
        return None
    return S.per_query_ms(1e3 * sum(float(s.attrs["cpu_ms"]) for s in found),
                          S.queries_served(run.spans))


def cpu_share(spans: Iterable) -> Optional[float]:
    """Thread CPU time over wall time of the spans that carry ``cpu_ms``,
    in percent: well under 100, the thread waited inside them."""
    found = [s for s in spans if "cpu_ms" in s.attrs]
    wall_ms = sum(s.dur_us for s in found) / 1e3
    if not wall_ms:
        return None
    return 100.0 * sum(float(s.attrs["cpu_ms"]) for s in found) / wall_ms


def hit_share(spans: Iterable) -> Optional[float]:
    """Cache hits over lookups of the spans that carry ``hits`` and
    ``misses``, in percent."""
    found = [s for s in spans if "hits" in s.attrs and "misses" in s.attrs]
    hits = sum(float(s.attrs["hits"]) for s in found)
    lookups = hits + sum(float(s.attrs["misses"]) for s in found)
    return 100.0 * hits / lookups if lookups else None
