"""Arithmetic over the program's finished spans (``SpanRecord``), copied
from ``benchmarks/trace_table.py`` / ``telemetry.stage_breakdown``: group
by name, durations in milliseconds, self time as a span's duration less
what its children cover."""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, Iterable, List, Optional

from bench.devtrace import union_length


def by_name(spans: Iterable, prefix: str) -> List:
    return [s for s in spans if s.name.startswith(prefix)]


def children(spans: Iterable) -> Dict[int, List]:
    out: Dict[int, List] = defaultdict(list)
    for s in spans:
        out[s.parent_id].append(s)
    return out


def covered_us(parent, kids: Iterable) -> float:
    """Length of the parent's interval that its children cover."""
    lo, hi = parent.ts_us, parent.ts_us + parent.dur_us
    clipped = [(max(k.ts_us, lo), min(k.ts_us + k.dur_us, hi)) for k in kids]
    return union_length([(s, e) for s, e in clipped if e > s])


def queries_served(spans: Iterable, stage_prefix: str = "stage.bm25"
                   ) -> int:
    """Queries the retrieval stage ran in the window (its span carries
    the batch's query count)."""
    return int(sum(s.attrs.get("queries", 1)
                   for s in by_name(spans, stage_prefix)))


def per_query_ms(total_us: float, queries: int) -> Optional[float]:
    return total_us / 1e3 / queries if queries else None


def hist_mean(registry: Dict[str, float], name: str) -> Optional[float]:
    """Mean of a registry histogram over the window (all label sets)."""
    total = sum(v for k, v in registry.items()
                if k == f"{name}_sum" or k.startswith(f"{name}_sum{{"))
    count = sum(v for k, v in registry.items()
                if k == f"{name}_count" or k.startswith(f"{name}_count{{"))
    return total / count if count else None


def hist_sum(registry: Dict[str, float], name: str) -> float:
    return sum(v for k, v in registry.items()
               if k == f"{name}_sum" or k.startswith(f"{name}_sum{{"))


def retrieve_ms(run) -> Optional[float]:
    """The retrieval stage spans' time per query served."""
    total = sum(s.dur_us for s in by_name(run.spans, "stage.bm25"))
    return per_query_ms(total, queries_served(run.spans))


def featurize_ms(run) -> Optional[float]:
    """The self time of ``pool.get_scores`` (its span less the batcher's
    queue-wait and compute spans under it) per query served: the pool
    tokenizes and builds overlap features there and opens no span of its
    own for it."""
    kids = children(run.spans)
    total = sum(p.dur_us - covered_us(p, kids.get(p.span_id, ()))
                for p in by_name(run.spans, "pool.get_scores"))
    return per_query_ms(total, queries_served(run.spans))
