"""Reduce a JAX profiler trace to device busy time, per-program device
time, the top device operations and the idle gaps labelled by what the
host was doing.

``load`` reads the ``.xplane.pb`` the profiler wrote into plain lists, so
the arithmetic (``reduce``) runs on a small recorded fixture as well as on
a real trace. Device planes are ``/device:TPU:<n>``; each holds an
``XLA Modules`` line (one event per program execution, named
``<jit name>(<fingerprint>)``) and an ``XLA Ops`` line (one event per
operation). Host and device events share the profile's clock; the
benchmark's ``bench.window`` annotation ties that clock to the program's
span clock.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

MARKER = "bench.window"
#: Gaps shorter than this are the device's own dispatch gaps inside a
#: program, not the host holding it back; they are summed under one label.
LABEL_MIN_GAP_NS = 100_000


def load(log_dir: str) -> Dict:
    """{"devices": {plane: {"modules": [[name, t0, dur]...], "ops": [...]}},
    "marker": [t0, dur] or None} from the newest trace under ``log_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {log_dir}")
    data = ProfileData.from_file(files[-1])
    out: Dict = {"devices": {}, "marker": None}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key:
                    lines[key] = [[e.name, float(e.start_ns),
                                   float(e.duration_ns)]
                                  for e in line.events]
            out["devices"][plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == MARKER:
                        out["marker"] = [float(e.start_ns),
                                         float(e.duration_ns)]
    return out


def union_length(intervals: Sequence[Tuple[float, float]]) -> float:
    """Total length covered by [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _clip(events, lo: float, hi: float) -> List[Tuple[str, float, float]]:
    out = []
    for name, t0, dur in events:
        s, e = max(t0, lo), min(t0 + dur, hi)
        if e > s:
            out.append((name, s, e))
    return out


def _gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    gaps, t = [], lo
    for s, e in sorted(busy):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def program_name(module_event: str) -> str:
    """``jit_foo(1234)`` -> ``jit_foo``."""
    return module_event.split("(", 1)[0]


def reduce(trace: Dict, window_ns: Tuple[float, float],
           host_spans: Optional[Sequence[Tuple[str, int, float, float]]]
           = None) -> Dict:
    """Busy and idle over ``window_ns`` (profile clock), averaged over the
    device planes; device time per program; top operations; idle gaps by
    host activity. ``host_spans`` are (name, thread, start_ns, end_ns) on
    the profile clock."""
    lo, hi = window_ns
    window = hi - lo
    busy_ns, programs = [], defaultdict(float)
    op_time = defaultdict(float)
    gaps_all = []
    for plane, lines in trace["devices"].items():
        ops = _clip(lines["ops"], lo, hi)
        intervals = [(s, e) for _, s, e in ops]
        busy_ns.append(union_length(intervals))
        mods = _clip(lines["modules"], lo, hi)
        for name, s, e in mods:
            programs[program_name(name)] += e - s
        starts = [s for _, s, _ in mods]
        for name, s, e in ops:
            i = bisect.bisect_right(starts, s) - 1
            prog = (program_name(mods[i][0])
                    if i >= 0 and mods[i][2] >= e else "?")
            op_time[f"{prog}/{name.split(' = ', 1)[0]}"] += e - s
        gaps_all.extend(_gaps(intervals, lo, hi))
    n_dev = max(len(busy_ns), 1)
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window / 1e9,
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "devices": len(busy_ns),
        "program_s": {k: v / n_dev / 1e9 for k, v in programs.items()},
        "device_ops": [[k, v / n_dev / 1e9] for k, v in top_ops],
        "idle_gaps": label_gaps(gaps_all, host_spans or (), n_dev),
    }


def label_gaps(gaps: Sequence[Tuple[float, float]],
               host_spans: Sequence[Tuple[str, int, float, float]],
               n_dev: int = 1) -> List[List]:
    """Idle seconds by what the host had open at each gap's midpoint: the
    innermost span of every thread, names joined by '+' ("no-span" when
    nothing was open). Gaps under ``LABEL_MIN_GAP_NS`` go under
    "short-gaps". Top 10 labels by idle time."""
    by_thread: Dict[int, List[Tuple[float, float, str]]] = defaultdict(list)
    for name, tid, s, e in host_spans:
        by_thread[tid].append((s, e, name))
    threads = []
    for spans in by_thread.values():
        spans.sort()
        threads.append(([s for s, _, _ in spans], spans))
    totals: Dict[str, float] = defaultdict(float)
    for g0, g1 in gaps:
        if g1 - g0 < LABEL_MIN_GAP_NS:
            totals["short-gaps"] += g1 - g0
            continue
        mid = 0.5 * (g0 + g1)
        names = set()
        for starts, spans in threads:
            i = bisect.bisect_right(starts, mid) - 1
            # spans of one thread nest; walk back to the innermost open one
            for j in range(i, max(i - 64, -1), -1):
                s, e, name = spans[j]
                if e > mid:
                    names.add(name)
                    break
        totals["+".join(sorted(names)) or "no-span"] += g1 - g0
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    return [[k, v / n_dev / 1e9] for k, v in top]


def to_profile_clock(epoch_us: np.ndarray, marker_epoch_us: float,
                     marker_ns: float) -> np.ndarray:
    """Span times (epoch microseconds) -> the profile's clock."""
    return (np.asarray(epoch_us) - marker_epoch_us) * 1e3 + marker_ns
