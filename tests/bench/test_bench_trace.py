"""Trace reduction: busy union, idle share, a program's device time and
idle gaps labelled by the host spans open in them, on a trace recorded on
a TPU v5 lite and on hand-made events."""
import json
from pathlib import Path

import pytest

from bench import devtrace

FIXTURE = Path(__file__).with_name("fixtures") / "tpu_trace.json"


@pytest.fixture
def recorded():
    return json.loads(FIXTURE.read_text())


def test_recorded_trace_busy_idle_and_scorer_time(recorded):
    plane = recorded["devices"]["/device:TPU:0"]
    lo = plane["modules"][0][1]
    hi = max(t + d for _, t, d in plane["ops"])
    out = devtrace.reduce(recorded, (lo, hi))
    # busy: the union of operation intervals, counted by hand
    ivs = sorted((t, t + d) for _, t, d in plane["ops"])
    merged = []
    for s, e in ivs:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    busy = sum(e - s for s, e in merged) / 1e9
    assert out["busy_s"] == pytest.approx(busy)
    assert out["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert 0 < out["busy_s"] < out["window_s"]
    lam = sum(min(t + d, hi) - t for n, t, d in plane["modules"]
              if n.startswith("jit__lambda(") and t < hi)
    assert out["program_s"]["jit__lambda"] == pytest.approx(lam / 1e9)
    names = [n for n, _ in out["device_ops"]]
    assert any(n.startswith("jit_seg/%sort") for n in names)
    assert sum(s for _, s in out["idle_gaps"]) == pytest.approx(
        out["window_s"] - out["busy_s"])


def test_union_and_clipping_by_hand():
    trace = {"marker": None, "devices": {
        "/device:TPU:0": {
            "modules": [["jit_a(1)", 0.0, 400.0], ["jit_b(2)", 500.0, 500.0]],
            "ops": [["%x = f32[] a", 0.0, 300.0], ["%y = f32[] a", 100.0, 300.0],
                    ["%z = f32[] b", 600.0, 200.0]]},
        "/device:TPU:1": {"modules": [], "ops": [["%w = f32[] c", 0.0, 1000.0]]},
    }}
    out = devtrace.reduce(trace, (0.0, 1000.0))
    # chip 0 busy 400 + 200, chip 1 busy 1000: mean 800 ns
    assert out["busy_s"] == pytest.approx(800e-9)
    assert out["devices"] == 2
    assert devtrace.union_length([(0, 3), (1, 2), (5, 6)]) == 4
    assert out["program_s"]["jit_a"] == pytest.approx(200e-9)


def test_gaps_labelled_by_innermost_open_span():
    gaps = [(0.0, 1e6), (2e6, 2.05e6), (3e6, 4e6)]
    spans = [("server.rank", 1, 0.0, 5e6), ("featurize", 1, 0.2e6, 0.9e6),
             ("client.rank", 2, 0.0, 1e6)]
    out = dict(devtrace.label_gaps(gaps, spans))
    assert out["client.rank+featurize"] == pytest.approx(1e-3)
    assert out["short-gaps"] == pytest.approx(0.05e-3)
    assert out["server.rank"] == pytest.approx(1e-3)
    assert dict(devtrace.label_gaps([(0, 1e6)], []))["no-span"] == 1e-3


def test_span_clock_alignment():
    got = devtrace.to_profile_clock([1_000_010.0], 1_000_000.0, 5e6)
    assert got[0] == pytest.approx(5e6 + 10e3)
    assert devtrace.program_name("jit__lambda(3214622743570666750)") == \
        "jit__lambda"
