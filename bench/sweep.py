"""Find the highest offered rate an open-loop cell sustains: one set-up,
then the cell's open loop at each rate in turn.

  python3 bench/sweep.py --workload trecqa-interactive --seed 11 \
      --rates 10,20,30,40 --seconds 20

A rate is sustained (``sustained`` in its line) when nothing fails, the
completed rate is within ``COMPLETED_SHARE`` of the offered one, and no
backlog grows: the median latency of the window's last quarter of
requests is within ``GROWTH`` of the first quarter's. The last line gives
the highest sustained rate below the first that is not, and four fifths
of it, which the cell's traffic file takes as a number.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
COMPLETED_SHARE = 0.98
GROWTH = 1.10


def sustained(row) -> bool:
    return (row["failed"] == 0
            and row["completed_qps"] >= COMPLETED_SHARE * row["offered_qps"]
            and row["last_quarter_p50_ms"]
            <= GROWTH * row["first_quarter_p50_ms"])


def highest_sustained(rows):
    """The highest offered rate sustained, below the first that is not."""
    best = None
    for row in sorted(rows, key=lambda r: r["offered_qps"]):
        if not sustained(row):
            break
        best = row["offered_qps"]
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import numpy as np
    from bench import harness, loadgen
    harness.use_compile_cache(ROOT)

    cell = harness.load_cell(ROOT, args.workload)
    rates = [float(r) for r in args.rates.split(",")]
    n = [max(int(round(r * args.seconds)), 1) for r in rates]
    st = harness.setup(cell, args.seed, sum(n))
    rows = []
    try:
        start = 0
        for rate, k in zip(rates, n):
            out = harness.drive(cell, st, args.seconds, first=start,
                                rate=rate)
            start += k
            reqs = out["requests"]
            lat = loadgen.latency_ms(reqs)
            ok = [r for r in reqs if r.ok]
            span = max(r.t_done for r in reqs)
            quarter = max(len(reqs) // 4, 1)
            row = {"offered_qps": rate,
                   "completed_qps": len(ok) / span,
                   "failed": len(reqs) - len(ok),
                   "p50_ms": float(np.percentile(lat, 50)),
                   "p95_ms": float(np.percentile(lat, 95)),
                   "first_quarter_p50_ms": float(np.median(lat[:quarter])),
                   "last_quarter_p50_ms": float(np.median(lat[-quarter:]))}
            row.update(loadgen.lateness_ms(reqs))
            row["sustained"] = sustained(row)
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        st.server.stop()
        st.pool.stop()
    best = highest_sustained(rows)
    print(json.dumps({"highest_sustained_qps": best,
                      "rate_qps": None if best is None
                      else round(0.8 * best, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
