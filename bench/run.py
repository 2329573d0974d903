"""Benchmark entry point: one run of one cell on the chips of this machine.

  python3 bench/run.py --workload trecqa-interactive --seed 7 \
      --seconds 30 --trace 0

Prints diagnostics on standard error, each compared number beside its
limit as the last lines there, and one JSON object as the last line of
standard output (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``; with ``--trace 1`` the per-layer metrics and a ``breakdown``;
``compared`` last). Exits non-zero, printing no result, when JAX finds no
TPU or fewer chips than the cell asks for, or when the program under test
is not beside the benchmark.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"error: the program under test (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    harness.use_compile_cache(ROOT)
    cell = harness.load_cell(ROOT, args.workload)
    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                             t_start=T_START)
    except harness.NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    for name, row in result["compared"].items():
        print(f"compared {name} = {row['value']!r} (limit {row['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
