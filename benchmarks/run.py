"""Benchmark orchestrator. One function per paper table; prints
``name,us_per_call,derived`` CSV and can dump the full rows as JSON so the
perf trajectory is machine-readable across PRs.

  PYTHONPATH=src python -m benchmarks.run                # all tables, quick
  PYTHONPATH=src python -m benchmarks.run --table 1      # just Table 1
  PYTHONPATH=src python -m benchmarks.run --table loadgen --json out.json
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import time


def snapshot_meta() -> dict:
    """Provenance stamped onto every JSON row: which commit, when, and on
    how many cores the numbers were taken — so two BENCH_*.json files are
    comparable (or visibly not, e.g. different host_cores)."""
    try:
        sha = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             capture_output=True, text=True, timeout=10,
                             cwd=os.path.dirname(os.path.abspath(__file__)),
                             ).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        sha = "unknown"
    return {
        "git_sha": sha,
        "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "host_cores": float(os.cpu_count() or 1),
    }


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--table", default="all",
                    choices=["all", "1", "2", "e2e", "pipeline_plans",
                             "loadgen", "fabric", "roofline", "rollout",
                             "lint"])
    ap.add_argument("--processes", default="1,2,4", metavar="N,N,...",
                    help="worker-process counts for --table fabric")
    ap.add_argument("--naive", action="store_true",
                    help="include the naive per-filter conv condition")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the result rows as a JSON list")
    args = ap.parse_args()

    from benchmarks import (e2e_pipeline, loadgen, pipeline_plans,
                            rollout_bench, roofline_table,
                            table1_feedforward, table2_service)
    from benchmarks.common import build_world

    rows = []
    world = None
    if args.table in ("all", "1", "2", "e2e", "pipeline_plans", "loadgen",
                      "rollout"):
        world = build_world()
    if args.table in ("all", "1"):
        rows += table1_feedforward.run(batch=1, world=world, naive=args.naive)
        rows += table1_feedforward.run(batch=64, world=world)
        rows += table1_feedforward.paper_size_contrast()
    if args.table in ("all", "2"):
        rows += table2_service.run(world=world)
    if args.table in ("all", "e2e"):
        rows += e2e_pipeline.run(world=world)
    if args.table in ("all", "pipeline_plans"):
        rows += pipeline_plans.run(world=world)
    if args.table in ("all", "loadgen"):
        rows += loadgen.run(world=world)
    if args.table == "fabric":
        # Not in "all": each process count spawns/tears down a worker
        # fleet (several seconds of process startup per level), so the
        # sweep runs only when asked for.
        rows += loadgen.run_fabric(
            tuple(int(x) for x in args.processes.split(",")))
    if args.table in ("all", "roofline"):
        rows += roofline_table.run()
    if args.table in ("all", "lint"):
        # Cheap (no world needed): times the repro-lint hard gate over
        # the real tree plus the sanitizer's per-acquisition overhead.
        from benchmarks import lint_bench
        rows += lint_bench.run()
    if args.table == "rollout":
        # Not in "all": it drives a live 2-replica pool with closed-loop
        # client threads for a couple of seconds per condition.
        rows += rollout_bench.run(world=world)

    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    if args.json:
        meta = snapshot_meta()
        for r in rows:
            r.update(meta)
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=2, sort_keys=True)
        print(f"# wrote {len(rows)} rows to {args.json} "
              f"(sha={meta['git_sha']} utc={meta['utc']} "
              f"cores={meta['host_cores']:g})")


if __name__ == "__main__":
    main()
