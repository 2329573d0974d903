"""Kernel (the scorer's compiled program): the least time the chip could
take for the scorer calls of the window, summed call by call (each the
larger of its operations over bf16 peak and its bytes over HBM bandwidth,
useful rows only, ``bench/flops.py``), over the device time of the scorer
program in the profiler trace. Which bound rules is logged."""
import sys

from bench import flops
from bench import spans as S
from bench.peaks import peaks


def read(run):
    if run.trace is None:
        return None
    device_s = run.trace["program_s"].get(
        run.cell.config["trace"]["scorer_program"], 0.0)
    calls = S.by_name(run.spans, "scorer")
    if not device_s or not calls:
        return None
    p = peaks(run.device_kind)
    least, bounds = 0.0, {"flops": 0, "bytes": 0}
    for c in calls:
        t, bound = flops.least_time_s(run.model, int(c.attrs["rows"]),
                                      p["bf16_flops"], p["hbm_bytes_s"])
        least += t
        bounds[bound] += 1
    print(f"# scorer_roofline: {len(calls)} calls, least {least:.6f} s, "
          f"device {device_s:.6f} s, bound by {bounds}", file=sys.stderr)
    return 100.0 * least / device_s
