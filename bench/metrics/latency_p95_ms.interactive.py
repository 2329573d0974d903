"""Whole request, at the client: the 95th percentile of every request's
latency from its scheduled arrival (``loadgen.latency_ms``), the tail of
all requests of the window. A host stall of S seconds at four fifths of
the sustained rate lifts some 4 S seconds of arrivals, so this reads far
off in a run the machine stood still in."""
import numpy as np

from bench import loadgen


def read(run):
    if not run.requests:
        return None
    return float(np.percentile(loadgen.latency_ms(run.requests), 95))
