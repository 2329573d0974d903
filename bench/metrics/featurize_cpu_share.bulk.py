"""Featurization: thread CPU time over wall time of the pool's
``featurize`` spans (their ``cpu_ms``), in percent. Well under 100, the
server threads featurizing under one GIL waited instead of working."""
from bench import splits


def read(run):
    return splits.cpu_share(splits.named(run.spans, "featurize"))
