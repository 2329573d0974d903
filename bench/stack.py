"""Stand up the served ranking stack a configuration's ``serving`` and
``pipeline`` groups describe, on the program's own classes.

The canonical cascade (``Retrieve(h=10) >> DynamicCutoff(3.0) >>
Rerank(k=3)``) is served through ``launch.serve.build_server``, as
deployments do. ``build_server`` serves no other pipeline, so any other is
built from the same objects it builds: a ``PlanContext``, an in-process
``ReplicaPool`` (micro-batcher and replica scorers), a ``PipelineEngine``
on the ``remote`` target, an ``AdmissionController`` sized for a 32-query
batch, and a ``ThreadPoolServer``.
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Dict, Tuple


def pipeline_of(pipe: Dict, backend: str):
    from repro.core import ops
    p = ops.Retrieve(h=int(pipe["retrieve_h"]))
    if pipe.get("dynamic_cutoff") is not None:
        p = p >> ops.DynamicCutoff(margin=float(pipe["dynamic_cutoff"]))
    return p >> ops.Rerank(backend, k=int(pipe["rerank_k"]))


def build(config: Dict, cfg, params, corpus, tok, index) -> Tuple:
    """(server, pool) serving ``config`` on a background thread pool."""
    from repro.launch import serve as LS
    serving = config["serving"]
    pipe = pipeline_of(config["pipeline"], serving["backend"])
    buckets = tuple(serving["buckets"])
    if pipe == LS.canonical_pipeline(serving["backend"]):
        args = argparse.Namespace(
            serve_pipeline=True, plan_target="remote",
            backend=serving["backend"], replicas=serving["replicas"],
            policy=serving["policy"], server="threadpool", host="127.0.0.1",
            port=0, workers=serving["workers"],
            max_queue=serving["max_queue"], hedge_ms=None, registry=None,
            model_version=None)
        server, pool = LS.build_server(args, cfg, params, corpus, tok,
                                       index=index)
        return server.start_background(), pool
    from repro.core.plan import PlanContext
    from repro.core.service import ThreadPoolServer
    from repro.serving.admission import AdmissionController
    from repro.serving.cluster import ReplicaPool
    from repro.serving.engine import PipelineEngine
    ctx = PlanContext.from_world(cfg, params, corpus, tok, index,
                                 buckets=buckets)
    pool = ReplicaPool.build(serving["backend"], ctx.params, cfg, tok,
                             corpus.idf, n_replicas=serving["replicas"],
                             buckets=buckets, policy=serving["policy"])
    ctx = dataclasses.replace(ctx, remote=pool)
    engine = PipelineEngine(pipe, ctx, target="remote")
    admission = AdmissionController(max_queue_rows=max(
        serving["max_queue"], engine.rows_per_query * 32))
    server = ThreadPoolServer(engine, host="127.0.0.1", port=0,
                              num_workers=serving["workers"],
                              admission=admission)
    return server.start_background(), pool
