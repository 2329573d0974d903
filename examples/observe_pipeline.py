"""Observability tour: follow one query from client to scorer and back.

Stands up the canonical cascade as a live service, fires queries at it,
then answers the three operator questions the telemetry fabric exists for:

  1. WHERE DID THE TIME GO — one request's span tree, from the client's
     ``client.rank_batch`` span down through server dispatch, admission,
     plan stages, micro-batcher queue-wait vs compute, and the scorer
     call, printed as an indented tree with per-span latency.
  2. WHAT IS THE FLEET DOING — the process-wide MetricsRegistry snapshot
     (Prometheus-style flattened keys: counters with labels, histogram
     buckets), the same payload a v5 MSG_STATS control frame returns to a
     fabric supervisor.
  3. CAN I LOOK AT IT PROPERLY — the collected spans exported as Chrome
     trace-event JSON; load the file in https://ui.perfetto.dev or
     chrome://tracing and every lane/nesting matches the printed tree.

  PYTHONPATH=src python examples/observe_pipeline.py
  PYTHONPATH=src python examples/observe_pipeline.py --queries 12 \\
      --trace-out pipeline_trace.json

The server's rerank dispatches into an in-process ``ReplicaPool``
(``target="remote"``), so the demo exercises the full instrumented path a
fabric worker runs — including the batcher queue-wait/compute split that
MSG_STATS aggregation reports per worker.
"""
import argparse

from repro.launch.world import build_world
from repro.core import backends as BK
from repro.core import ops
from repro.core import service as SV
from repro.core.plan import PlanContext
from repro.serving import telemetry
from repro.serving.cluster import ReplicaPool
from repro.serving.engine import PipelineEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="numpy", choices=BK.BACKENDS)
    ap.add_argument("--queries", type=int, default=8)
    ap.add_argument("--trace-out", default="pipeline_trace.json",
                    metavar="PATH", help="Chrome trace-event JSON output "
                    "(open in Perfetto); empty string disables")
    args = ap.parse_args()

    print("== building world (corpus, index, trained reranker) ==")
    cfg, params, corpus, tok, index, _ = build_world(train_steps=30)

    print(f"== serving the canonical cascade ({args.backend}, rerank via "
          f"in-process replica pool) ==")
    pipeline = (ops.Retrieve(h=10) >> ops.DynamicCutoff(margin=3.0)
                >> ops.Rerank(args.backend, k=3))
    pool = ReplicaPool.build(args.backend, params, cfg, tok, corpus.idf,
                             n_replicas=2, buckets=(1, 8, 64, 256))
    engine = PipelineEngine(
        pipeline,
        PlanContext.from_world(cfg, params, corpus, tok, index,
                               buckets=(1, 8, 64, 256), remote=pool),
        target="remote")
    srv = SV.ThreadPoolServer(engine).start_background()
    print(f"  {engine.describe()}")

    queries = corpus.questions[: args.queries]
    telemetry.reset_all()           # the report covers only this traffic
    with SV.Client(srv.address) as client:
        for q in queries:
            client.rank_batch([q])
        # The client span is the trace root: its context crossed the wire
        # (v5 FLAG_TRACE), so the server-side spans join the same tree.
        spans = telemetry.get_tracer().finished()
        last_trace = spans[-1].trace_id

        print(f"\n== span tree: last query ({queries[-1]!r}) ==")
        print(telemetry.format_span_tree(spans, trace_id=last_trace))

        print("\n== metrics registry snapshot (MSG_STATS payload) ==")
        snap = telemetry.get_registry().snapshot()
        for key in sorted(snap):
            if "_bucket{" in key:   # elide per-bucket rows for readability
                continue
            print(f"  {key} = {snap[key]:g}")
        waits = [k for k in snap if k.startswith("batcher_queue_wait_ms")]
        print(f"  (+ {sum(1 for k in snap if '_bucket{' in k)} histogram "
              f"bucket keys, e.g. {len(waits)} for batcher queue-wait)")

    if args.trace_out:
        n = telemetry.export_chrome_trace(args.trace_out, spans)
        print(f"\n== wrote {n} trace events to {args.trace_out} ==")
        print("   open in https://ui.perfetto.dev or chrome://tracing")

    srv.stop()
    pool.stop()


if __name__ == "__main__":
    main()
