"""One benchmark run of one cell: set up, measure a window, check.

The cell comes from ``BENCHMARK.json`` by name; its configuration file,
traffic file, model family (``bench/families``) and per-layer metric
readers are found by the names there, so a new cell, mix, metric or
model family is a new file and a new entry, never an edit here.

A run: make the corpus, index and queries from the seed; draw the weights
on the device; stand up the served stack (``stack``); compile and warm
every shape the cell's traffic uses; drive the traffic for ``seconds``
(``loadgen``), with the profiler on for ``--trace 1``; read the device's
memory peak; stop the stack; hold a sample of the served rankings to the
plain reference (``reference``). A request that failed (shed, errored or
unanswered) is a compared number too, with the limit 0. Set-up is timed
by phase.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import logging
import sys
import tempfile
import threading
import time
import types
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import corpus as C
from bench import devtrace, families, loadgen, reference


#: Seconds the load generator waits, after the window closes, for replies
#: to requests sent in it; a request still open then has failed.
DRAIN_S = 60.0


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    root: Path
    config: Dict
    traffic: Dict
    chips: int
    end_to_end: List[Dict]
    per_layer: List[Dict]


def load_cell(root: Path, workload: str) -> Cell:
    """The cell ``workload`` of ``root/BENCHMARK.json``, with its files."""
    root = Path(root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(
        name=workload, root=root,
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads((root / "bench" / "traffic"
                            / f"{w['traffic']}.json").read_text()),
        chips=int(w["chips"]), end_to_end=e2e, per_layer=per_layer)


def load_reader(root: Path, metric: str) -> Callable:
    """``bench/metrics/<metric>.py``'s ``read(run) -> float | None``."""
    path = Path(root) / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def use_compile_cache(root: Path) -> None:
    """JAX's persistent compilation cache in one fixed directory of the
    checkout, every program kept, so only a checkout's first run compiles
    (the program's ``enable_compile_cache`` takes the same variable)."""
    import os
    import jax
    cache = str(Path(root) / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class RunData:
    """What a per-layer metric reader reads."""
    cell: Cell
    requests: List
    window_s: float                   # send window and replies to it
    spans: List                       # program spans finished in the window
    registry: Dict[str, float]        # registry counters, window delta
    trace: Optional[Dict]             # devtrace.reduce output
    device_kind: str
    model: Dict


class CompileCounter(logging.Handler):
    """Counts the programs JAX compiles while installed (its compile log),
    so a compile inside the measured window shows."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.count = 0

    def emit(self, record):
        if "Finished XLA compilation" in record.getMessage():
            self.count += 1

    def __enter__(self):
        import jax
        self._was = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        logging.getLogger("jax").addHandler(self)
        return self

    def __exit__(self, *exc):
        import jax
        logging.getLogger("jax").removeHandler(self)
        jax.config.update("jax_log_compiles", self._was)


class SpanCollector:
    """Copies the program's finished spans out of its bounded ring every
    ``period`` seconds (deduplicated by span id), so a long window loses
    none. Runs only in a traced run."""

    def __init__(self, tracer, period: float = 0.2):
        self._tracer = tracer
        self._period = period
        self._seen: Dict[int, object] = {}
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def _poll(self):
        for s in self._tracer.finished():
            self._seen.setdefault(s.span_id, s)

    def _loop(self):
        while not self._stop.wait(self._period):
            self._poll()

    def __enter__(self):
        self._tracer.clear()
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=5.0)
        self._poll()

    def spans(self):
        return sorted(self._seen.values(), key=lambda s: s.ts_us)


class GcPauses:
    """Times the collector's passes while installed, so a host stall in the
    window can be told apart from one of the collector."""

    def __init__(self):
        self.pauses: List[float] = []
        self._t0 = 0.0

    def _callback(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append(time.perf_counter() - self._t0)

    def __enter__(self):
        gc.callbacks.append(self._callback)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._callback)

    def summary(self) -> str:
        p = self.pauses or [0.0]
        return (f"collections={len(self.pauses)} total_ms={1e3 * sum(p):.1f} "
                f"max_ms={1e3 * max(p):.1f}")


def _delta(before: Dict[str, float], after: Dict[str, float]):
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def _warm_bm25(index, cfg_index, h: int, budget: int) -> int:
    """Compile BM25 scoring for every postings bucket the program pads to
    (256, 512, ... budget) at the query-batch bucket it pads to (8)."""
    from repro.core import bm25
    term = int(np.argmax(np.diff(index.term_ptr)))
    p, n = 256, 0
    while p <= budget:
        bm25.retrieve_many(cfg_index, [[term]], h, budget=p)
        p *= 2
        n += 1
    return n


@dataclasses.dataclass
class Setup:
    """A served stack ready for its window, and what set-up made."""
    corpus: object
    index: object
    server: object
    pool: object
    queries: C.QueryStream        # warm-up queries first, then timed ones
    n_warm: int
    key_bits: int
    model: Dict
    family: types.ModuleType
    devices: List
    setup_s: float

    def text(self, j: int) -> str:
        """Timed query j."""
        return self.queries.text(self.n_warm + j)

    def words(self, j: int) -> np.ndarray:
        return self.queries.words(self.n_warm + j)


def timed_queries(cell: Cell, seconds: float) -> int:
    """Queries made at set-up for the window: an open loop's every arrival;
    a closed loop's first round (the rest are made as it draws them)."""
    t = cell.traffic
    if t["loop"] == "open":
        return max(int(round(t["rate_qps"] * seconds)), 1)
    return int(t["batch"]) * int(t["connections"])


def setup(cell: Cell, seed: int, n_timed: int, require_chip: bool = True,
          t_start: Optional[float] = None) -> Setup:
    """Everything before the window: corpus, index, queries, weights,
    served stack, compilation and warm-up, timed by phase."""
    t_start = time.time() if t_start is None else t_start
    phases: Dict[str, float] = {}
    mark = [time.time()]

    def phase(name):
        now = time.time()
        phases[name] = now - mark[0]
        mark[0] = now

    import jax
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        raise NoChip(f"cell {cell.name} needs {cell.chips} TPU chip(s); "
                     f"JAX found {len(devices)} {devices[0].platform} "
                     f"device(s)")
    jax.block_until_ready(jax.numpy.zeros(8) + 1)
    phase("jax_init")

    from repro.core.bm25 import BM25Index
    from repro.data.tokenizer import HashingTokenizer

    config, traffic = cell.config, cell.traffic
    m = dict(config["model"])
    family = families.load(cell.root, config)
    cfg = family.program_config(config)
    pipe = config["pipeline"]
    corpus = C.generate(config["corpus"], m["vocab_size"], seed)
    idf = corpus.idf
    phase("corpus")
    idx = C.build_index(corpus, m["vocab_size"])
    prog_index = BM25Index(idx.term_ptr, idx.post_docs, idx.post_tf, idx.idf,
                           idx.doc_len, idx.avg_dl, idx.n_docs)
    phase("index")

    batch = int(traffic.get("batch", 1))
    n_warm = int(traffic["warm_requests"]) * batch
    qs = C.QueryStream(corpus, config["queries"], seed)
    qs.ensure(n_warm + n_timed)
    warm_q = qs.texts[:n_warm]
    counts = np.asarray([C.postings_count(idx, corpus.term_of_word[w])
                         for w in qs.word_ids[n_warm:]])
    over = float(np.mean(counts > pipe["postings_budget"]))
    phase("queries")

    key_bits = C.jax_key_bits(seed)
    init = jax.jit(lambda k: family.init_params(k, cfg))
    params = jax.block_until_ready(init(jax.random.PRNGKey(key_bits)))
    phase("weights")

    from bench import stack
    tok = HashingTokenizer(m["vocab_size"])
    world = types.SimpleNamespace(idf=idf, documents=C.Documents(corpus))
    server, pool = stack.build(config, cfg, params, world, tok, prog_index)
    phase("compile")
    try:
        n_bm25 = _warm_bm25(idx, prog_index, pipe["retrieve_h"],
                            pipe["postings_budget"])
        family.warm(pool, cfg, config["serving"]["buckets"])
        with _connector(server.address)() as client:
            for i in range(0, len(warm_q), batch):
                if traffic["loop"] == "open":
                    client.rank(warm_q[i])
                else:
                    client.rank_batch(warm_q[i:i + batch])
    except BaseException:
        server.stop()
        pool.stop()
        raise
    phase("warmup")
    setup_s = time.time() - t_start
    log("# setup by phase (s): " + " ".join(
        f"{k}={v:.3f}" for k, v in phases.items())
        + f" total={setup_s:.3f} bm25_shapes={n_bm25}")
    log(f"# corpus: docs={corpus.n_docs} sentences="
        f"{len(corpus.sent_ptr) - 1} tokens={len(corpus.tokens)} "
        f"postings={len(idx.post_docs)} queries={len(qs) - n_warm} "
        f"postings_over_budget_share={over:.4f}")
    return Setup(corpus, idx, server, pool, qs, n_warm, key_bits, m,
                 family, devices, setup_s)


def drive(cell: Cell, st: Setup, seconds: float, first: int = 0,
          rate: Optional[float] = None) -> Dict:
    """The cell's traffic against the stack for ``seconds``, from timed
    query ``first`` on."""
    traffic = cell.traffic
    connect = _connector(st.server.address)
    if traffic["loop"] == "open":
        # The arrival pattern is part of the mix (its own seed); the run's
        # seed draws the corpus, the queries and the weights.
        rate = float(rate or traffic["rate_qps"])
        n = max(int(round(rate * seconds)), 1)
        texts = [st.text(first + j) for j in range(n)]
        return loadgen.open_loop(
            connect, texts, rate, seconds, int(traffic["connections"]),
            C.seeded(int(traffic["schedule_seed"]), 3), DRAIN_S)
    return loadgen.closed_loop(connect, st.text, int(traffic["connections"]),
                               int(traffic["batch"]), seconds, DRAIN_S)


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        require_chip: bool = True, t_start: Optional[float] = None) -> Dict:
    """One run; returns the result line's object."""
    st = setup(cell, seed, timed_queries(cell, seconds), require_chip,
               t_start)
    import jax
    from repro.serving import telemetry
    devices = st.devices
    kind = devices[0].device_kind
    tracer = telemetry.get_tracer()
    registry = telemetry.get_registry()
    trace_out, spans = None, []
    try:
        reg0 = registry.snapshot()
        with contextlib.ExitStack() as keep:
            log_dir = (keep.enter_context(tempfile.TemporaryDirectory(
                prefix="bench-trace-")) if trace else None)
            with contextlib.ExitStack() as tracing:
                if trace:
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    opts.host_tracer_level = 1
                    jax.profiler.start_trace(log_dir, profiler_options=opts)
                    tracing.callback(jax.profiler.stop_trace)
                    collector = tracing.enter_context(SpanCollector(tracer))
                with jax.profiler.TraceAnnotation(devtrace.MARKER), \
                        CompileCounter() as compiles, GcPauses() as pauses:
                    t_drive = time.perf_counter()
                    marker_epoch_us = telemetry.perf_to_epoch_us(t_drive)
                    out = drive(cell, st, seconds)
                    # the window runs on to the last reply to a request
                    # sent in it, so its spans and device time agree
                    window_s = time.perf_counter() - t_drive
                reg1 = registry.snapshot()
            if trace:
                spans = [s for s in collector.spans()
                         if s.ts_us >= marker_epoch_us]
                trace_out = _reduce_trace(devtrace.load(log_dir),
                                          marker_epoch_us, window_s, spans)
        log(f"# programs compiled in the window: {compiles.count}")
        log(f"# garbage collector in the window: {pauses.summary()}")
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices[:cell.chips])
    finally:
        st.server.stop()
        st.pool.stop()
    st.server = st.pool = None

    reqs = out["requests"]
    loop = cell.traffic["loop"]
    if loop == "open":
        attempted = len(reqs)
        failed = sum(not r.ok for r in reqs)
        lat = loadgen.latency_ms(reqs)
        e2e = {"p50_ms": float(np.percentile(lat, 50)),
               "p95_ms": float(np.percentile(lat, 95))}
        log("# generator: " + " ".join(
            f"{k}={v:.3f}" for k, v in loadgen.lateness_ms(reqs).items()))
    else:
        attempted = sum(len(r.queries) for r in reqs)
        failed = sum(len(r.queries) for r in reqs if not r.ok)
        e2e = {"qps": loadgen.closed_rate(reqs)}
    e2e["setup_s"] = st.setup_s
    log(f"# window: requests={len(reqs)} attempted={attempted} "
        f"failed={failed} " + " ".join(f"{k}={v:.6g}"
                                       for k, v in e2e.items()))
    errors = sorted({r.error for r in reqs if r.error})[:3]
    if errors:
        log(f"# errors: {errors}")

    t_ref = time.time()
    rows = [{"name": "failed", "value": float(failed), "limit": 0.0}]
    rows += _check(cell, st, reqs, seed)
    correct = reference.passes(rows)
    log(f"# reference check: {time.time() - t_ref:.3f} s")

    result = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {},
        "device": {"platform": devices[0].platform, "kind": kind,
                   "count": cell.chips, "memory_peak_bytes": peak},
    }
    if not trace:
        for metric in cell.end_to_end:
            result["metrics"][metric["name"]] = {
                "value": e2e[metric["name"]], "unit": metric["unit"]}
    else:
        result["device"]["busy_s"] = trace_out["busy_s"]
        result["device"]["window_s"] = trace_out["window_s"]
        data = RunData(cell, reqs, window_s, spans, _delta(reg0, reg1),
                       trace_out, kind, st.model)
        for metric in cell.per_layer:
            value = load_reader(cell.root, metric["name"])(data)
            if value is not None:
                result["metrics"][metric["name"]] = {
                    "value": float(value), "unit": metric["unit"]}
        result["breakdown"] = {"device_ops": trace_out["device_ops"],
                               "idle_gaps": trace_out["idle_gaps"]}
    result["compared"] = {r["name"]: {"value": r["value"],
                                      "limit": r["limit"]} for r in rows}
    return result


def _connector(address):
    from repro.core.service import Client
    return lambda: Client(tuple(address))


def _reduce_trace(raw: Dict, marker_epoch_us: float, seconds: float,
                  spans: List) -> Dict:
    if raw["marker"] is None:
        raise RuntimeError("the profiler trace holds no bench.window marker")
    lo = raw["marker"][0]
    hi = lo + seconds * 1e9
    host = [(s.name, s.tid,
             float(devtrace.to_profile_clock(s.ts_us, marker_epoch_us, lo)),
             float(devtrace.to_profile_clock(s.ts_us + s.dur_us,
                                             marker_epoch_us, lo)))
            for s in spans]
    return devtrace.reduce(raw, (lo, hi), host)


def _check(cell: Cell, st: Setup, reqs, seed: int) -> List[Dict]:
    """Hold a sample of the served rankings to the reference: drawn from
    the seed among finished requests, with the longest query in it."""
    conf = cell.config["check"]
    done = [(q, r.rankings[j]) for r in reqs if r.ok
            for j, q in enumerate(r.queries)]
    rng = C.seeded(seed, 4)
    n = min(int(conf["queries"]), len(done))
    pick = set(rng.choice(len(done), size=n, replace=False).tolist()) \
        if done else set()
    if done:
        pick.add(max(range(len(done)),
                     key=lambda i: len(st.words(done[i][0]))))
    W = st.family.reference_weights(st.key_bits, st.model)
    result = reference.Check()
    limits = conf["limits"]
    tie = 2.0 * limits["score_gap"]
    for i in sorted(pick):
        q, ranking = done[i]
        reference.check_query(result, st.corpus, st.index,
                              cell.config["pipeline"], st.family, W,
                              st.model, st.words(q), ranking, tie)
    log(f"# checked {result.queries} queries, {result.items} served items: "
        f"retrieval misses {result.retrieval_misses}, rank misses "
        f"{result.rank_misses}")
    return reference.numbers(result, limits)
