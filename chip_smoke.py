"""Smoke run of the sm-cnn ranking cascade on a TPU, at the model's
published widths, through the entry points a user calls.

  python chip_smoke.py              # one chip: every phase below
  python chip_smoke.py --chips 4    # only: 4-replica pool vs 1 replica

Phases, each reporting on its own lines:

  device     platform, device kind and count; anything but a TPU fails
  cache      the persistent compilation cache directory in use
  world      ``build_world`` trains sm-cnn (published config) on the chip;
             the training loss must be finite
  served     ``launch.serve``'s ``build_server`` with --serve-pipeline
             --server threadpool --plan-target remote (admission ->
             micro-batcher -> replica -> scorer), its server on a thread,
             driven over the socket with ``Client.rank`` / ``rank_batch``
  reference  every served ranking vs a ``local`` plan on the float32
             ``numpy`` backend built from the same params, as logits
  backends   the same pairs scored once on eager / jit / aot / pallas /
             artifact vs ``numpy``; the Pallas kernel must be compiled
             (``tpu_custom_call`` in the lowered module), not interpreted

Times are printed as set-up (compilation included) and steady; they are
informative only. Every phase raises on failure, so the process exits
non-zero before the last line, which on success is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import backends as BK  # noqa: E402
from repro.core import ops  # noqa: E402
from repro.core import service as SV  # noqa: E402
from repro.core.plan import PlanContext, plan  # noqa: E402
from repro.data import qa as QA  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import (build_parser, build_server,  # noqa: E402
                                canonical_pipeline)
from repro.launch.world import build_world  # noqa: E402
from repro.models import sm_cnn  # noqa: E402

#: The platform every phase must run on.
REQUIRED_PLATFORM = "tpu"

#: The configuration served: sm-cnn at its published widths.
CONFIG = "sm-cnn"
#: Training steps for the world, and the seed of its weights and corpus.
TRAIN_STEPS = 60
SEED = 0

#: Scores are P(relevant), the sigmoid of the model's logit margin, and
#: the trained world puts most of them near 0 or 1, where the slope
#: p(1 - p) hides a large logit error. So scores are compared as logits,
#: log p - log(1 - p), with p clipped to [SCORE_CLIP, 1 - SCORE_CLIP]:
#: float32 still resolves 1 - p there to 6e-4 of itself.
SCORE_CLIP = 1e-4
#: Logit tolerance against the float32 numpy reference. On the TPU, XLA
#: runs float32 matmuls at its default precision, a single bfloat16 pass
#: (8 mantissa bits per operand). On a v5e at published widths the served
#: path sat 0.023 from the reference and every backend within 0.019; 0.1
#: gives that four times over. Shifting every logit by 0.3 fails the
#: check, and so do weights of another seed (5.0) or a zeroed question
#: conv arm (2.7). The CPU tests keep their own 1e-5 on scores
#: (``verify_plans``), and the served precision stays as it is.
LOGIT_ATOL = 0.1

BACKENDS = ("eager", "jit", "aot", "pallas", "artifact")


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def fail(phase: str, msg: str) -> SystemExit:
    return SystemExit(f"[{phase}] FAILED: {msg}")


def device_phase(chips: int):
    devices = jax.devices()
    d = devices[0]
    say("device", f"platform={d.platform} device_kind={d.device_kind!r} "
                  f"count={len(devices)}")
    if d.platform != REQUIRED_PLATFORM:
        raise fail("device", f"platform {d.platform!r}, need "
                             f"{REQUIRED_PLATFORM!r}")
    if len(devices) < chips:
        raise fail("device", f"{len(devices)} devices, need {chips}")
    return devices


def world_phase():
    t0 = time.perf_counter()
    world = build_world(TRAIN_STEPS, seed=SEED, cfg=get_config(CONFIG))
    setup_s = time.perf_counter() - t0
    cfg, params, corpus, tok, _, eval_pairs = world
    placed = {str(dev) for leaf in jax.tree.leaves(params)
              for dev in leaf.devices()}
    n_params = sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree.leaves(params))
    loss, metrics = sm_cnn.loss_fn(
        params, QA.make_batch(corpus, tok, cfg.max_len, eval_pairs), cfg)
    say("world", f"config={cfg.name} vocab={cfg.vocab_size} "
                 f"d={cfg.embed_dim} F={cfg.conv_filters} "
                 f"width={cfg.filter_width} hidden={cfg.n_hidden} "
                 f"max_len={cfg.max_len} params={n_params} "
                 f"trained {TRAIN_STEPS} steps on {sorted(placed)} "
                 f"eval_loss={float(loss)!r} eval_acc={float(metrics['acc'])!r} "
                 f"setup_s={setup_s:.3f}")
    if not np.isfinite(float(loss)):
        raise fail("world", f"eval loss {float(loss)}")
    return world


def queries_of(corpus):
    """16 single queries, then 3 batches of 8."""
    qs = corpus.questions
    return qs[:16], [qs[16 + 8 * i:24 + 8 * i] for i in range(3)]


def serve_and_drive(world, replicas: int, policy: str):
    """Stand up launch.serve's pipeline server on a thread, drive it over
    the socket, and return (rankings by query, {replica: (device, rows
    scored)})."""
    cfg, params, corpus, tok, index, _ = world
    sargs = build_parser().parse_args([
        "--config", CONFIG, "--serve-pipeline",
        "--server", "threadpool", "--plan-target", "remote",
        "--replicas", str(replicas), "--policy", policy])
    t0 = time.perf_counter()
    srv, pool = build_server(sargs, cfg, params, corpus, tok, index=index)
    setup_s = time.perf_counter() - t0
    singles, batches = queries_of(corpus)
    rankings = {}
    srv.start_background()
    try:
        with SV.Client(srv.address) as client:
            t0 = time.perf_counter()
            for q in singles:
                rankings[q] = client.rank(q)
            for batch in batches:
                for q, r in zip(batch, client.rank_batch(batch)):
                    rankings[q] = r
            steady_s = time.perf_counter() - t0
        server_stats = srv.stats()
        pool_stats = pool.stats()
        devices = [rep.batcher.scorer.device for rep in pool.replicas]
    finally:
        srv.stop()
        pool.stop()
    n_req = len(singles) + len(batches)
    empty = [q for q, r in rankings.items() if not r]
    sheds = server_stats.get("shed_total", 0.0) + sum(
        v for k, v in pool_stats.items() if k.endswith("_rows_shed"))
    say("served", f"backend={sargs.backend} replicas={replicas} "
                  f"policy={policy} requests={n_req} "
                  f"queries={len(rankings)} empty={len(empty)} "
                  f"sheds={sheds:g} setup_s={setup_s:.3f} "
                  f"steady_s={steady_s:.3f} "
                  f"steady_ms_per_request={steady_s / n_req * 1e3:.3f}")
    if empty or sheds:
        raise fail("served", f"{len(empty)} empty rankings, {sheds:g} sheds")
    rows = {f"replica{i}": (devices[i],
                            pool_stats[f"replica{i}_rows_scored"])
            for i in range(replicas)}
    return rankings, rows


def logit(p):
    """log p - log(1 - p), with p clipped to [SCORE_CLIP, 1 - SCORE_CLIP]."""
    p = np.clip(np.asarray(p, np.float64), SCORE_CLIP, 1 - SCORE_CLIP)
    return np.log(p) - np.log1p(-p)


def check_rankings(phase, got, want, k: int, atol: float):
    """Hold each ranking in ``got`` to the untruncated reference ``want``
    (both query -> [(doc, sent, score)], best first): the same length,
    every score's logit within ``atol`` of the reference's for the same
    candidate, and at every rank a candidate whose reference logit is
    within ``atol`` of the reference's own — so only near-ties may swap,
    also across the top-``k`` cut. Returns (identical, max |d score|,
    max |d logit|)."""
    identical, max_dp, max_dl = 0, 0.0, 0.0
    for q, items in got.items():
        ref = {(doc, sent): score for doc, sent, score in want[q]}
        top = want[q][:k]
        if len(items) != len(top) or not items:
            raise fail(phase, f"{q!r}: {len(items)} ranked, reference "
                              f"{len(top)}")
        for rank, ((doc, sent, score), (_, _, top_score)) in enumerate(
                zip(items, top)):
            if (doc, sent) not in ref:
                raise fail(phase, f"{q!r} rank {rank}: ({doc}, {sent}) is "
                                  f"not a reference candidate")
            r = ref[(doc, sent)]
            dl = float(abs(logit(score) - logit(r)))
            gap = float(abs(logit(r) - logit(top_score)))
            max_dp = max(max_dp, abs(score - r))
            max_dl = max(max_dl, dl)
            if dl > atol or gap > atol:
                raise fail(phase, f"{q!r} rank {rank}: ({doc}, {sent}) "
                                  f"score {score:.6f}, reference {r:.6f}; "
                                  f"reference rank holder {top_score:.6f}")
        identical += [i[:2] for i in items] == [t[:2] for t in top]
    return identical, max_dp, max_dl


def reference_rankings(world, queries):
    """The canonical cascade on a local plan over the float32 numpy
    backend, without its final top-k cut, so that a served candidate that
    crossed the cut on a near-tie still has a reference score."""
    cfg, params, corpus, tok, index, _ = world
    ctx = PlanContext.from_world(cfg, params, corpus, tok, index)
    steps = canonical_pipeline("numpy").steps
    rerank = steps[-1]
    pipeline = ops.Pipeline(steps[:-1] + (ops.Rerank("numpy"),))
    with plan(pipeline, "local", ctx) as ref_plan:
        out = ref_plan.run_many(list(queries))
    return {q: [(c.doc_id, c.sent_id, c.score) for c in cands]
            for q, (cands, _) in zip(queries, out)}, rerank.k


def reference_phase(world, rankings):
    want, k = reference_rankings(world, list(rankings))
    identical, max_dp, max_dl = check_rankings("reference", rankings, want,
                                               k, LOGIT_ATOL)
    say("reference", f"{len(rankings)} served rankings match the float32 "
                     f"numpy reference within logit atol={LOGIT_ATOL:g}; "
                     f"identical order={identical}/{len(rankings)} "
                     f"max_abs_dscore={max_dp!r} max_abs_dlogit={max_dl!r}")


def backends_phase(world):
    cfg, params, corpus, tok, _, eval_pairs = world
    b = QA.make_batch(corpus, tok, cfg.max_len, eval_pairs[:64])
    q, a, f = b["q_tok"], b["a_tok"], b["feats"]
    n = len(q)
    ref = BK.make_scorer("numpy", params, cfg, buckets=(64,))(q, a, f)
    for name in BACKENDS:
        t0 = time.perf_counter()
        scorer = BK.make_scorer(name, params, cfg, buckets=(64,))
        got = scorer(q, a, f)
        setup_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        reps = 20
        for _ in range(reps):
            scorer(q, a, f)
        steady_ms = (time.perf_counter() - t0) / reps * 1e3
        dp = float(np.max(np.abs(np.asarray(got, np.float64) - ref)))
        dl = float(np.max(np.abs(logit(got) - logit(ref))))
        say("backends", f"{name}: rows={n} max_abs_dscore={dp!r} "
                        f"max_abs_dlogit={dl!r} setup_s={setup_s:.3f} "
                        f"steady_ms={steady_ms:.3f}")
        if not np.all(np.isfinite(got)) or dl > LOGIT_ATOL:
            raise fail("backends", f"{name} off the numpy reference by "
                                   f"{dl!r} in logit (atol {LOGIT_ATOL:g})")
    lowered = jax.jit(functools.partial(kops.sm_cnn_score, cfg=cfg)).lower(
        params, q[:8], a[:8], f[:8]).as_text()
    compiled = "tpu_custom_call" in lowered
    say("backends", f"pallas: interpret={kops.interpret_default()} "
                    f"tpu_custom_call_in_lowered={compiled}")
    if kops.interpret_default() or not compiled:
        raise fail("backends", "the Pallas kernel is not compiled for the "
                               "chip")


def pool_phase(world, chips: int):
    """``chips`` replicas, one per device, vs one replica, same queries."""
    one, _ = serve_and_drive(world, 1, "round_robin")
    many, rows = serve_and_drive(world, chips, "round_robin")
    for name, (dev, n) in rows.items():
        say("pool", f"{name} on {dev}: rows_scored={n:g}")
    devices = {str(dev) for dev, n in rows.values() if n > 0}
    if len(devices) != chips:
        raise fail("pool", f"rows scored on {len(devices)} of {chips} "
                           f"devices")
    k = max(len(r) for r in one.values())
    identical, max_dp, max_dl = check_rankings("pool", many, one, k,
                                               LOGIT_ATOL)
    say("pool", f"{chips}-replica rankings match the 1-replica rankings "
                f"within logit atol={LOGIT_ATOL:g}; identical order="
                f"{identical}/{len(many)} max_abs_dscore={max_dp!r} "
                f"max_abs_dlogit={max_dl!r}")


def main(argv=None) -> None:
    cache_dir = enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the replica pool across 4 chips "
                         "against a 1-replica pool")
    args = ap.parse_args(argv)

    devices = device_phase(args.chips)
    say("cache", f"compilation cache dir={cache_dir}")
    world = world_phase()
    if args.chips > 1:
        pool_phase(world, args.chips)
    else:
        rankings, _ = serve_and_drive(world, 2, "least_outstanding")
        reference_phase(world, rankings)
        backends_phase(world)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform,
                                             "kind": d.device_kind,
                                             "count": len(devices)}}))


if __name__ == "__main__":
    main()
