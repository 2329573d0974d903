"""Integration backends — the paper's three strategies, TPU/JAX-native.

  eager  : op-by-op dispatch (no jit)           ~ PyTorch eager feedforward
  jit    : jax.jit, weights as runtime args      ~ framework-optimized serving
  aot    : weights frozen as XLA constants,      ~ 'compile the network into
           AOT .lower().compile() per shape        a C++ binary'
  numpy  : export -> pure-NumPy evaluator        ~ Deeplearning4J import
  pallas : jit + fused Pallas conv kernel        ~ hand-optimized Blaze/BLAS
  artifact: serialized jax.export StableHLO      ~ the shipped single binary

All backends expose ``score(q_tok, a_tok, feats) -> np.ndarray`` with
identical semantics (bit-comparable within dtype), so Table 1/2 benchmarks
measure integration overhead, not model differences.
"""
from __future__ import annotations

import functools
import time
from typing import Callable, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from repro.configs.base import TextPairConfig
from repro.core import compiled_artifact, export as export_lib, numpy_eval
from repro.models import sm_cnn
from repro.serving import telemetry

BACKENDS = ("eager", "jit", "aot", "numpy", "pallas", "artifact")


def _bucket(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


class Scorer:
    """Uniform scoring interface over any integration backend."""

    def __init__(self, fn: Callable, buckets: Sequence[int], name: str,
                 params=None):
        self._fn = fn
        self._buckets = tuple(buckets)
        self.name = name
        #: The params as placed for the device backends (None for numpy,
        #: which evaluates its own exported copy on the host).
        self.params = params

    @property
    def device(self):
        """The jax device this scorer computes on (None for numpy)."""
        if self.params is None:
            return None
        leaf = jax.tree.leaves(self.params)[0]
        return next(iter(leaf.devices()))

    def __call__(self, q_tok, a_tok, feats) -> np.ndarray:
        n = q_tok.shape[0]
        cap = self._buckets[-1]
        if n > cap:  # coalesced cross-query batches: chunk to the top bucket
            return np.concatenate(
                [self(q_tok[i:i + cap], a_tok[i:i + cap], feats[i:i + cap])
                 for i in range(0, n, cap)])
        b = _bucket(n, self._buckets)
        if b != n:  # pad to bucket so jit/aot hit their compiled entry
            pad = b - n
            q_tok = np.concatenate([q_tok, np.zeros((pad,) + q_tok.shape[1:], q_tok.dtype)])
            a_tok = np.concatenate([a_tok, np.zeros((pad,) + a_tok.shape[1:], a_tok.dtype)])
            feats = np.concatenate([feats, np.zeros((pad,) + feats.shape[1:], feats.dtype)])
        tracer = telemetry.get_tracer()
        # Only open a kernel-side span when this call is already inside a
        # request trace (e.g. the batcher adopted the batch's context);
        # untraced benchmark loops should not flood the ring with roots.
        if tracer.current_context() is not None:
            with tracer.span("scorer", backend=self.name, rows=n, bucket=b):
                t0 = time.perf_counter()
                out = np.asarray(self._fn(q_tok, a_tok, feats))
                dt_ms = (time.perf_counter() - t0) * 1e3
        else:
            t0 = time.perf_counter()
            out = np.asarray(self._fn(q_tok, a_tok, feats))
            dt_ms = (time.perf_counter() - t0) * 1e3
        telemetry.get_registry().observe("scorer_batch_ms", dt_ms,
                                         backend=self.name, bucket=b)
        return out[:n]


def make_scorer(backend: str, params: Dict, cfg: TextPairConfig,
                buckets: Sequence[int] = (1, 8, 64, 256),
                device=None) -> Scorer:
    """A ``Scorer`` for ``backend``. ``device`` (a ``jax.Device``) pins its
    params, compiled entries and inputs to that device; None keeps JAX's
    default device."""
    if backend == "numpy":
        blob = export_lib.dumps(params, model=cfg.name,
                                meta={"filter_width": cfg.filter_width})
        ev = numpy_eval.NumpySMCNN.from_bytes(blob)
        return Scorer(lambda q, a, f: ev.get_score(np.asarray(q), np.asarray(a),
                                                   np.asarray(f)), buckets, backend)

    params = jax.device_put(params, device)

    def put(q, a, f):
        return (jax.device_put(np.asarray(q, np.int32), device),
                jax.device_put(np.asarray(a, np.int32), device),
                jax.device_put(np.asarray(f, np.float32), device))

    def specs(b):
        sharding = None if device is None else SingleDeviceSharding(device)
        return (jax.ShapeDtypeStruct((b, cfg.max_len), jnp.int32,
                                     sharding=sharding),
                jax.ShapeDtypeStruct((b, cfg.max_len), jnp.int32,
                                     sharding=sharding),
                jax.ShapeDtypeStruct((b, cfg.n_extra_feats), jnp.float32,
                                     sharding=sharding))

    if backend == "eager":
        # block_until_ready via np.asarray in Scorer
        return Scorer(lambda q, a, f: sm_cnn.score(params, *put(q, a, f),
                                                   cfg=cfg),
                      buckets, backend, params)

    if backend == "jit":
        jfn = jax.jit(functools.partial(sm_cnn.score, cfg=cfg))
        return Scorer(lambda q, a, f: jfn(params, *put(q, a, f)),
                      buckets, backend, params)

    if backend == "aot":
        # weights closed over as constants; shape-specialized AOT compiles
        base = jax.jit(lambda q, a, f: sm_cnn.score(params, q, a, f, cfg))
        compiled: Dict[int, Callable] = {
            b: base.lower(*specs(b)).compile() for b in buckets}
        return Scorer(lambda q, a, f: compiled[q.shape[0]](*put(q, a, f)),
                      buckets, backend, params)

    if backend == "pallas":
        from repro.kernels import ops as kops
        jfn = jax.jit(functools.partial(kops.sm_cnn_score, cfg=cfg))
        return Scorer(lambda q, a, f: jfn(params, *put(q, a, f)),
                      buckets, backend, params)

    if backend == "artifact":
        blob = compiled_artifact.build_artifact(
            lambda q, a, f: sm_cnn.score(params, q, a, f, cfg),
            {f"b{b}": specs(b) for b in buckets}, meta={"model": cfg.name})
        art = compiled_artifact.CompiledArtifact.from_bytes(blob)
        return Scorer(lambda q, a, f: art.call(f"b{q.shape[0]}",
                                               *put(q, a, f)),
                      buckets, backend, params)

    raise ValueError(f"unknown backend {backend!r}; known: {BACKENDS}")
