"""Per-architecture sharding rules: param specs, optimizer ZeRO sharding,
input/output specs. Pattern-matching on param tree paths keeps the rules in
ONE place; everything else (models, optimizers) stays sharding-agnostic and
the SPMD partitioner propagates interior shardings.

LM      : Megatron-style TP over 'model' (heads / ffn / vocab), batch over
          ('pod','data'); optimizer state additionally ZeRO-sharded over the
          data axes (largest divisible dim) — grads reduce-scatter into the
          opt shards and updated params all-gather back, all emitted by SPMD
          from the in/out sharding contract.
MoE     : experts over 'model' (EP); router replicated; shared expert TP.
GNN     : edges over ALL axes (1D edge partition), nodes replicated,
          partial segment_sum + all-reduce.
RecSys  : embedding tables row-sharded over ALL axes (the tables are the
          model); MLPs replicated; batch over data axes.
TextPair: replicated params, batch over data axes (the model is tiny — the
          paper's serving regime).
"""
from __future__ import annotations

import re
from typing import Any, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.mesh import axis_size, data_axes


def _dp(mesh) -> Tuple[str, ...]:
    return data_axes(mesh)


def _div(n: int, mesh, *axes) -> bool:
    return n % axis_size(mesh, *axes) == 0


# ---------------------------------------------------------------------------
# LM rules (path regex -> spec builder)
# ---------------------------------------------------------------------------

def _lm_fsdp_spec(path: str, shape, mesh) -> P:
    """FSDP: every weight matrix sharded over ALL mesh axes on its largest
    divisible dim; XLA all-gathers each layer's weights inside the scan body
    and reduce-scatters its grads — no per-layer activation collectives, no
    head-divisibility constraints. The dense-LM train strategy for v5e-class
    meshes (cf. MaxText)."""
    if re.search(r"norm", path) or not shape:
        return P(*([None] * len(shape)))
    # vocab tensors shard V over 'model' only, aligned with the logits rule
    # (FSDP-sharding them over all axes forces (B,S,V) gathers at the head)
    if re.search(r"embed$", path):
        return P("model" if shape[0] % axis_size(mesh, "model") == 0 else None,
                 None)
    if re.search(r"lm_head$", path):
        return P(None,
                 "model" if shape[1] % axis_size(mesh, "model") == 0 else None)
    every = tuple(mesh.axis_names)
    n = axis_size(mesh, *every)
    entries = [None] * len(shape)
    best, best_dim = -1, -1
    for i, dim in enumerate(shape):
        if dim % n == 0 and dim > best:
            best, best_dim = dim, i
    if best_dim >= 0:
        entries[best_dim] = every
        return P(*entries)
    # fall back to the data axes only (e.g. dims divisible by 32 not 512)
    dp = _dp(mesh)
    ndp = axis_size(mesh, *dp)
    for i, dim in enumerate(shape):
        if dim % ndp == 0 and dim > best:
            best, best_dim = dim, i
    if best_dim >= 0:
        entries[best_dim] = dp if len(dp) > 1 else dp[0]
    return P(*entries)


def _lm_spec(path: str, shape, mesh) -> P:
    dp = _dp(mesh)
    m = "model"
    rules = [
        (r"embed$", P(m, None)),
        (r"lm_head$", P(None, m)),
        (r"layers/attn/wq$", P(None, None, m)),
        (r"layers/attn/wk$", P(None, None, m) if _div(shape[-1], mesh, m) else P(None, None, None)),
        (r"layers/attn/wv$", P(None, None, m) if _div(shape[-1], mesh, m) else P(None, None, None)),
        (r"layers/attn/wo$", P(None, m, None)),
        (r"layers/attn/(q|k)_norm$", P(None, None)),
        (r"layers/(attn_norm|mlp_norm)$", P(None, None)),
        (r"layers/mlp/w_(gate|up)$", P(None, None, m)),
        (r"layers/mlp/w_down$", P(None, m, None)),
        (r"layers/moe/router$", P(None, None, None)),
        (r"layers/moe/w_(gate|up)$", P(None, m, None, None)),   # (L,E,d,de): EP
        (r"layers/moe/w_down$", P(None, m, None, None)),
        (r"layers/moe/shared/w_(gate|up)$", P(None, None, m)),
        (r"layers/moe/shared/w_down$", P(None, m, None)),
        (r"final_norm$", P(None)),
    ]
    for pat, spec in rules:
        if re.search(pat, path):
            return spec
    return P(*([None] * len(shape)))


def _gnn_spec(path: str, shape, mesh) -> P:
    return P(*([None] * len(shape)))  # GNN MLPs are tiny: replicate


def _recsys_spec(path: str, shape, mesh) -> P:
    every = tuple(mesh.axis_names)
    if re.search(r"(^|/)(emb|lin)$", path) and shape and _div(shape[0], mesh, *every):
        # the big tables: row-shard over the whole mesh
        return P(every, *([None] * (len(shape) - 1)))
    return P(*([None] * len(shape)))


def _textpair_spec(path: str, shape, mesh) -> P:
    return P(*([None] * len(shape)))


_FAMILY_RULES = {
    "lm": _lm_spec,
    "lm_fsdp": _lm_fsdp_spec,
    "gnn": _gnn_spec,
    "recsys": _recsys_spec,
    "textpair": _textpair_spec,
}


def param_specs(params: Any, family: str, mesh) -> Any:
    """Pytree of PartitionSpec matching ``params`` (works on shape structs)."""
    rule = _FAMILY_RULES[family]

    def one(path, leaf):
        name = jax.tree_util.keystr(path, simple=True, separator="/")
        return rule(name, np.shape(leaf), mesh)

    return jax.tree_util.tree_map_with_path(one, params)


def param_shardings(params: Any, family: str, mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(params, family, mesh))


# ---------------------------------------------------------------------------
# optimizer-state sharding: ZeRO over the data axes
# ---------------------------------------------------------------------------

def zero_shard_spec(spec: P, shape, mesh) -> P:
    """Additionally shard the largest yet-unsharded dim over the data axes.
    This is ZeRO-1: master weights + moments live sharded; SPMD emits the
    reduce-scatter (grads -> opt shard) and all-gather (updated params)."""
    dp = _dp(mesh)
    if not dp:
        return spec
    used = set()
    for e in spec:
        for a in (e if isinstance(e, tuple) else (e,)):
            if a is not None:
                used.add(a)
    if used & set(dp):
        return spec  # data axes already consumed by this param's spec
    dp_size = axis_size(mesh, *dp)
    entries = list(spec) + [None] * (len(shape) - len(spec))
    best, best_dim = -1, -1
    for i, (e, n) in enumerate(zip(entries, shape)):
        if e is None and n % dp_size == 0 and n > best:
            best, best_dim = n, i
    if best_dim >= 0:
        entries[best_dim] = dp if len(dp) > 1 else dp[0]
    return P(*entries)


def opt_state_specs(opt_state: Any, params: Any, family: str, mesh) -> Any:
    """Specs for {step, mu, nu, master} (adamw) / {step, vel, master} (sgd):
    moments & master follow the ZeRO-extended param spec."""
    pspecs = param_specs(params, family, mesh)

    def extend(tree):
        return jax.tree.map(
            lambda spec, leaf: zero_shard_spec(spec, np.shape(leaf), mesh),
            pspecs, tree)

    out = {}
    for k, v in opt_state.items():
        if k == "step":
            out[k] = P()
        else:
            out[k] = extend(v)
    return out


# ---------------------------------------------------------------------------
# batch/input specs per family+kind
# ---------------------------------------------------------------------------

def batch_specs(batch: Any, family: str, kind: str, mesh) -> Any:
    dp = _dp(mesh)
    dpa = dp if len(dp) > 1 else dp[0]
    every = tuple(mesh.axis_names)

    if family == "recsys" and kind in ("rec_train", "rec_serve"):
        # recsys MLPs are replicated (tables shard rows over the full mesh),
        # so the batch shards over EVERY axis — pure DP at 256/512-way
        def rec_default(leaf):
            nd = np.ndim(leaf)
            n = np.shape(leaf)[0] if nd else 0
            ax = every if n % axis_size(mesh, *every) == 0 else dpa
            return P(ax, *([None] * (nd - 1))) if nd else P()
        return jax.tree.map(rec_default, batch)

    def default(leaf):
        nd = np.ndim(leaf)
        return P(dpa, *([None] * (nd - 1))) if nd else P()

    if family == "gnn" and kind in ("graph_full", "graph_sampled"):
        # edges over ALL axes, node arrays replicated
        def gnn_rule(path, leaf):
            name = jax.tree_util.keystr(path, simple=True, separator="/")
            nd = np.ndim(leaf)
            if re.search(r"(edges|senders|receivers|edge_mask)$", name):
                return P(every, *([None] * (nd - 1)))
            return P(*([None] * nd))
        return jax.tree_util.tree_map_with_path(gnn_rule, batch)

    if family == "recsys" and kind == "rec_retrieval":
        def rec_rule(path, leaf):
            name = jax.tree_util.keystr(path, simple=True, separator="/")
            nd = np.ndim(leaf)
            if re.search(r"candidates$", name):
                return P(every, *([None] * (nd - 1)))
            return P(*([None] * nd))  # the single query context: replicated
        return jax.tree_util.tree_map_with_path(rec_rule, batch)

    return jax.tree.map(default, batch)


def cache_specs(cache: Any, cfg, mesh) -> Any:
    """KV cache (L, B, S, Hkv, Dh) [+ (L, B, S, Hkv) int8 scales]: batch
    over data axes; SEQUENCE over 'model' (kv heads rarely divide 16) ->
    decode attention becomes flash-decoding-style partial-softmax + small
    all-reduce under SPMD."""
    dp = _dp(mesh)
    dpa = dp if len(dp) > 1 else dp[0]
    def one(leaf):
        shape = np.shape(leaf)
        s = shape[2]
        seq_ax = "model" if s % axis_size(mesh, "model") == 0 else None
        return P(None, dpa, seq_ax, *([None] * (len(shape) - 3)))
    return jax.tree.map(one, cache)


def named(mesh, tree_of_specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_of_specs)
