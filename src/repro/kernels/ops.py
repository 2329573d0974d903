"""jit'd public wrappers for the Pallas kernels.

``interpret=None`` resolves through ``interpret_default()``, the one place
that decides it: kernels run interpreted (bodies executed in Python, for
correctness) only when the default backend is the CPU; on TPU they compile
to Mosaic. Callers that want interpret mode elsewhere pass it explicitly.
``sm_cnn_score`` is the full paper model with both conv arms running
through the fused kernel — the ``pallas`` integration backend.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import TextPairConfig
from repro.kernels.embedding_bag import embedding_bag as _bag_kernel
from repro.kernels.flash_attention import flash_attention as _flash_kernel
from repro.kernels.sm_cnn_conv import conv_tanh_maxpool as _conv_kernel


def interpret_default() -> bool:
    """Whether kernels called with ``interpret=None`` run interpreted."""
    return jax.default_backend() == "cpu"


def conv_tanh_maxpool(x_emb, filters, bias, width: int,
                      interpret: Optional[bool] = None):
    if interpret is None:
        interpret = interpret_default()
    return _conv_kernel(x_emb, filters, bias, width, interpret=interpret)


def embedding_bag(table, ids, weights=None, interpret: Optional[bool] = None):
    if interpret is None:
        interpret = interpret_default()
    return _bag_kernel(table, ids, weights, interpret=interpret)


def flash_attention(q, k, v, block_q: int = 128, block_kv: int = 128,
                    interpret: Optional[bool] = None):
    if interpret is None:
        interpret = interpret_default()
    return _flash_kernel(q, k, v, block_q=block_q, block_kv=block_kv,
                         interpret=interpret)


def sm_cnn_score(params: Dict, q_tok, a_tok, feats, cfg: TextPairConfig,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """P(relevant) with both conv arms on the fused Pallas kernel."""
    if interpret is None:
        interpret = interpret_default()
    emb = params["embed"]
    w = cfg.filter_width
    xq = conv_tanh_maxpool(emb[q_tok], params["conv_q"]["w"],
                           params["conv_q"]["b"], w, interpret=interpret)
    xa = conv_tanh_maxpool(emb[a_tok], params["conv_a"]["w"],
                           params["conv_a"]["b"], w, interpret=interpret)
    xj = jnp.concatenate([xq, xa, feats.astype(xq.dtype)], axis=-1)
    h = jnp.tanh(xj @ params["join"]["w"] + params["join"]["b"])
    logits = h @ params["out"]["w"] + params["out"]["b"]
    return jax.nn.softmax(logits, axis=-1)[:, 1]
