"""Pallas TPU kernel: EmbeddingBag (fixed-arity bags) — the recsys hot path.

out[b] = sum_l weight[b,l] * table[ids[b,l]]        ids: (B, L) -> (B, d)

TPU adaptation: JAX/XLA has no EmbeddingBag; the jnp reference is
take + segment_sum (two HBM round-trips for the gathered rows). This kernel
fuses gather + weighted reduce: a batch block's ids sit in VMEM, each row is
fetched with a dynamic VMEM load and accumulated on the VPU, and only the
(Bblk, d) bag results are written back. The table rides in (interpret-mode)
VMEM here; on real silicon the same body runs with the table HBM-resident
and rows DMA'd via double-buffering (ids scalar-prefetched), which this
container cannot exercise.

Per-field single-hot lookups (DLRM's 26 fields) are the L=1..n_fields case
with field offsets folded into ids by the wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(ids_ref, w_ref, table_ref, o_ref):
    bblk, l = ids_ref.shape
    d = table_ref.shape[1]

    def one_bag(i, _):
        acc = jnp.zeros((d,), jnp.float32)

        def one_hot_row(j, acc):
            idx = ids_ref[i, j]
            row = table_ref[pl.ds(idx, 1), :][0]
            return acc + row.astype(jnp.float32) * w_ref[i, j]

        acc = jax.lax.fori_loop(0, l, one_hot_row, acc)
        o_ref[i, :] = acc.astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, bblk, one_bag, 0)


def embedding_bag(table: jnp.ndarray, ids: jnp.ndarray,
                  weights: jnp.ndarray | None = None,
                  block_b: int = 8, interpret: bool = False) -> jnp.ndarray:
    """table (V, d); ids (B, L) int32; weights (B, L) or None (=1.0)."""
    b, l = ids.shape
    v, d = table.shape
    if weights is None:
        weights = jnp.ones((b, l), jnp.float32)
    block_b = min(block_b, b)
    assert b % block_b == 0, (b, block_b)

    return pl.pallas_call(
        _kernel,
        grid=(b // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, l), lambda i: (i, 0)),
            pl.BlockSpec((block_b, l), lambda i: (i, 0)),
            pl.BlockSpec((v, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, d), table.dtype),
        interpret=interpret,
    )(ids, weights.astype(jnp.float32), table)
