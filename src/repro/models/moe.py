"""Mixture-of-Experts FFN: shared + fine-grained routed experts (DeepSeekMoE).

Dispatch strategy (chosen for pjit-partitionability, see DESIGN.md §5):
tokens are reshaped into groups (G, S, d) with G sharded over the data axes
and experts sharded over the model axis. Routing builds a fixed-capacity
index buffer (G, E, C) by scatter, experts run as one batched einsum over
(G, E, C, d), and outputs gather back per token. Everything is fixed-shape
(no ragged ops), so SPMD partitioning is closed-form; overflow tokens drop
(capacity_factor bounds the drop rate) and still flow through the shared
experts + residual, per standard practice.

Shared experts: the sum of N parallel SwiGLU experts equals ONE SwiGLU with
hidden width N*d_expert (concatenate hidden units, stack down-proj rows), so
shared experts are fused into a single wide FFN — exact, and one less einsum.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LMConfig, MoESpec
from repro.models.layers import dense_init


def moe_params(key, cfg: LMConfig, dtype) -> Dict:
    spec = cfg.moe
    d, e, de = cfg.d_model, spec.n_routed, spec.d_expert
    k0, k1, k2, k3, k4 = jax.random.split(key, 5)
    std = 1.0 / math.sqrt(d)
    p = {
        "router": (jax.random.normal(k0, (d, e), jnp.float32) * std).astype(jnp.float32),
        "w_gate": (jax.random.normal(k1, (e, d, de), jnp.float32) * std).astype(dtype),
        "w_up": (jax.random.normal(k2, (e, d, de), jnp.float32) * std).astype(dtype),
        "w_down": (jax.random.normal(k3, (e, de, d), jnp.float32) / math.sqrt(de)).astype(dtype),
    }
    if spec.n_shared:
        ds = spec.n_shared * de
        ka, kb, kc = jax.random.split(k4, 3)
        p["shared"] = {
            "w_gate": dense_init(ka, d, ds, dtype),
            "w_up": dense_init(kb, d, ds, dtype),
            "w_down": dense_init(kc, ds, d, dtype),
        }
    return p


def _capacity(spec: MoESpec, s: int) -> int:
    c = int(math.ceil(s * spec.top_k * spec.capacity_factor / spec.n_routed))
    return max(8, ((c + 7) // 8) * 8)


def route(router_w: jnp.ndarray, x: jnp.ndarray, spec: MoESpec
          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Router: returns (weights (G,S,k), expert_idx (G,S,k), aux_loss scalar)."""
    logits = (x.astype(jnp.float32) @ router_w).astype(jnp.float32)  # (G,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, spec.top_k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    # GShard-style load-balance loss: E * mean_e(frac_tokens_e * mean_prob_e)
    e = spec.n_routed
    sel = jax.nn.one_hot(idx[..., 0], e, dtype=jnp.float32)  # top-1 fraction
    aux = e * jnp.mean(jnp.mean(sel, axis=(0, 1)) * jnp.mean(probs, axis=(0, 1)))
    return w, idx, aux


def moe_apply(p: Dict, x: jnp.ndarray, cfg: LMConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (y, aux_loss)."""
    spec = cfg.moe
    b, s0, d = x.shape
    t = b * s0
    sg = min(spec.group_size, t)
    assert t % sg == 0, f"tokens {t} % group {sg} != 0"
    g = t // sg
    e, k = spec.n_routed, spec.top_k
    c = _capacity(spec, sg)

    xg = x.reshape(g, sg, d)
    w, idx, aux = route(p["router"], xg, spec)           # (G,S,k)

    # --- position-in-expert via k sequential one-hot cumsums (fixed shape) ---
    counts = jnp.zeros((g, e), jnp.int32)
    pos_list = []
    for j in range(k):
        oh = jax.nn.one_hot(idx[:, :, j], e, dtype=jnp.int32)      # (G,S,E)
        excl = jnp.cumsum(oh, axis=1) - oh                          # exclusive
        pos_j = jnp.take_along_axis(excl + counts[:, None, :],
                                    idx[:, :, j:j + 1], axis=2)[..., 0]
        pos_list.append(pos_j)
        counts = counts + jnp.sum(oh, axis=1)
    pos = jnp.stack(pos_list, axis=-1)                              # (G,S,k)
    keep = pos < c
    pos_c = jnp.where(keep, pos, c)      # c is out-of-bounds -> scatter drops

    # --- build (G, E, C) token-index buffer by scatter ---
    gi = jnp.arange(g, dtype=jnp.int32)[:, None, None]
    gi = jnp.broadcast_to(gi, (g, sg, k))
    si = jnp.arange(sg, dtype=jnp.int32)[None, :, None]
    si = jnp.broadcast_to(si, (g, sg, k))
    idx_buf = jnp.full((g, e, c), sg, jnp.int32)  # sentinel -> zero pad row
    idx_buf = idx_buf.at[gi, idx, pos_c].set(si, mode="drop")

    # --- dispatch gather ---
    x_pad = jnp.concatenate([xg, jnp.zeros((g, 1, d), xg.dtype)], axis=1)
    dispatched = jax.vmap(lambda xp, ib: xp[ib])(x_pad, idx_buf)    # (G,E,C,d)

    # --- expert FFN (E sharded over model axis) ---
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", dispatched, p["w_gate"]))
    h = h * jnp.einsum("gecd,edf->gecf", dispatched, p["w_up"])
    eo = jnp.einsum("gecf,efd->gecd", h, p["w_down"])               # (G,E,C,d)

    # --- combine gather: each token reads its k slots ---
    def gather_out(eo_g, idx_g, pos_g):                             # per group
        return eo_g[idx_g, jnp.minimum(pos_g, c - 1)]               # (S,k,d)
    outs = jax.vmap(gather_out)(eo, idx, pos_c)                     # (G,S,k,d)
    wk = (w * keep.astype(jnp.float32)).astype(x.dtype)
    y = jnp.einsum("gskd,gsk->gsd", outs, wk)

    # --- shared experts (always-on wide SwiGLU) ---
    if "shared" in p:
        sh = p["shared"]
        y = y + (jax.nn.silu(xg @ sh["w_gate"]) * (xg @ sh["w_up"])) @ sh["w_down"]

    return y.reshape(b, s0, d), aux


# ---------------------------------------------------------------------------
# Expert-parallel MoE with explicit all-to-all (shard_map)
# ---------------------------------------------------------------------------
#
# The pjit gather/scatter formulation above is correct everywhere but its
# combine step materializes a (G, S, k, d) tensor that the SPMD partitioner
# replicates across the model axis (48 GB/device/layer on deepseek-moe-16b x
# train_4k: EXPERIMENTS.md §Perf iteration M1). The production pattern is
# GShard/DeepSpeed-style expert parallelism: tokens are ROUTED to the shard
# owning their expert with one all-to-all, computed locally, and routed back
# with a second all-to-all — per-device volume T_loc*k*cf*d*2 per layer,
# ~200x less than the replicated combine.

def _local_dispatch(x, expert_ids, n_buckets, cap, valid=None):
    """Scatter rows of x (T, d) into (n_buckets, cap, d) by expert_ids,
    first-come-first-served capacity. Rows with valid=False neither occupy
    capacity nor get written. Returns (buffer, slot, kept)."""
    oh = jax.nn.one_hot(expert_ids, n_buckets, dtype=jnp.int32)   # (T, M)
    if valid is not None:
        oh = oh * valid[:, None].astype(jnp.int32)
    pos = (jnp.cumsum(oh, axis=0) - oh)                            # exclusive
    slot = jnp.take_along_axis(pos, expert_ids[:, None], axis=1)[:, 0]
    kept = slot < cap
    if valid is not None:
        kept = kept & valid
    slot_c = jnp.where(kept, slot, cap)          # cap -> dropped by mode=drop
    buf = jnp.zeros((n_buckets, cap, x.shape[1]), x.dtype)
    buf = buf.at[expert_ids, slot_c].set(x, mode="drop")
    return buf, slot_c, kept


def moe_apply_a2a(p: Dict, x: jnp.ndarray, cfg: LMConfig, mesh,
                  axis: str = "model") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B, S, d) -> (y, aux). Must run under ``mesh``; experts sharded over
    ``axis``; x sharded (data-axes, axis, None) [sequence parallel]."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.mesh import data_axes

    spec = cfg.moe
    m_size = mesh.shape[axis]
    assert spec.n_routed % m_size == 0
    e_local = spec.n_routed // m_size
    dp = data_axes(mesh)
    dpa = dp if len(dp) > 1 else dp[0]
    d = cfg.d_model

    def block(router_w, w_gate, w_up, w_down, shared, x_loc):
        # x_loc: (B_loc, S_loc, d); expert weights: (E_local, d, d_e)
        b_loc, s_loc, _ = x_loc.shape
        t = b_loc * s_loc
        xf = x_loc.reshape(t, d)
        # --- route (local tokens, global experts) ---
        logits = xf.astype(jnp.float32) @ router_w                  # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, spec.top_k)                   # (T, k)
        w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)
        sel = jax.nn.one_hot(idx[:, 0], spec.n_routed, dtype=jnp.float32)
        aux_local = spec.n_routed * jnp.mean(
            jnp.mean(sel, axis=0) * jnp.mean(probs, axis=0))
        aux = jax.lax.pmean(jax.lax.pmean(aux_local, axis), dpa)

        # --- dispatch to owner shards ---
        tk = t * spec.top_k
        flat_e = idx.reshape(tk)                                    # expert id
        dest = flat_e // e_local                                    # owner shard
        cap = max(8, int(math.ceil(t * spec.top_k * spec.capacity_factor
                                   / m_size / 8)) * 8)
        x_rep = jnp.repeat(xf, spec.top_k, axis=0)                  # (T*k, d)
        send, slot, kept = _local_dispatch(x_rep, dest, m_size, cap)
        meta = jnp.stack([flat_e % e_local,
                          jnp.where(kept, 1, 0)], axis=1)           # (T*k, 2)
        send_meta, _, _ = _local_dispatch(meta.astype(jnp.int32), dest,
                                          m_size, cap)
        recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0,
                                  tiled=False)                      # (M,cap,d)
        recv_meta = jax.lax.all_to_all(send_meta, axis, split_axis=0,
                                       concat_axis=0, tiled=False)

        # --- local expert compute (second, local dispatch by expert) ---
        rx = recv.reshape(m_size * cap, d)
        re = recv_meta.reshape(m_size * cap, 2)
        eid = jnp.minimum(re[:, 0], e_local - 1)
        rvalid = re[:, 1] > 0
        # received rows are already capacity-bounded per shard; only the
        # *within-shard* expert imbalance needs slack (1.3 -> 1.1 cut the
        # expert-FFN buffer + FLOP waste ~18%: §Perf iteration M2)
        cap2 = max(8, int(math.ceil(m_size * cap * 1.1 / e_local / 8)) * 8)
        ebuf, eslot, ekept = _local_dispatch(rx, eid, e_local, cap2,
                                             valid=rvalid)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ebuf, w_gate))
        h = h * jnp.einsum("ecd,edf->ecf", ebuf, w_up)
        eo = jnp.einsum("ecf,efd->ecd", h, w_down)                  # (E_l,c2,d)
        # gather back into the recv layout; drop invalid + over-capacity
        back = eo[eid, jnp.minimum(eslot, cap2 - 1)]
        back = back * ekept[:, None].astype(back.dtype)
        back = back.reshape(m_size, cap, d)
        ret = jax.lax.all_to_all(back, axis, split_axis=0, concat_axis=0,
                                 tiled=False)                       # (M,cap,d)

        # --- combine: each token reads its k slots from its send buffer ---
        vals = ret[dest, jnp.minimum(slot, cap - 1)]                # (T*k, d)
        vals = vals * kept[:, None].astype(vals.dtype)
        vals = vals.reshape(t, spec.top_k, d)
        y = jnp.einsum("tkd,tk->td", vals, w.astype(vals.dtype))

        if shared is not None:
            y = y + (jax.nn.silu(xf @ shared["w_gate"]) *
                     (xf @ shared["w_up"])) @ shared["w_down"]
        return y.reshape(b_loc, s_loc, d), aux

    shared = p.get("shared")
    in_specs = (P(None, None),                      # router replicated
                P(axis, None, None), P(axis, None, None), P(axis, None, None),
                None if shared is None else
                jax.tree.map(lambda _: P(None, None), shared),
                P(dpa, axis, None))                 # x: batch x seq(SP)
    out_specs = (P(dpa, axis, None), P())
    fn = jax.shard_map(block, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(p["router"], p["w_gate"], p["w_up"], p["w_down"], shared, x)
