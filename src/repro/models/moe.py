"""Mixture-of-Experts layer with expert parallelism over the 'model' axis.

Two dispatch implementations (config: MoEConfig.dispatch):

* ``scatter`` (baseline): capacity-buffer dispatch expressed with gather /
  scatter under plain pjit; GSPMD inserts the cross-shard data movement.
* ``a2a`` (optimized): explicit expert-local dispatch under shard_map.
  We set out to build the Vertica Send/Recv resegmentation (all_to_all of
  tokens to expert shards) -- and discovered mid-implementation that the
  co-located-join-with-replicated-dimension plan (paper §6.2) is strictly
  cheaper here: TP already replicates activations over 'model', so expert
  dispatch is local and the only collective is the output psum. See
  moe_apply_expert_local and EXPERIMENTS.md §Perf for the measured
  collective-byte reduction.

Capacity policy: tokens beyond ``capacity_factor * N * top_k / E`` per expert
are dropped (standard Switch/GShard semantics); the residual stream carries
them unchanged.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import MoEConfig
from ..distributed.sharding import shard_hint
from .params import ParamDecl


def moe_decls(d: int, moe: MoEConfig) -> Dict[str, Any]:
    e, f = moe.num_experts, moe.d_ff_expert
    return {
        "router": ParamDecl((d, e), ("embed", "experts")),
        "wi_gate": ParamDecl((e, d, f), ("experts", "expert_in", "mlp")),
        "wi_up": ParamDecl((e, d, f), ("experts", "expert_in", "mlp")),
        "wo": ParamDecl((e, f, d), ("experts", "mlp", "expert_in")),
    }


def _route(p, x, moe: MoEConfig):
    """Router: returns (gates (N,k), experts (N,k), aux_loss)."""
    logits = (x @ p["router"].astype(x.dtype)).astype(jnp.float32)  # (N,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, moe.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss
    e = moe.num_experts
    density = jnp.zeros((e,), jnp.float32).at[experts.reshape(-1)].add(
        1.0) / max(1, experts.size)
    mean_prob = probs.mean(axis=0)
    aux = e * jnp.sum(density * mean_prob)
    return gates.astype(x.dtype), experts, aux


def _expert_ffn(p, h):
    """h: (E, C, d) -> (E, C, d); per-expert SwiGLU."""
    g = jnp.einsum("ecd,edf->ecf", h, p["wi_gate"].astype(h.dtype))
    u = jnp.einsum("ecd,edf->ecf", h, p["wi_up"].astype(h.dtype))
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                      p["wo"].astype(h.dtype))


def moe_apply(p, x: jax.Array, moe: MoEConfig, *,
              mesh=None) -> tuple[jax.Array, jax.Array]:
    """x: (B,S,d) -> (out (B,S,d), aux_loss scalar). Dispatch mode from
    MoEConfig; 'a2a' requires an active activation_hints context (mesh)."""
    if moe.dispatch == "a2a":
        from ..distributed.sharding import _HINTS
        ctx = getattr(_HINTS, "ctx", None)
        if ctx is not None:
            return moe_apply_expert_local(p, x, moe, ctx[0], ctx[1])
    return _moe_apply_scatter(p, x, moe)


def _moe_apply_scatter(p, x: jax.Array, moe: MoEConfig
                       ) -> tuple[jax.Array, jax.Array]:
    """x: (B,S,d) -> (out (B,S,d), aux_loss scalar)."""
    B, S, d = x.shape
    n = B * S
    flat = x.reshape(n, d)
    gates, experts, aux = _route(p, flat, moe)

    e = moe.num_experts
    cap = int(np.ceil(n * moe.top_k * moe.capacity_factor / e))
    cap = max(cap, 4)

    # position of each (token, k) within its expert, by arrival order
    flat_e = experts.reshape(-1)                              # (n*k,)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)       # (n*k, E)
    pos_in_e = (jnp.cumsum(onehot, axis=0) - onehot)          # exclusive
    pos = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
    keep = pos < cap

    # dispatch: scatter tokens into the (E, cap, d) buffer
    tok_idx = jnp.arange(n * moe.top_k) // moe.top_k
    buf = jnp.zeros((e, cap, d), flat.dtype)
    buf = buf.at[flat_e, jnp.where(keep, pos, cap - 1)].add(
        jnp.where(keep[:, None], flat[tok_idx], 0))
    buf = shard_hint(buf, "experts", "expert_cap", None)
    buf = _expert_ffn(p, buf)
    buf = shard_hint(buf, "experts", "expert_cap", None)

    # combine: gather expert outputs back and weight by gates
    out_tok = buf[flat_e, jnp.clip(pos, 0, cap - 1)]          # (n*k, d)
    out_tok = jnp.where(keep[:, None], out_tok, 0)
    out = (out_tok * gates.reshape(-1)[:, None]).reshape(n, moe.top_k, d)
    return out.sum(axis=1).reshape(B, S, d), aux * moe.router_aux_coef


# ---------------------------------------------------------------------------
# Expert-local dispatch under shard_map (the §Perf-optimized path)
# ---------------------------------------------------------------------------

def moe_apply_expert_local(p, x: jax.Array, moe: MoEConfig, rules, mesh
                           ) -> tuple[jax.Array, jax.Array]:
    """Explicit expert-parallel dispatch via shard_map.

    Hypothesis history (EXPERIMENTS.md §Perf): we set out to implement the
    Vertica Send/Recv resegmentation (all_to_all of tokens to their expert
    shard). Working it through exposed a cheaper plan the paper itself
    suggests (§6.2 'co-located joins with a replicated dimension'):
    activations are already REPLICATED over the 'model' axis under tensor
    parallelism, so every expert shard already holds every local token --
    dispatch is a purely LOCAL gather, and the only collective is one psum
    of the combined outputs (identical in shape to a dense TP MLP's
    all-reduce). Token->expert movement: zero bytes.

    Layout inside shard_map:
      x       : sharded over ('pod','data') on batch, replicated on 'model'
      router  : replicated (d x E is tiny)
      experts : expert dim sharded over 'model', d dim sharded over 'data'
                (FSDP storage) and all-gathered here, explicitly.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    names = mesh.axis_names
    dp_axes = tuple(a for a in ("pod", "data") if a in names)
    tp = mesh.shape["model"] if "model" in names else 1
    e, k, f = moe.num_experts, moe.top_k, moe.d_ff_expert
    assert e % tp == 0, (e, tp)
    e_loc = e // tp
    B, S, d = x.shape

    def local(router, wig, wiu, wo, x_l):
        n = x_l.shape[0] * x_l.shape[1]
        flat = x_l.reshape(n, d)
        # FSDP: assemble full expert weights from their 'data' shards
        if dp_axes:
            wig = jax.lax.all_gather(wig, "data", axis=1, tiled=True)
            wiu = jax.lax.all_gather(wiu, "data", axis=1, tiled=True)
            wo = jax.lax.all_gather(wo, "data", axis=2, tiled=True)
        logits = (flat @ router.astype(flat.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, experts = jax.lax.top_k(probs, k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
        m_idx = jax.lax.axis_index("model") if tp > 1 else 0
        lo = m_idx * e_loc
        # local capacity dispatch: only (token, k) pairs routed to MY experts
        flat_e = experts.reshape(-1)                       # (n*k,)
        local_e = flat_e - lo
        mine = (local_e >= 0) & (local_e < e_loc)
        local_e = jnp.where(mine, local_e, 0)
        cap = max(4, int(np.ceil(n * k * moe.capacity_factor / e)))
        onehot = jax.nn.one_hot(local_e, e_loc, dtype=jnp.int32) * \
            mine[:, None].astype(jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) - onehot)[
            jnp.arange(n * k), local_e]
        keep = mine & (pos < cap)
        tok_idx = jnp.arange(n * k, dtype=jnp.int32) // k
        # SLOT-INDEXED dispatch (§Perf LM-1 iter 3): scatter 4-byte token
        # indices into slots, then gather token data ONCE at slot
        # granularity -- the naive pair-wise gather+scatter materializes
        # (n*k, d) token copies, ~k/capacity_factor = ~6x more bytes
        # (measured 1.2 TB/dev of phantom traffic on olmoe train_4k).
        n_slots = e_loc * cap
        flat_slot = jnp.where(keep, local_e * cap + pos, n_slots)
        slot_tok = jnp.zeros(n_slots + 1, jnp.int32).at[flat_slot].set(
            tok_idx)
        slot_gate = jnp.zeros(n_slots + 1, jnp.float32).at[flat_slot].set(
            jnp.where(keep, gates.reshape(-1), 0.0))
        slot_live = jnp.zeros(n_slots + 1, jnp.bool_).at[flat_slot].set(
            keep)
        buf = flat[slot_tok[:n_slots]] * slot_live[:n_slots, None].astype(
            flat.dtype)
        buf = buf.reshape(e_loc, cap, d)
        # expert FFN on local experts
        g = jnp.einsum("ecd,edf->ecf", buf, wig.astype(buf.dtype))
        u = jnp.einsum("ecd,edf->ecf", buf, wiu.astype(buf.dtype))
        hid = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                         wo.astype(buf.dtype))
        # combine: gate-weight each slot, scatter-add back to its token
        weighted = hid.reshape(n_slots, d) * \
            slot_gate[:n_slots, None].astype(hid.dtype)
        part = jnp.zeros((n, d), flat.dtype).at[slot_tok[:n_slots]].add(
            jnp.where(slot_live[:n_slots, None], weighted, 0))
        # combine partial expert outputs: ONE all-reduce, same shape as a
        # dense TP MLP's -- zero-byte token movement
        if tp > 1:
            part = jax.lax.psum(part, "model")
        # load-balance aux: the per-DP-shard estimator (density x mean-prob
        # computed over local tokens, then averaged) -- the standard choice
        # under data parallelism; it differs from a global-batch estimator
        # by O(1/shards) sampling noise
        density = jnp.zeros((e,), jnp.float32).at[flat_e].add(
            1.0) / max(1, n * k)
        aux = e * jnp.sum(density * probs.mean(axis=0))
        if dp_axes:
            aux = jax.lax.pmean(aux, dp_axes)
        return part.reshape(x_l.shape), aux

    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(), P("model", "data"), P("model", "data"),
                  P("model", None, "data"), P(dp_axes)),
        out_specs=(P(dp_axes), P()),
        check_vma=False)
    out, aux = fn(p["router"], p["wi_gate"], p["wi_up"], p["wo"], x)
    return out, aux * moe.router_aux_coef
