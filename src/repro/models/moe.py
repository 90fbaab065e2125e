"""Mixture-of-Experts layer with gather-based (einsum-free) dispatch.

Scales from phi3.5-moe (16 experts, top-2) to kimi-k2 (384 experts, top-8,
~1T params).  The classic GShard one-hot dispatch einsum is O(tokens x E x
capacity) in memory/FLOPs -- infeasible at 384 experts x 1M tokens -- so we
dispatch by *index*: top-k routing -> per-expert slot positions via a cumsum
over the routing one-hot (cheap: int32 (t, E)) -> a (groups, E, capacity)
token-index table -> ``take_along_axis`` gather into expert-major buffers ->
grouped batched GEMMs -> scatter-add combine.  All ops are differentiable
(gather/scatter adjoints) and shard cleanly under pjit:

  tokens/groups -> ("pod","data")    experts -> "model" (EP)

Capacity-factor token dropping (overflow slots -> ``mode='drop'``) follows
Switch/GShard semantics; the aux load-balancing loss is returned to the
caller.  DeepSeek/Kimi-style shared experts run densely alongside.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.parallel import constrain

from . import layers as L
from .config import ModelConfig


def moe_init(key, cfg: ModelConfig) -> dict:
    d, E, ff = cfg.d_model, cfg.n_experts, cfg.expert_ff
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    p = {
        "router": (jax.random.normal(ks[0], (d, E), jnp.float32) * d**-0.5),
        "experts": {
            "w_gate": L._dense_init(ks[1], (E, d, ff), dt, d),
            "w_up": L._dense_init(ks[2], (E, d, ff), dt, d),
            "w_down": L._dense_init(ks[3], (E, ff, d), dt, ff),
        },
    }
    if cfg.n_shared_experts:
        p["shared_mlp"] = L.mlp_init(ks[4], cfg, d, ff * cfg.n_shared_experts)
    return p


def _capacity(t: int, k: int, E: int, factor: float) -> int:
    return max(k, int(t * k * factor / E) + 1)


def apply_moe(p: dict, x: jax.Array, cfg: ModelConfig) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (y, aux_loss).  Groups = batch rows (data-sharded)."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    cap = _capacity(S, k, E, cfg.capacity_factor)

    # ---- routing (f32) ----
    logits = jnp.einsum("gtd,de->gte", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate, ids = jax.lax.top_k(probs, k)                      # (G, t, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)      # renormalize

    # aux load-balance loss (Switch eq. 4): E * sum_e f_e * P_e
    me = jnp.mean(jax.nn.one_hot(ids, E, dtype=jnp.float32), axis=(1, 2))  # (G, E)
    pe = jnp.mean(probs, axis=1)                                           # (G, E)
    aux = E * jnp.mean(jnp.sum(me * pe, axis=-1))

    # ---- slot assignment: position of each (t, k) within its expert ----
    flat_ids = ids.reshape(B, S * k)                         # (G, N)
    oh = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)        # (G, N, E)
    pos_all = jnp.cumsum(oh, axis=1) - oh                    # rank within expert
    position = jnp.sum(pos_all * oh, axis=-1)                # (G, N)

    # ---- build (G, E, cap) token-index table (sentinel = S) ----
    g_idx = jnp.arange(B)[:, None]
    token_idx = jnp.broadcast_to(jnp.arange(S)[:, None], (S, k)).reshape(S * k)
    table = jnp.full((B, E, cap), S, dtype=jnp.int32)
    table = table.at[g_idx, flat_ids, position].set(
        jnp.broadcast_to(token_idx, (B, S * k)), mode="drop"
    )
    gates_tbl = jnp.zeros((B, E, cap), dtype=jnp.float32)
    gates_tbl = gates_tbl.at[g_idx, flat_ids, position].set(
        gate.reshape(B, S * k), mode="drop"
    )

    # ---- gather -> expert-major compute -> gather-back combine ----
    # Both directions are GATHERS (take_along_axis): XLA shards gathers
    # over the batch dim cleanly, whereas the scatter-add combine was
    # SPMD-replicated into a (B, S, d) fp32 buffer (16 GiB/dev observed).
    slot_valid = table < S                                   # (B, E, cap)
    xe = jnp.take_along_axis(
        x, jnp.clip(table, 0, S - 1).reshape(B, E * cap, 1), axis=1
    ).reshape(B, E, cap, d)
    xe = jnp.where(slot_valid[..., None], xe, jnp.zeros((), xe.dtype))
    xe = constrain(xe, "batch", "model", None, None)

    we = p["experts"]
    h_gate = jnp.einsum("gecd,edf->gecf", xe, we["w_gate"])
    h_up = jnp.einsum("gecd,edf->gecf", xe, we["w_up"])
    act = jax.nn.silu(h_gate) if cfg.mlp == "swiglu" else jax.nn.gelu(h_gate)
    ye = jnp.einsum("gecf,efd->gecd", act * h_up, we["w_down"])
    ye = ye * gates_tbl[..., None].astype(ye.dtype)
    ye = constrain(ye, "batch", "model", None, None)

    # combine: token (s, k) reads its slot (flat_ids, position) back.
    # When experts are TP-sharded this gather spans the sharded E axis,
    # which auto-SPMD lowers as a full fp32 all-gather of ye (14 TB/dev at
    # kimi scale) -- so the sharded case runs an explicit partial-combine:
    # each rank gathers only its local experts' slots and the partials are
    # psum'd over "model" (one (B,S,d) all-reduce per layer, EP-style).
    tok_valid = position < cap                               # (B, N)
    y = _combine(ye, flat_ids, position, tok_valid, S, k, cap)
    y = constrain(y, "batch", None, None)

    if cfg.n_shared_experts:
        y = y + L.apply_mlp(p["shared_mlp"], x, cfg)
    return y.astype(x.dtype), aux


def _combine_local(ye_flat, flat_ids, position, tok_valid, S, k, cap,
                   e_lo, e_local):
    """Gather-back combine against a (B, e_local*cap, d) slot buffer."""
    B, _, d = ye_flat.shape
    in_range = (flat_ids >= e_lo) & (flat_ids < e_lo + e_local)
    valid = tok_valid & in_range
    slot = jnp.where(valid, (flat_ids - e_lo) * cap + position, 0)
    y_tok = jnp.take_along_axis(ye_flat, slot[..., None], axis=1)
    y_tok = jnp.where(valid[..., None], y_tok, jnp.zeros((), y_tok.dtype))
    return y_tok.reshape(B, S, k, d).sum(axis=2)


def _combine(ye, flat_ids, position, tok_valid, S, k, cap):
    from jax.sharding import PartitionSpec as P

    from repro.parallel import axis_size

    B, E, _, d = ye.shape
    tp = axis_size("model")
    if tp <= 1 or E % tp != 0:
        return _combine_local(ye.reshape(B, E * cap, d), flat_ids, position,
                              tok_valid, S, k, cap, 0, E)

    mesh = jax.sharding.get_abstract_mesh()
    bat = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    b_entry = bat if (bat and B % _prod(mesh, bat) == 0) else None
    e_local = E // tp

    def local(ye_l, fids, pos, tv):
        e_lo = jax.lax.axis_index("model") * e_local
        part = _combine_local(
            ye_l.reshape(ye_l.shape[0], e_local * cap, d),
            fids, pos, tv, S, k, cap, e_lo, e_local)
        return jax.lax.psum(part, "model")

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(b_entry, "model", None, None), P(b_entry, None),
                  P(b_entry, None), P(b_entry, None)),
        out_specs=P(b_entry, None, None),
        check_vma=False,
    )(ye, flat_ids, position, tok_valid)


def _prod(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def moe_ref_dense(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Oracle: compute every expert densely, combine by renormalized top-k
    gates (no capacity dropping).  Used by tests on small shapes."""
    B, S, d = x.shape
    logits = jnp.einsum("gtd,de->gte", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate, ids = jax.lax.top_k(probs, cfg.top_k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    we = p["experts"]
    hg = jnp.einsum("btd,edf->btef", x, we["w_gate"])
    hu = jnp.einsum("btd,edf->btef", x, we["w_up"])
    act = jax.nn.silu(hg) if cfg.mlp == "swiglu" else jax.nn.gelu(hg)
    ye = jnp.einsum("btef,efd->bted", act * hu, we["w_down"])  # (B,S,E,d)
    mask = jax.nn.one_hot(ids, cfg.n_experts, dtype=jnp.float32)  # (B,S,k,E)
    w_e = jnp.einsum("bske,bsk->bse", mask, gate)
    y = jnp.einsum("bsed,bse->bsd", ye.astype(jnp.float32), w_e).astype(x.dtype)
    if cfg.n_shared_experts:
        y = y + L.apply_mlp(p["shared_mlp"], x, cfg)
    return y
