"""Sharding rules: DP / FSDP / TP / EP / SP over the ("pod","data","model") mesh.

Parameter sharding is rule-based on parameter-tree path names: every weight
is 2-D sharded -- its TP axis over "model" (heads / ff / experts / vocab) and
its largest remaining axis over "data" (FSDP, ZeRO-3-style; XLA all-gathers
just-in-time at use and the optimizer state inherits the sharding).  The
"pod" axis is pure data parallelism: only gradient all-reduces cross pods.

Activation constraints are applied inside the models via :func:`constrain`,
which degrades gracefully to a no-op when no mesh (or a mesh without the
named axes) is active -- so the same model code runs in single-device smoke
tests and the 512-chip dry-run.

This module also hosts the paper's three-mode parallel strategy analogue for
the Winograd conv path (see ``strategy.py``).
"""

from __future__ import annotations

import re
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def _active_mesh():
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return None
    return mesh


def act_batch_axes(mesh=None) -> tuple[str, ...]:
    """Mesh axes that shard the batch: ("pod", "data") when present."""
    mesh = mesh or _active_mesh()
    if mesh is None:
        return ()
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _clean_spec(spec: P, mesh) -> P | None:
    """Drop axis names missing from the active mesh; None if nothing left."""
    names = set(mesh.axis_names)

    def clean_entry(e):
        if e is None:
            return None
        if isinstance(e, (tuple, list)):
            kept = tuple(a for a in e if a in names)
            return kept if kept else None
        return e if e in names else None

    cleaned = P(*[clean_entry(e) for e in spec])
    if all(e is None for e in cleaned):
        return None
    return cleaned


def axis_size(name: str) -> int:
    """Extent of a mesh axis in the active mesh (1 if absent/no mesh)."""
    mesh = _active_mesh()
    if mesh is None or name not in mesh.axis_names:
        return 1
    return mesh.shape[name]


def _entry_size(mesh, e) -> int:
    if e is None:
        return 1
    if isinstance(e, (tuple, list)):
        n = 1
        for a in e:
            n *= mesh.shape[a]
        return n
    return mesh.shape[e]


def constrain(x: jax.Array, *spec_entries) -> jax.Array:
    """with_sharding_constraint that is a no-op without a mesh context.

    Entries may use the pseudo-axis "batch", which expands to the active
    ("pod", "data") axes.  Assignments whose array dimension is not
    divisible by the mesh-axis extent are dropped (degrade-to-replicate)
    so the same model code serves every (arch x mesh) combination.
    """
    mesh = _active_mesh()
    if mesh is None:
        return x
    expanded = []
    for i, e in enumerate(spec_entries):
        if e == "batch":
            axes = act_batch_axes(mesh)
            e = axes if axes else None
        if e is not None and i < x.ndim:
            names = set(mesh.axis_names)
            if isinstance(e, (tuple, list)):
                e = tuple(a for a in e if a in names) or None
            elif e not in names:
                e = None
            if e is not None and x.shape[i] % _entry_size(mesh, e) != 0:
                if isinstance(e, tuple):
                    e = next((a for a in e if x.shape[i] % mesh.shape[a] == 0),
                             None)
                else:
                    e = None
        expanded.append(e)
    spec = _clean_spec(P(*expanded), mesh)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


# ------------------------- parameter sharding rules -------------------------
#
# Matched in order against the '/'-joined parameter path; first hit wins.
# Axis entries may be "batch" (expands to ("pod","data") -> FSDP over both)
# or "fsdp" (expands to "data" only -- pod axis kept pure-DP for params so
# cross-pod traffic stays gradient-only).

PARAM_RULES: list[tuple[str, tuple]] = [
    # tied embedding: vocab-parallel (explicit masked-gather shard_map)
    (r"embed/table_tied", ("model", None)),
    # untied embedding: d over model -> token gather is collective-free
    (r"embed/table", (None, "model")),
    (r"embed/unembed", ("fsdp", "model")),
    # attention
    (r"(attn|self_attn|cross_attn|shared_attn)/wq", ("fsdp", "model", None)),
    (r"(attn|self_attn|cross_attn|shared_attn)/wk", ("fsdp", "model", None)),
    (r"(attn|self_attn|cross_attn|shared_attn)/wv", ("fsdp", "model", None)),
    (r"(attn|self_attn|cross_attn|shared_attn)/wo", ("model", None, "fsdp")),
    # MoE experts: E on model (EP), d on data (FSDP)
    (r"experts/w_gate", ("model", "fsdp", None)),
    (r"experts/w_up", ("model", "fsdp", None)),
    (r"experts/w_down", ("model", None, "fsdp")),
    (r"router", (None, None)),
    # dense MLP: ff on model, d on data
    (r"mlp/w_gate|shared_mlp/w_gate|mlp/w_up|shared_mlp/w_up", ("fsdp", "model")),
    (r"mlp/w_down|shared_mlp/w_down", ("model", "fsdp")),
    # rwkv / mamba big matrices: inner dim on model
    (r"cmix/w_v$", ("model", "fsdp")),          # channel-mix down-proj (ff,d)
    (r"(tmix|cmix|ssm|mamba)/w_(in|xz|r|k|v|g|up)$", ("fsdp", "model")),
    (r"(tmix|cmix|ssm|mamba)/w_(out|down|o)$", ("model", "fsdp")),
    # conv filters (CNN path): K on model
    (r"conv.*/w$", (None, None, None, "model")),
    # everything else (norm scales, small vectors, decays): replicated
]


def _spec_for_path(path: str, ndim: int, shape: tuple[int, ...]) -> P:
    for pat, entries in PARAM_RULES:
        if re.search(pat, path):
            if len(entries) == ndim:
                return P(*entries)
            if len(entries) < ndim:  # stacked-by-layer leading axis
                return P(*([None] * (ndim - len(entries)) + list(entries)))
    return P()


def param_pspecs(params: Any) -> Any:
    """Pytree of PartitionSpec matching ``params`` via PARAM_RULES."""
    flat = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat[0]:
        path_str = "/".join(
            str(getattr(k, "key", getattr(k, "idx", k))) for k in path
        )
        specs.append(_spec_for_path(path_str, jnp.ndim(leaf), jnp.shape(leaf)))
    return jax.tree_util.tree_unflatten(flat[1], specs)


def _expand_param_spec(spec: P, mesh) -> P | None:
    expanded = []
    for e in spec:
        if e == "fsdp":
            expanded.append("data" if "data" in mesh.axis_names else None)
        elif e == "batch":
            axes = act_batch_axes(mesh)
            expanded.append(axes if axes else None)
        else:
            expanded.append(e)
    return _clean_spec(P(*expanded), mesh)


def param_shardings(params: Any, mesh) -> Any:
    """NamedShardings for a param pytree on a concrete mesh."""
    specs = param_pspecs(params)

    def to_sharding(spec):
        cleaned = _expand_param_spec(spec, mesh)
        return NamedSharding(mesh, cleaned if cleaned is not None else P())

    return jax.tree_util.tree_map(to_sharding, specs)


def shard_params(params: Any, mesh) -> Any:
    """Device_put params according to the rules (for real runs)."""
    return jax.device_put(params, param_shardings(params, mesh))
