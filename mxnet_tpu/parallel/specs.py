"""Sharding rule helpers.

GSPMD sharding annotations replace the reference's per-tensor kvstore traffic
(SURVEY.md §2.5). Parameters can carry explicit specs
(`Parameter.set_sharding`); these helpers fill in the rest.
"""
from __future__ import annotations

import os

import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from .mesh import current_mesh

__all__ = ["param_spec", "batch_spec", "replicated", "fsdp_spec",
           "apply_tp_rules", "constrain_batch", "constrain_seq", "DATA_AXES",
           "attention_axes", "spec_to_tree", "spec_from_tree"]

# both dp and fsdp are "data" axes from the batch's point of view
DATA_AXES = ("dp", "fsdp")


def replicated(mesh=None):
    mesh = mesh or current_mesh()
    return NamedSharding(mesh, PartitionSpec())


def batch_spec(ndim, mesh=None, extra=None):
    """Batch sharded over the data axes on dim 0; rest replicated."""
    mesh = mesh or current_mesh()
    axes = [a for a in DATA_AXES if mesh.shape.get(a, 1) > 1] or list(DATA_AXES)
    spec = [tuple(axes)] + [None] * (ndim - 1)
    if extra:
        for dim, ax in extra.items():
            spec[dim] = ax
    return NamedSharding(mesh, PartitionSpec(*spec))


def attention_axes(mesh, batch, heads):
    """How a (B, H, L, D) attention operand splits over `mesh` inside a
    shard_map: (batch axes or None, head axis or None). Batch goes on the
    data axes — dropped from the right until their product divides B —
    and heads on `tp` when divisible. Shared by the sequence-parallel
    wrapper and the per-device flash-attention kernel call."""
    data = [a for a in DATA_AXES if mesh.shape.get(a, 1) > 1]
    while data and batch % int(np.prod([mesh.shape[a] for a in data])):
        data.pop()
    tp = mesh.shape.get("tp", 1)
    return (tuple(data) if data else None,
            "tp" if (tp > 1 and heads % tp == 0) else None)


# Only shard params with at least this many elements over fsdp (reference:
# MXNET_KVSTORE_BIGARRAY_BOUND — small arrays are not worth distributing).
# Small 1D params (LayerNorm gamma/beta, biases) otherwise force a constant
# stream of GSPMD reshards around their broadcasts/reductions.
# Knob: config 'fsdp_min_size' / MXNET_TPU_FSDP_MIN_SIZE.


def _fsdp_min_size():
    from .. import config
    return config.get("fsdp_min_size")


def fsdp_spec(shape, mesh=None, hint=None):
    """ZeRO-style: shard the largest divisible dim over 'fsdp' (TPU analog of
    the reference's big-array round-robin across PS servers). Arrays smaller
    than FSDP_MIN_SIZE elements stay replicated.

    hint='embedding' (gather tables): replicate. GSPMD cannot partition a
    gather over the indexed dim (vocab-sharded → involuntary full
    rematerialization of the table), and feature-dim sharding forces the
    scatter-grad to reshard batch-sharded (B,L,E) updates onto the feature
    axis — another involuntary-remat pattern. Replication costs a little
    ZeRO memory on one table; explicit tp rules (e.g. BERT's feature-dim
    vocab projection sharding) still apply via set_sharding."""
    mesh = mesh or current_mesh()
    size = mesh.shape.get("fsdp", 1)
    if size <= 1 or not shape:
        return replicated(mesh)
    if hint == "embedding" or int(np.prod(shape)) < _fsdp_min_size():
        return replicated(mesh)
    if len(shape) == 2:
        # (out, in) Dense weights: prefer the contraction (input) dim — the
        # partitioned matmul then psums partial products and activations
        # stay batch-sharded. Output-dim sharding pushes feature shardings
        # onto activations, which GSPMD can only undo next to a gather by
        # involuntary full rematerialization.
        order = [1, 0]
    else:
        order = sorted(range(len(shape)), key=lambda i: -shape[i])
    for dim in order:
        if shape[dim] % size == 0 and shape[dim] >= size:
            spec = [None] * len(shape)
            spec[dim] = "fsdp"
            return NamedSharding(mesh, PartitionSpec(*spec))
    return replicated(mesh)


def constrain_batch(x, mesh=None):
    """Pin an activation (jax array) to batch sharding over the data axes.

    Use after ops whose transpose is a scatter (gather/take_along_axis):
    without the pin, sharding propagation from a downstream fsdp-sharded
    weight can make the scatter's updates feature-sharded, which GSPMD can
    only reach from batch-sharded via involuntary full rematerialization.
    `with_sharding_constraint` transposes to itself, so the pin holds for
    the cotangent too. No-op when no data axis is sharded, or when the
    batch dim isn't divisible by the sharded data-axis product (e.g. eager
    small-batch inference with a big mesh active)."""
    import jax

    from .mesh import _manual
    if _manual:
        return x  # inside shard_map: arrays are per-shard, no constraints
    mesh = mesh or current_mesh()
    sharded = [a for a in DATA_AXES if mesh.shape.get(a, 1) > 1]
    if not sharded:
        return x
    total = int(np.prod([mesh.shape[a] for a in sharded]))
    if x.ndim == 0 or x.shape[0] % total != 0:
        return x
    return jax.lax.with_sharding_constraint(x, batch_spec(x.ndim, mesh))


def constrain_seq(x, mesh=None, seq_dim=1):
    """Pin a (B, L, ...) activation to batch sharding on dim 0 AND `sp`
    sharding on the sequence dim — the anchor that keeps long-context
    activations sequence-sharded between ring-attention shard_maps (without
    it GSPMD may all-gather L after the first elementwise op). Falls back
    to `constrain_batch` when sp is 1 or L does not divide."""
    import jax

    from .mesh import _manual
    if _manual:
        return x
    mesh = mesh or current_mesh()
    sp = mesh.shape.get("sp", 1)
    if sp <= 1 or x.ndim <= seq_dim or x.shape[seq_dim] % sp != 0:
        return constrain_batch(x, mesh)
    sharded = [a for a in DATA_AXES if mesh.shape.get(a, 1) > 1]
    # shard dim 0 over the largest axis subset whose product divides B —
    # pinning it to None would force an all-gather of a batch GSPMD may
    # already have sharded
    while sharded and x.shape[0] % int(
            np.prod([mesh.shape[a] for a in sharded])):
        sharded.pop()
    spec = [tuple(sharded) if sharded else None] + [None] * (x.ndim - 1)
    spec[seq_dim] = "sp"
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec(*spec)))


def param_spec(param, mesh=None, mode="replicate"):
    """Sharding for one Parameter: explicit set_sharding wins; else policy."""
    mesh = mesh or current_mesh()
    if param.sharding is not None:
        s = param.sharding
        if isinstance(s, PartitionSpec):
            return NamedSharding(mesh, s)
        return s
    if mode == "fsdp":
        return fsdp_spec(param.shape, mesh, getattr(param, "shard_hint", None))
    if mode != "replicate":
        # an unrecognized mode must not silently replicate — a typo like
        # "shard" would otherwise run (and test) the wrong configuration
        raise ValueError(f"param_mode {mode!r}: expected 'replicate' or "
                         "'fsdp'")
    return replicated(mesh)


def spec_to_tree(spec):
    """PartitionSpec (or NamedSharding) → a JSON-able list: one entry per
    dim, each None | axis-name | [axis-names]. The serialization the
    checkpoint manifest records per array so a restore on a DIFFERENT
    topology can plan the redistribution (parallel/reshard.py)."""
    if isinstance(spec, NamedSharding):
        spec = spec.spec
    out = []
    for entry in tuple(spec):
        if entry is None or isinstance(entry, str):
            out.append(entry)
        else:
            out.append(list(entry))
    return out


def spec_from_tree(tree):
    """Inverse of spec_to_tree."""
    entries = []
    for entry in tree or []:
        if entry is None or isinstance(entry, str):
            entries.append(entry)
        else:
            entries.append(tuple(entry))
    return PartitionSpec(*entries)


def apply_tp_rules(block, rules):
    """Attach Megatron-style tp specs by parameter-path regex.

    rules: list of (regex, PartitionSpec). First match wins. Example for a
    transformer MLP: [(r'.*ffn_in.*weight', P('tp', None)),
                      (r'.*ffn_out.*weight', P(None, 'tp'))]."""
    import re
    for path, p in block.collect_params().items():
        for pattern, spec in rules:
            if re.search(pattern, path):
                p.set_sharding(spec)
                break
