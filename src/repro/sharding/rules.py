"""Logical-axis -> mesh-axis sharding rules.

Model code annotates every param with logical axis names; this module maps
them onto the production mesh (("data","model") or ("pod","data","model")).
The pod axis only ever carries batch (pure cross-pod data parallelism — the
slow inter-pod links carry gradients, which is where GPULZ gradient
compression applies).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

LOGICAL_RULES = {
    # embeddings
    "vocab": "model",  # output/tied table rows
    "vocab_in": "data",  # input table rows (d sharded on model)
    "embed_sharded": "model",
    "embed": "data",  # d_model inside weights: FSDP over data
    "embed_unsharded": None,
    "embed_out": "data",
    # attention
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "lora": None,  # MLA latent dims (replicated)
    # mlp / moe
    "ffn": "model",
    "experts": "model",  # expert parallelism
    "expert_ffn": None,
    # ssm
    "ssm_inner": "model",
    "ssm_heads": "model",
    "ssm_inner_conv": None,
    "state": None,
    "conv": None,
    # stacking
    "layers": None,
}


def _is_axes(x):
    return isinstance(x, tuple) and all(isinstance(s, str) for s in x)


# Weight-FSDP toggle (§Perf lever): when off, weight d_model/vocab_in dims
# replicate over the data axis — no per-layer weight all-gathers, at the cost
# of (params+grads)/model_axis bytes per device.  Profitable for models whose
# replicated working set fits HBM; required off... see steps.fsdp_decision.
_FSDP_AXES = ("embed", "vocab_in", "embed_out")
_FSDP = True


def set_fsdp(enabled: bool):
    global _FSDP
    _FSDP = bool(enabled)


def fsdp_enabled() -> bool:
    return _FSDP


def spec_for(axes: tuple) -> P:
    def one(a):
        if a in _FSDP_AXES and not _FSDP:
            return None
        return LOGICAL_RULES.get(a, None)

    return P(*(one(a) for a in axes))


def batch_axes(mesh: Mesh) -> tuple:
    """Mesh axes that carry the batch dimension."""
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else ("data",)


def batch_spec(mesh: Mesh, batch_size: int) -> P:
    """Shard batch if divisible by the batch axes; else replicate (B=1)."""
    ax = batch_axes(mesh)
    total = int(np.prod([mesh.shape[a] for a in ax]))
    return P(ax) if batch_size % total == 0 else P(None)


def params_shardings(axes_tree, mesh: Mesh):
    return jax.tree.map(
        lambda a: NamedSharding(mesh, spec_for(a)), axes_tree, is_leaf=_is_axes
    )


def compute_spec(axes: tuple) -> P:
    """Weight layout *during compute*: storage spec minus the data (FSDP)
    axis.  Constraining a layer's weights to this inside the scan body makes
    the partitioner emit one small per-layer weight all-gather (classic FSDP)
    instead of replicating batch activations."""

    def one(a):
        r = LOGICAL_RULES.get(a, None)
        return None if r == "data" else r

    return P(*(one(a) for a in axes))


def compute_specs_tree(axes_tree, drop_leading: int = 0):
    """drop_leading: strip stacked dims (e.g. the (L, ...) 'layers' axis)
    when the specs will be applied to per-layer slices."""
    return jax.tree.map(
        lambda a: compute_spec(a[drop_leading:]), axes_tree, is_leaf=_is_axes
    )


def params_pspecs(axes_tree):
    return jax.tree.map(spec_for, axes_tree, is_leaf=_is_axes)


def zero_spec(spec: P, shape: tuple, mesh: Mesh) -> P:
    """Additionally shard optimizer state over the data axis (ZeRO-style).

    Picks the first unsharded dim divisible by the data axis; leaves the
    param's own (model) sharding intact.
    """
    data = mesh.shape["data"]
    parts = list(spec) + [None] * (len(shape) - len(spec))
    if any(p == "data" or (isinstance(p, tuple) and "data" in p) for p in parts):
        return P(*parts)  # already FSDP-sharded over data
    for i, (p, n) in enumerate(zip(parts, shape)):
        if p is None and n % data == 0 and n >= data:
            parts[i] = "data"
            return P(*parts)
    return P(*parts)


def zero_shardings(axes_tree, abstract_params, mesh: Mesh):
    def one(a, s):
        return NamedSharding(mesh, zero_spec(spec_for(a), s.shape, mesh))

    return jax.tree.map(one, axes_tree, abstract_params, is_leaf=_is_axes)


def activation_spec(mesh: Mesh, batch_size: int) -> P:
    """(B, T, d) activations: batch sharded, T/d replicated."""
    return batch_spec(mesh, batch_size)


# --------------------------------------------------------------------------
# Activation-sharding context: model code pins batch sharding with bare
# PartitionSpecs (resolved against the context mesh the step builders in
# launch/steps.py trace under).  Without these pins the SPMD partitioner may choose
# to replicate activations instead of weights once weights are FSDP-sharded.

_BATCH_AXES: tuple = ("data",)
_SEQ_PARALLEL = False  # shard T of the residual stream on "model"
_DATA_SHARDS = 1  # batch-axes size (for per-shard MoE dispatch)


def set_activation_batch_axes(axes: tuple, data_shards: int = None):
    global _BATCH_AXES, _DATA_SHARDS
    _BATCH_AXES = tuple(axes)
    if data_shards is not None:
        _DATA_SHARDS = int(data_shards)


def data_shard_count() -> int:
    return _DATA_SHARDS


def activation_batch_axes() -> tuple:
    return _BATCH_AXES


def set_seq_parallel(enabled: bool):
    """Megatron-style sequence parallelism: between layers the (B, T, d)
    residual stream is sharded (batch->data, T->model).  The partitioner then
    turns each TP partial-sum all-reduce into reduce-scatter(+all-gather at
    the next consumer), halving exchanged bytes and keeping norms/residuals
    T-sharded.  §Perf lever."""
    global _SEQ_PARALLEL
    _SEQ_PARALLEL = bool(enabled)


def seq_parallel_enabled() -> bool:
    return _SEQ_PARALLEL


def constrain_batch(x, *rest):
    """Pin dim0 of ``x`` to the batch axes (no-op without a mesh context).

    rest: specs for the remaining dims (defaults to None each).  With
    sequence parallelism on, 3D activations additionally shard dim1 (T) on
    the model axis.
    """
    if jax.sharding.get_abstract_mesh().empty:
        return x
    explicit = len(rest)
    rest = list(rest) + [None] * (x.ndim - 1 - len(rest))
    if _SEQ_PARALLEL and explicit == 0 and x.ndim == 3:
        rest[0] = "model"
    spec = [_BATCH_AXES] + rest
    return jax.lax.with_sharding_constraint(x, P(*spec))
