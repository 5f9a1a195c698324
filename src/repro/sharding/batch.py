"""Shard-mapped multi-device batch compression.

GPULZ's design scales by making every chunk independent (paper §IV: per-chunk
Kernel I, global prefix sums, deflate scatter).  The same independence holds
one level up: whole *buffers* in a batch are independent too, so the batched
entry points (``pipeline.compress_many_chunks`` / ``decompress_many_chunks``)
can partition the B dimension over a named mesh axis and run the registered
single-device pipeline per shard, instead of dispatching all B buffers on one
chip (cf. Sitaridi et al., "Massively-Parallel Lossless Data Decompression" —
the decompression-side version of the same argument).

``ShardedBatchRunner`` is that layer:

  * the batch dimension is padded up to a multiple of the shard count
    (zero rows, discarded after the gather) and split with ``shard_map``
    over the mesh axes named by ``batch_axis`` (default: the logical batch
    axes from ``sharding/rules.py`` — ``("pod", "data")`` when a pod axis
    exists, else ``("data",)``);
  * every shard runs the *existing* registered backend/decoder — the
    auto-resolved platform default (the single-kernel ``fused-mono`` pair
    in both directions on TPU, ``xla``/``xla-parallel`` elsewhere; the
    decode side dispatches through the ``decode_blob`` hook, so each shard's
    decompress is ONE Pallas launch reading its blobs straight from HBM) —
    so per-buffer blobs/symbols are byte-identical to the single-device
    dispatch by construction;
  * the ragged per-buffer blobs gather back as the same ``(B, cap)`` buffer +
    ``(B,)`` totals contract the unsharded batched cores return.

The runner is exposed through the backend registry rather than ``if``-ladders
in ``core/pipeline.py``: ``LZSSConfig(backend="sharded", decoder="sharded",
mesh=..., batch_axis=...)`` selects the registered ``"sharded"``
compressor/decoder pair (``pipeline.ShardedCompressor`` /
``pipeline.ShardedDecoder``), which lazily constructs a runner here.  With
``mesh=None`` (or a single-shard mesh) the runner degenerates to the plain
vmapped dispatch, so ``"sharded"`` is always a safe registry key.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from repro.core import pipeline
from repro.sharding import rules


def unsharded(cfg: "pipeline.LZSSConfig") -> "pipeline.LZSSConfig":
    """The per-shard (single-device) view of a sharded config.

    Strips ``mesh``/``batch_axis`` and resolves the ``"sharded"`` registry
    keys to the platform defaults, so the function a shard runs is exactly
    the unsharded dispatch — this is what makes sharded output byte-identical
    by construction (and what prevents shard_map recursion).
    """
    backend = "auto" if cfg.backend == "sharded" else cfg.backend
    decoder = "auto" if cfg.decoder == "sharded" else cfg.decoder
    backend = pipeline.resolve_backend(backend)
    decoder = pipeline.resolve_decoder(decoder)
    if (backend, decoder, cfg.mesh) == (cfg.backend, cfg.decoder, None):
        return cfg
    return dataclasses.replace(
        cfg, backend=backend, decoder=decoder, mesh=None, batch_axis=None
    )


def normalize_batch_axes(mesh: Mesh, batch_axis=None) -> tuple:
    """Mesh axes carrying the batch dimension, as a tuple of axis names.

    ``batch_axis`` may be a single axis name, a tuple of names, or ``None``
    (use the logical batch axes from ``rules.batch_axes``, filtered to the
    axes this mesh actually has; falls back to the mesh's leading axis).
    """
    if batch_axis is None:
        axes = tuple(a for a in rules.batch_axes(mesh) if a in mesh.axis_names)
        return axes or (mesh.axis_names[0],)
    if isinstance(batch_axis, str):
        batch_axis = (batch_axis,)
    axes = tuple(batch_axis)
    missing = [a for a in axes if a not in mesh.axis_names]
    if missing:
        raise ValueError(
            f"batch_axis {missing} not in mesh axes {tuple(mesh.axis_names)}"
        )
    return axes


def _sharded_call(fn, mesh: Mesh, axes: tuple, in_arity: int):
    """shard_map ``fn`` with dim 0 of every arg and output split over ``axes``.

    Mesh axes outside ``axes`` replicate the computation; every replica
    holds the same bytes, so the output names only ``axes``.
    ``check_vma=False`` is required because the body runs jitted pipeline
    code (Pallas kernels on TPU) whose replication JAX cannot infer.  The
    map runs over Auto axes whatever the caller's mesh says, so the ragged
    trims after it (``blobs[:b]``) need no sharding annotations.
    """
    auto = mesh.update(axis_types=(AxisType.Auto,) * len(mesh.axis_names))
    return jax.shard_map(
        fn,
        mesh=auto,
        in_specs=(P(axes),) * in_arity,
        out_specs=P(axes),
        check_vma=False,
    )


def shard_vmap(fn, mesh: Mesh, axis):
    """vmap ``fn`` over dim 0, with the rows split over ``axis`` shards.

    The shard-mapped analogue of ``jax.vmap(fn)``: each shard of the named
    mesh axis (or axes) maps ``fn`` over its local rows only.  Used by the
    gradient exchange to pin per-pod compression to the pod that owns the
    shard, and by ``ShardedBatchRunner`` below.
    """
    axes = (axis,) if isinstance(axis, str) else tuple(axis)

    def call(*args):
        return _sharded_call(jax.vmap(fn), mesh, axes, len(args))(*args)

    return call


def _pad_rows(x: jnp.ndarray, rows: int) -> jnp.ndarray:
    """Zero-pad dim 0 up to ``rows``; padded outputs are sliced off after the
    gather.

    Zero rows are valid pipeline inputs on both sides (all-zero symbols
    compress fine; a zero "container" row decodes as zero tokens — every
    section gather is bounds-checked).
    """
    pad = rows - x.shape[0]
    if pad == 0:
        return x
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))


class ShardedBatchRunner:
    """Partition the B dimension of the batched cores over a mesh axis.

    ``mesh=None`` (or a single-shard axis) degenerates to the plain vmapped
    single-device dispatch — same code path, same bytes.  Otherwise B is
    padded to a multiple of the shard count and ``shard_map`` runs the
    unsharded batched core per shard (see module docstring).
    """

    def __init__(self, mesh: Mesh | None, batch_axis=None):
        self.mesh = mesh
        self.axes = None if mesh is None else normalize_batch_axes(mesh, batch_axis)

    @property
    def n_shards(self) -> int:
        if self.mesh is None:
            return 1
        n = 1
        for a in self.axes:
            n *= self.mesh.shape[a]
        return n

    def _padded_rows(self, b: int) -> int:
        k = self.n_shards
        return -(-b // k) * k

    def compress_many(self, symbols, cfg, orig_bytes):
        """(B, nc, C) symbols -> ((B, cap) u8 blobs, (B,) totals), sharded.

        Every shard compresses its local rows with the unsharded config
        (``unsharded(cfg)``), so each row's container is byte-identical to
        the single-device ``compress_many_chunks`` output.
        """
        inner = unsharded(cfg)
        if self.n_shards == 1:
            return pipeline.compress_many_chunks(symbols, inner, orig_bytes)
        b = symbols.shape[0]
        bp = self._padded_rows(b)
        run = _sharded_call(
            lambda s_, o_: pipeline.compress_many_chunks(s_, inner, o_),
            self.mesh,
            self.axes,
            2,
        )
        blobs, totals = run(_pad_rows(symbols, bp), _pad_rows(orig_bytes, bp))
        return blobs[:b], totals[:b]

    def decompress_many(
        self,
        blobs,
        n_tokens,
        payload_sizes,
        *,
        symbol_size,
        chunk_symbols,
        n_chunks,
        decoder="auto",
        chunks_per_block=None,
        method_params=(),
    ):
        """(B, L) blobs + (B, nc) tables -> (B, nc, C) symbols, sharded."""
        dec = pipeline.resolve_decoder("auto" if decoder == "sharded" else decoder)
        kw = dict(
            symbol_size=symbol_size,
            chunk_symbols=chunk_symbols,
            n_chunks=n_chunks,
            decoder=dec,
            chunks_per_block=chunks_per_block,
            method_params=method_params,
        )
        if self.n_shards == 1:
            return pipeline.decompress_many_chunks(
                blobs, n_tokens, payload_sizes, **kw
            )
        b = blobs.shape[0]
        bp = self._padded_rows(b)
        run = _sharded_call(
            lambda b_, t_, p_: pipeline.decompress_many_chunks(b_, t_, p_, **kw),
            self.mesh,
            self.axes,
            3,
        )
        out = run(
            _pad_rows(blobs, bp),
            _pad_rows(n_tokens, bp),
            _pad_rows(payload_sizes, bp),
        )
        return out[:b]
