"""GPULZ top-level API: the paper's five-step pipeline on TPU/XLA.

    matching -> local prefix sum -> encoding -> global prefix sum -> deflating
    `------------- Kernel I -------------'    `-- Kernel II --'   `Kernel III'

The pipeline is pluggable (core/pipeline.py): ``LZSSConfig(backend=...)``
selects the Kernel-I strategy AND the emit tail — ``fused-mono`` (the TPU
``"auto"`` default) runs the whole chain, from matching through the
Kernel-III deflate-scatter, in ONE Pallas kernel (``fused-deflate`` keeps
the three-launch split as the fallback).
``compress_chunks`` / ``compress_many_chunks`` are the fully jittable cores
(fixed shapes, usable in-graph for gradient/KV compression); ``compress`` /
``decompress`` and ``compress_many`` / ``decompress_many`` are host-facing
wrappers handling padding, headers and dynamic sizes.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core import format as fmt

# The jittable pipeline layer; re-exported so existing imports keep working.
from repro.core.pipeline import (  # noqa: F401
    DEFAULT_CONFIG,
    WINDOW_LEVELS,
    CompressorBackend,
    DecoderBackend,
    LZSSConfig,
    available_backends,
    available_decoders,
    compress_chunks,
    compress_many_chunks,
    decompress_chunks,
    decompress_many_chunks,
    default_backend,
    default_decoder,
    get_backend,
    get_decoder,
    pack_symbols,
    register_backend,
    register_decoder,
    resolve_backend,
    resolve_chunk_geometry,
    resolve_decode_geometry,
    resolve_decoder,
    tuned_config,
    unpack_symbols,
)

# ---------------------------------------------------------------- host API


@dataclasses.dataclass(frozen=True)
class CompressResult:
    data: np.ndarray        # uint8, exactly total_bytes long
    orig_bytes: int
    total_bytes: int

    @property
    def ratio(self) -> float:
        return self.orig_bytes / max(1, self.total_bytes)


_DISPATCH_QUANTUM = 4096  # decompress shape-bucketing granularity (bytes)


def _dispatch_capacity(n_bytes: int) -> int:
    """Round a container size up to the next dispatch bucket.

    The decompression gathers are bounds-checked (clipped + masked), so the
    dispatch buffer only needs to cover the blob itself; rounding to a coarse
    quantum bounds jit-cache growth across blob sizes.  Crucially this is
    linear in the blob size — small blobs are NOT padded to the worst-case
    ``max_compressed_bytes`` capacity of their (possibly huge) chunk geometry.
    """
    return -(-max(n_bytes, 1) // _DISPATCH_QUANTUM) * _DISPATCH_QUANTUM


# little-endian host views of S-byte symbols (``pack_symbols`` layout)
_SYMBOL_DTYPE = {1: np.dtype("u1"), 2: np.dtype("<u2"), 4: np.dtype("<i4")}


def _pack_padded(raw: np.ndarray, nc: int, cfg: LZSSConfig) -> jnp.ndarray:
    """(n,) uint8 host bytes -> (nc, C) int32 symbols, zero-padded.

    Packed on the host as a dtype view: the device receives the int32
    symbols alone, never an (n, S) byte intermediate.
    """
    s, c = cfg.symbol_size, cfg.chunk_symbols
    padded = np.zeros(nc * c * s, np.uint8)
    padded[: raw.size] = raw
    sym = padded.view(_SYMBOL_DTYPE[s]).astype(np.int32)
    return jnp.asarray(sym.reshape(nc, c))


def _as_bytes(data) -> np.ndarray:
    return np.ascontiguousarray(data).view(np.uint8).reshape(-1)


def compress(data, config: LZSSConfig = DEFAULT_CONFIG) -> CompressResult:
    """Compress any array/bytes. Pads to whole chunks; header records truth."""
    raw = _as_bytes(data)
    n = raw.size
    s, c = config.symbol_size, config.chunk_symbols
    nsym = -(-max(n, 1) // s)
    nc = -(-nsym // c)
    symbols = _pack_padded(raw, nc, config)
    # tuned geometry must resolve HERE, outside the jit trace — timed
    # sweeps inside a trace would measure tracing, not kernels
    config = resolve_chunk_geometry(config)
    buf, total = compress_chunks(symbols, config, jnp.int32(n))
    buf, total = np.asarray(buf), int(total)
    return CompressResult(data=buf[:total], orig_bytes=n, total_bytes=total)


def decompress(blob, decoder: str = "auto", chunks_per_block=None) -> np.ndarray:
    """Decompress a container -> uint8 array of the original bytes.

    ``decoder`` selects the decode strategy by registry key
    (``available_decoders()``; ``"auto"`` = the single-launch ``fused-mono``
    decoder on TPU, which reads the blob straight from HBM — ONE Pallas
    launch per decompress, no section gathers).  ``chunks_per_block`` pins
    the decode kernels' block geometry (format-invisible; ``None`` = the
    autotuner, resolved eagerly here — outside the jit trace).
    """
    blob = np.asarray(blob, np.uint8)
    # raises ValueError (expected vs actual byte counts) on truncated or
    # table-corrupted blobs instead of decoding garbage symbols
    h, n_tokens, payload_sizes = fmt.validate_container(blob)
    full = np.zeros(_dispatch_capacity(blob.size), np.uint8)
    full[: blob.size] = blob
    # the container's method byte routes the decode: entropy containers
    # decode only through the entropy decoder, lossy ones only through the
    # lossy decoder, raw ones through any raw decoder — a mismatch is a
    # clean ValueError, never garbage symbols
    method_params = ()
    if h.method == fmt.METHOD_HUFFMAN:
        if decoder not in ("auto", "deflate-full"):
            raise ValueError(
                f"method-1 (entropy) container: decodes only via "
                f"decoder='deflate-full' (or 'auto'), got {decoder!r}"
            )
        dec = "deflate-full"
    elif h.method == fmt.METHOD_LOSSY:
        if decoder not in ("auto", "lossy-fz"):
            raise ValueError(
                f"method byte {h.method} (lossy) container: decodes only "
                f"via decoder='lossy-fz' (or 'auto'), got {decoder!r}"
            )
        dec = "lossy-fz"
        # mode / inner method are trace-shape relevant: recover them from
        # the header host-side and pin them as static decode parameters
        method_params = get_decoder(dec).static_params(h)
    else:
        # canonicalize before the jit boundary: "auto"/aliases must share
        # the resolved key's trace cache entry, not mint their own
        dec = resolve_decoder(decoder)
        if dec == "deflate-full":
            raise ValueError(
                "decoder='deflate-full' decodes method-1 (entropy) "
                "containers only; this container is method 0 (raw LZSS)"
            )
        if dec == "lossy-fz":
            raise ValueError(
                "decoder='lossy-fz' decodes method-2 (lossy) containers "
                f"only; this container's method byte is {h.method}"
            )
    symbols = decompress_chunks(
        jnp.asarray(full),
        jnp.asarray(n_tokens),
        jnp.asarray(payload_sizes),
        symbol_size=h.symbol_size,
        chunk_symbols=h.chunk_symbols,
        n_chunks=h.n_chunks,
        decoder=dec,
        # tuned decode geometry resolves eagerly — never inside the trace
        chunks_per_block=resolve_decode_geometry(
            chunks_per_block,
            symbol_size=h.symbol_size,
            chunk_symbols=h.chunk_symbols,
            decoder=dec,
        ),
        method_params=method_params,
    )
    sym = np.asarray(symbols).reshape(-1).astype(_SYMBOL_DTYPE[h.symbol_size])
    return sym.view(np.uint8)[: h.orig_bytes]


def compression_ratio(data, config: LZSSConfig = DEFAULT_CONFIG) -> float:
    return compress(data, config).ratio


# ------------------------------------------------------------ batched API


@dataclasses.dataclass(frozen=True)
class BatchedCompressResult:
    """B containers compressed in one dispatch.

    ``data`` is the stacked (B, cap) uint8 buffer; row ``b`` holds a complete
    container in its first ``total_bytes[b]`` bytes (zeros beyond).
    """

    data: np.ndarray          # (B, cap) uint8
    orig_bytes: np.ndarray    # (B,) int64
    total_bytes: np.ndarray   # (B,) int64
    config: LZSSConfig

    def __len__(self) -> int:
        return self.data.shape[0]

    def __getitem__(self, b: int) -> CompressResult:
        return CompressResult(
            data=self.data[b, : self.total_bytes[b]],
            orig_bytes=int(self.orig_bytes[b]),
            total_bytes=int(self.total_bytes[b]),
        )

    @property
    def ratio(self) -> float:
        return int(self.orig_bytes.sum()) / max(1, int(self.total_bytes.sum()))


def compress_many(
    arrays, config: LZSSConfig = DEFAULT_CONFIG
) -> BatchedCompressResult:
    """Compress a batch of buffers in ONE jitted dispatch.

    ``arrays`` is either a list of array-likes (ragged sizes allowed — every
    buffer is padded to the batch's common chunk count, headers record true
    sizes) or a (B, n) array treated as B equal-size buffers.  This is the
    entry point the serving / checkpoint / gradient consumers use instead of
    per-array ``compress()`` loops.
    """
    if isinstance(arrays, np.ndarray) and arrays.ndim == 2:
        raws = [_as_bytes(arrays[i]) for i in range(arrays.shape[0])]
    else:
        raws = [_as_bytes(a) for a in arrays]
    if not raws:
        raise ValueError("compress_many needs at least one buffer")
    s, c = config.symbol_size, config.chunk_symbols
    sizes = np.array([r.size for r in raws], np.int64)
    nsym_max = -(-max(1, int(sizes.max())) // s)
    nc = -(-nsym_max // c)
    # tuned geometry must resolve HERE, outside the jit trace (see compress)
    config = resolve_chunk_geometry(config)
    if len(raws) == 1:
        # a batch of one takes the single-buffer core: its (cap,) u8 output
        # is not padded the way a (1, cap) u8 array's tiles are on TPU (4x),
        # which matters for a checkpoint leaf of a GiB
        data, total = compress_chunks(
            _pack_padded(raws[0], nc, config), config, jnp.int32(sizes[0])
        )
        data, totals = data[None], total[None]
    else:
        symbols = jnp.stack([_pack_padded(r, nc, config) for r in raws])
        data, totals = compress_many_chunks(
            symbols, config, jnp.asarray(sizes, jnp.int32)
        )
    return BatchedCompressResult(
        data=np.asarray(data),
        orig_bytes=sizes,
        total_bytes=np.asarray(totals, np.int64),
        config=config,
    )


def decompress_many(
    batch, decoder: str = "auto", mesh=None, batch_axis=None,
    chunks_per_block=None,
) -> list:
    """Decompress a batch of containers in ONE jitted dispatch.

    ``batch`` is a ``BatchedCompressResult`` or a list of container blobs.
    All containers must share the same geometry (S, C, n_chunks) — true for
    anything produced by ``compress_many``.  ``decoder`` selects the decode
    strategy by registry key.  ``mesh``/``batch_axis`` shard the B dimension
    of the dispatch over a device mesh via the ``"sharded"`` decoder
    (``sharding/batch.py``); symbols are identical to the single-device
    dispatch.  ``chunks_per_block`` pins the decode kernels' block geometry
    (format-invisible; ``None`` = the autotuner, resolved eagerly here).
    Entropy (method-1) batches route to the ``"deflate-full"`` decoder
    automatically — with a mesh, it becomes the per-shard inner decoder of
    the sharded dispatch.  Returns a list of uint8 arrays.
    """
    if mesh is None and batch_axis is not None:
        # mirror LZSSConfig.__post_init__: a batch_axis without a mesh
        # would otherwise be silently dropped by the vmap default path
        raise ValueError("batch_axis requires mesh=...")
    if isinstance(batch, BatchedCompressResult):
        # slice rows to their live bytes: the stacked buffer is worst-case
        # wide, and the dispatch width below must track actual sizes
        blobs = [
            batch.data[b, : int(batch.total_bytes[b])]
            for b in range(len(batch))
        ]
    else:
        blobs = [np.asarray(b, np.uint8) for b in batch]
    headers, tables = [], []
    for i, b in enumerate(blobs):
        try:
            h, n_tok, pay = fmt.validate_container(b)
        except ValueError as e:
            raise ValueError(f"buffer {i}: {e}") from None
        headers.append(h)
        tables.append((n_tok, pay))
    h0 = headers[0]
    for i, h in enumerate(headers[1:], start=1):
        if (h.symbol_size, h.chunk_symbols, h.n_chunks, h.method) != (
            h0.symbol_size, h0.chunk_symbols, h0.n_chunks, h0.method
        ):
            raise ValueError(
                f"decompress_many requires a homogeneous batch geometry; "
                f"buffer 0 has (symbol_size={h0.symbol_size}, "
                f"chunk_symbols={h0.chunk_symbols}, n_chunks={h0.n_chunks}, "
                f"method={h0.method}) "
                f"but buffer {i} has (symbol_size={h.symbol_size}, "
                f"chunk_symbols={h.chunk_symbols}, n_chunks={h.n_chunks}, "
                f"method={h.method}); "
                f"decompress mismatched containers individually"
            )
    # method-byte routing, mirroring ``decompress``: entropy batches take
    # the entropy decoder, lossy batches the lossy decoder (per-shard,
    # when a mesh shards the dispatch)
    entropy_batch = h0.method == fmt.METHOD_HUFFMAN
    lossy_batch = h0.method == fmt.METHOD_LOSSY
    method_params = ()
    if lossy_batch:
        # mode / inner method are static decode parameters (trace-shape
        # relevant), so a batched dispatch needs them homogeneous too
        sp = get_decoder("lossy-fz").static_params
        method_params = sp(h0)
        for i, h in enumerate(headers[1:], start=1):
            if sp(h) != method_params:
                raise ValueError(
                    f"decompress_many requires a homogeneous lossy batch; "
                    f"buffer 0 has (mode, inner_method)={method_params} "
                    f"but buffer {i} has {sp(h)}; "
                    f"decompress mismatched containers individually"
                )
    inner_decoder = None
    if mesh is not None:
        if decoder not in ("auto", "sharded"):
            raise ValueError(
                f"mesh= shards the dispatch through the 'sharded' decoder; "
                f"it cannot be combined with decoder={decoder!r}"
            )
        decoder = "sharded"
        if entropy_batch:
            inner_decoder = "deflate-full"
        elif lossy_batch:
            inner_decoder = "lossy-fz"
    elif entropy_batch:
        if decoder not in ("auto", "deflate-full"):
            raise ValueError(
                f"method-1 (entropy) containers: decode only via "
                f"decoder='deflate-full' (or 'auto'), got {decoder!r}"
            )
        decoder = "deflate-full"
    elif lossy_batch:
        if decoder not in ("auto", "lossy-fz"):
            raise ValueError(
                f"method byte {h0.method} (lossy) containers: decode only "
                f"via decoder='lossy-fz' (or 'auto'), got {decoder!r}"
            )
        decoder = "lossy-fz"
    elif decoder != "sharded" and resolve_decoder(decoder) == "deflate-full":
        raise ValueError(
            "decoder='deflate-full' decodes method-1 (entropy) containers "
            "only; this batch is method 0 (raw LZSS)"
        )
    elif decoder != "sharded" and resolve_decoder(decoder) == "lossy-fz":
        raise ValueError(
            "decoder='lossy-fz' decodes method-2 (lossy) containers only; "
            f"this batch's method byte is {h0.method}"
        )
    width = _dispatch_capacity(max(b.size for b in blobs))
    stacked = np.zeros((len(blobs), width), np.uint8)
    for i, b in enumerate(blobs):
        stacked[i, : b.size] = b
    dec = resolve_decoder(decoder)  # one trace cache entry per key
    symbols = decompress_many_chunks(
        jnp.asarray(stacked),
        jnp.asarray(np.stack([t[0] for t in tables])),
        jnp.asarray(np.stack([t[1] for t in tables])),
        symbol_size=h0.symbol_size,
        chunk_symbols=h0.chunk_symbols,
        n_chunks=h0.n_chunks,
        decoder=dec,
        chunks_per_block=resolve_decode_geometry(
            chunks_per_block,  # eager: sweeps never run inside the trace
            symbol_size=h0.symbol_size,
            chunk_symbols=h0.chunk_symbols,
            decoder=dec,
        ),
        mesh=mesh,
        batch_axis=(
            tuple(batch_axis)
            if isinstance(batch_axis, list)
            else batch_axis  # static jit arg: must be hashable
        ),
        inner_decoder=inner_decoder,
        method_params=method_params,
    )
    flat = np.asarray(symbols).reshape(len(blobs), -1)
    out_bytes = flat.astype(_SYMBOL_DTYPE[h0.symbol_size]).view(np.uint8)
    return [out_bytes[i, : h.orig_bytes] for i, h in enumerate(headers)]
