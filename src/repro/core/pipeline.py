"""Pluggable compression pipeline backends.

The paper's pipeline is

    matching -> local prefix sum -> encoding -> global prefix sum -> deflating
    `------------- Kernel I -------------'    `-- Kernel II --'   `Kernel III'

Kernel I is the part with real implementation freedom (their Fig. 4(c) vs
(d)): it can be staged through HBM as separate XLA ops, or fused so the
equality rows, run lengths, selection walk and local prefix sum never leave
VMEM.  This module makes that choice a *backend*:

  * ``CompressorBackend`` — the Kernel-I contract: ``kernel1(symbols, cfg)``
    returns every per-position / per-chunk array the shared Kernel-II/III
    tail needs (see ``Kernel1Result``).
  * a registry (``register_backend`` / ``get_backend``) so new execution
    strategies plug in without touching the pipeline tail — this is the
    extension point for future PRs (see ROADMAP.md).
  * ``compress_chunks`` / ``decompress_chunks`` — the jittable single-buffer
    cores, now dispatching Kernel I through the configured backend.
  * ``compress_many_chunks`` / ``decompress_many_chunks`` — the batched
    in-graph API: one dispatch compresses B independent buffers (vmap over
    the backend + tail), which is what the gradient/KV/checkpoint consumers
    need instead of per-array host loops.

A backend may also own the *emit* tail (Kernel II global prefix sums +
Kernel III deflate-scatter) by providing an optional ``emit`` method; the
default is the shared XLA tail ``emit_xla``.  This keeps new execution
strategies registry entries rather than ``if``-ladders in
``compress_chunks``.

Registered backends:

  ``xla``           unfused reference path (workflow (c)): XLA matching, the
                    beyond-paper pointer-doubling selector, XLA prefix sums.
  ``xla-scan``      same but with the paper-faithful sequential selection
                    walk (lax.scan) — the equivalence oracle.
  ``pallas-match``  Pallas matching kernel, XLA select + prefix sums (the
                    old ``matcher="pallas"`` switch).
  ``fused``         the fused Pallas Kernel I (kernels/lz_match.py) produces
                    lengths/offsets/emitted/local_off/payload_sizes/n_tokens
                    in one VMEM-resident kernel; the redundant XLA selection
                    and local prefix sum are skipped entirely.  The emit
                    tail stays XLA.
  ``fused-deflate`` fused Kernel I plus a fused Kernel II+III
                    (kernels/lz_scatter.py) — one kernel computes both
                    global exclusive prefix sums, a second rebuilds the
                    flag/payload sections in VMEM and scatters them into the
                    blob via scalar-prefetched per-chunk offsets.  The
                    aligned (nc, C//8)/(nc, C*S) section arrays never
                    materialize in HBM, but the (nc, C) Kernel-I outputs
                    still round-trip through it between the launches.
  ``fused-mono``    the paper's workflow (d) end to end in ONE kernel
                    (kernels/lz_fused.py): matching, selection, both local
                    AND global prefix sums (SMEM carry over the sequential
                    grid), section rebuild and the blob scatter — no
                    intermediate of any shape touches HBM, and the blob is
                    written through per-chunk DMA windows instead of a
                    VMEM-resident (1, cap) block, so containers are not
                    bounded by VMEM.  Owns the whole single-buffer path via
                    the optional ``compress`` hook (see below).
  ``sharded``       multi-device batch layer (sharding/batch.py): the B
                    dimension of the batched entry points is shard-mapped
                    over ``LZSSConfig(mesh=..., batch_axis=...)`` and every
                    shard runs the auto-resolved platform backend.  Plugs in
                    through two more optional backend hooks, ``compress_many``
                    / ``decompress_many`` (mirroring ``emit``): a backend may
                    own the whole batched dispatch, with the vmapped
                    single-buffer core as the default.
  ``deflate-full``  the entropy-coded container (core/entropy.py): the
                    platform LZSS pipeline runs first (via the ``compress``
                    hook, so the fused-mono kernel is still the Kernel-I/II/
                    III engine on TPU), then both container sections are
                    canonical-Huffman coded into a method-1 VERSION-2
                    container with gap-array parallel entry points.  The
                    only backend whose containers differ from the others —
                    byte-identity is traded for ratio; decode requires the
                    ``deflate-full`` decoder (``LZSSConfig`` normalizes
                    ``decoder="auto"`` to it, and ``lzss.decompress``
                    dispatches on the container's method byte).

Decompression mirrors the same design: ``DecoderBackend`` is the decode-side
contract (per-chunk aligned flag/payload sections -> symbols), with its own
registry (``register_decoder`` / ``get_decoder``) and entries

  ``xla-parallel``  beyond-paper fully parallel XLA decoder
                    (core/decode.py:decode_parallel).
  ``xla-scan``      paper-faithful sequential token walk — the oracle.
  ``fused``         fused Pallas decoder (kernels/lz_decode.py): flag
                    extraction, both read/write prefix sums, payload gather
                    and pointer-doubling copy resolution stay in VMEM per
                    chunk block; symbols are written to HBM exactly once.
                    The sections still reach it via two XLA
                    ``deflate.gather_section`` gathers staged through HBM.
  ``fused-mono``    the decode-side workflow (d): ONE Pallas launch per
                    decompress (kernels/lz_decode_mono.py).  The container
                    blob is read straight from HBM (``memory_space=ANY``)
                    through per-chunk DMA windows at scalar-prefetched
                    section offsets, so the gathers fuse into the decode
                    chain and ``deflate.gather_section`` drops out of the
                    decode path entirely.  Owns the whole container->symbols
                    path via the optional ``decode_blob`` hook (the decode
                    mirror of the compressor's ``compress`` hook).
  ``sharded``       decode-side mirror of the sharded compressor: batched
                    decompression shard-mapped over the mesh passed at
                    dispatch, platform decoder per shard.  Entropy containers
                    shard too: ``lzss.decompress_many`` forwards the inner
                    per-shard decoder (``inner_decoder=``) through the
                    ``decompress_many`` hook.
  ``deflate-full``  decoder for method-1 (entropy) containers: gap-array
                    parallel Huffman bitstream decode (Pallas kernel on TPU,
                    vectorized lax.scan elsewhere) rebuilds the raw sections,
                    then hands off to the platform LZSS decode chain.  Raw
                    containers raise a ValueError under it, and entropy
                    containers raise under every raw decoder —
                    ``lzss.decompress`` routes on the method byte.

``LZSSConfig.decoder`` accepts a registry key, ``"auto"`` (the single-launch
``fused-mono`` decoder on TPU, xla-parallel elsewhere — resolved at
dispatch, like ``default_backend()``) or the legacy aliases
``"parallel"``/``"scan"``, which are normalized to registry keys at
construction.

On TPU the single-kernel ``fused-mono`` paths are the default in BOTH
directions (``REPRO_FUSED_MONO=0`` falls back to the split ``fused-deflate``
compressor / ``fused`` decoder, e.g. while auditing the mono kernels'
Mosaic lowering on new hardware); elsewhere the kernels execute in
interpret mode, so the defaults stay ``xla`` / ``xla-parallel`` (identical
bytes, no interpreter overhead).  Kernel block geometry
(``chunks_per_block``, and prospectively ``chunk_symbols`` via
``tuned_config``) resolves through the ``core/autotune.py`` chooser.  All
backends produce byte-identical containers and all decoders identical
symbols — property- and sweep-tested in tests/test_pipeline.py,
tests/test_decoders.py, tests/test_conformance.py, tests/test_decode_mono.py
and the golden corpus under tests/golden/.
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
import math
import os
from typing import Dict, Protocol

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import autotune
from repro.core import decode as decode_mod
from repro.core import deflate, encode, format as fmt, match

# --------------------------------------------------------------- config


def default_backend() -> str:
    """The preferred compressor backend for the current accelerator.

    On TPU the single-kernel ``fused-mono`` compressor is the hot path;
    setting ``REPRO_FUSED_MONO=0`` falls back to the split ``fused-deflate``
    pipeline (byte-identical output, three launches instead of one).
    """
    if jax.default_backend() != "tpu":
        return "xla"
    if os.environ.get("REPRO_FUSED_MONO", "1") == "0":
        return "fused-deflate"
    return "fused-mono"


def default_decoder() -> str:
    """The preferred decoder for the current accelerator.

    On TPU the single-launch ``fused-mono`` decoder is the hot path;
    ``REPRO_FUSED_MONO=0`` falls back to the split ``fused`` decoder
    (gathered sections + per-chunk kernel — identical symbols, two extra
    HBM-staged gathers), the same audit escape hatch as the compress side.
    """
    if jax.default_backend() != "tpu":
        return "xla-parallel"
    if os.environ.get("REPRO_FUSED_MONO", "1") == "0":
        return "fused"
    return "fused-mono"


def resolve_chunk_geometry(cfg: "LZSSConfig") -> "LZSSConfig":
    """Pin ``chunks_per_block`` eagerly, *before* any jit trace.

    The autotuner's timed sweep is only meaningful outside a trace
    (``autotune.best_geometry`` refuses to sweep under one — in-trace
    timings measure tracing, not kernels).  The host wrappers
    (``lzss.compress`` / ``compress_many``) call this right before the
    jitted cores: with tuning enabled and no user pin, the tuned g is
    resolved here — eagerly, kernels actually executing — and baked into
    the config as a static pin, so no call site inside the trace ever
    needs the tuner.  With tuning disabled (or an explicit pin) the config
    passes through unchanged: the in-trace fallback is deterministic.
    """
    if cfg.chunks_per_block is not None or not autotune.enabled():
        return cfg
    g = autotune.block_geometry(
        symbol_size=cfg.symbol_size,
        chunk_symbols=cfg.chunk_symbols,
        direction="compress",
        window=cfg.window,
    )
    return dataclasses.replace(cfg, chunks_per_block=g)


def resolve_decode_geometry(
    chunks_per_block, *, symbol_size: int, chunk_symbols: int, decoder="auto"
):
    """Decode-side mirror of ``resolve_chunk_geometry``.

    Returns the ``chunks_per_block`` value to pass (statically) into
    ``decompress_chunks`` / ``decompress_many_chunks``: the caller's pin if
    given, the eagerly tuned g when tuning is enabled, else ``None`` (the
    in-trace deterministic fallback).  Called by ``lzss.decompress`` /
    ``decompress_many`` with the container header's geometry, before the
    jit boundary.  Decoders that never tile a kernel (the pure-XLA entries
    mark themselves ``uses_block_geometry = False``) skip the sweep — a
    tuned g would be dead weight there.
    """
    if chunks_per_block is not None or not autotune.enabled():
        return chunks_per_block
    if not getattr(get_decoder(decoder), "uses_block_geometry", True):
        return None  # geometry never reaches a kernel: nothing to tune
    return autotune.block_geometry(
        symbol_size=symbol_size,
        chunk_symbols=chunk_symbols,
        direction="decompress",
    )


@dataclasses.dataclass(frozen=True)
class LZSSConfig:
    """Paper parameters: S (symbol bytes), W (window), C (chunk symbols).

    ``backend`` selects the Kernel-I execution strategy and ``decoder`` the
    decompression strategy (see module docstring); both are registry keys,
    and both accept ``"auto"`` (resolved per-platform at dispatch time).
    The legacy decoder aliases ``"parallel"``/``"scan"`` normalize to their
    registry keys here.

    ``chunks_per_block`` pins the Pallas kernels' block geometry (how many
    chunks ride one grid step's sublane dimension); the default ``None``
    defers to the ``core/autotune.py`` chooser (tuned cache on TPU,
    deterministic static fallback elsewhere).  The config travels with the
    *compress* direction; decode entry points take the same pin as their
    own ``chunks_per_block=`` argument (it is format-invisible, so the
    containers decode identically either way) — consumers holding a config
    forward it, e.g. ``KVBlockStore.restore_many`` and
    ``CheckpointManager`` restores.  The
    (chunk_symbols, chunks_per_block) pair is validated against the VMEM
    block budget here — ``autotune.validate_block_geometry`` — so an
    oversized geometry fails at config construction with the offending pair
    named instead of as an opaque Mosaic allocation error inside Pallas.

    ``mesh``/``batch_axis`` configure the shard-mapped multi-device batch
    layer (``sharding/batch.py``): the ``"sharded"`` compressor/decoder pair
    partitions the B dimension of the batched entry points over the named
    mesh axis (or axes; default: the logical batch axes from
    ``sharding/rules.py``) and runs the platform-default backend per shard.
    Only those registry entries consult ``mesh`` — setting it with any other
    backend/decoder would be silently ignored, so it is rejected here.
    """

    symbol_size: int = 2  # S in {1, 2, 4}
    window: int = 128  # W in [1, 255]; levels 1-4 = 32/64/128/255
    chunk_symbols: int = 2048  # C; VMEM-resident chunk
    chunks_per_block: object = None  # g; None = autotune (core/autotune.py)
    backend: str = "xla"  # registry key, see available_backends()
    decoder: str = "auto"  # registry key, see available_decoders()
    mesh: object = None  # jax.sharding.Mesh for "sharded" entries
    batch_axis: object = None  # axis name (or tuple) carrying B; None=auto
    lossy_eb: object = None  # error bound for backend="lossy-fz" (0=lossless)
    lossy_inner: str = "auto"  # lossless stage inside a lossy-fz container

    def __post_init__(self):
        if self.symbol_size not in (1, 2, 4):
            raise ValueError(f"symbol_size must be 1, 2 or 4: {self.symbol_size}")
        if not 1 <= self.window <= 255:
            raise ValueError(f"window must be in [1, 255]: {self.window}")
        if self.chunk_symbols % 8:
            raise ValueError("chunk_symbols must be a multiple of 8")
        # VMEM block-fit check: chunks_per_block=None is validated against
        # the deterministic fallback geometry (the autotuner's candidate
        # filter enforces the same budget on anything it would pick later).
        autotune.validate_block_geometry(
            self.chunk_symbols,
            self.chunks_per_block
            if self.chunks_per_block is not None
            else autotune.DEFAULT_CHUNKS_PER_BLOCK,
            self.symbol_size,
        )
        if self.backend != "auto" and self.backend not in _BACKENDS:
            raise ValueError(
                f"unknown backend {self.backend!r}; "
                f"registered: {available_backends()} (also accepted: 'auto')"
            )
        object.__setattr__(
            self, "decoder", _DECODER_ALIASES.get(self.decoder, self.decoder)
        )
        if self.decoder != "auto" and self.decoder not in _DECODERS:
            raise ValueError(
                f"unknown decoder {self.decoder!r}; "
                f"registered: {available_decoders()} "
                f"(also accepted: 'auto', {sorted(_DECODER_ALIASES)})"
            )
        # the entropy pair is a container *format*, not just an execution
        # strategy: method-1 containers decode only through their own
        # decoder, so pin the pairing here instead of failing at dispatch
        if self.backend == "deflate-full" and self.decoder == "auto":
            object.__setattr__(self, "decoder", "deflate-full")
        if self.decoder == "deflate-full" and self.backend != "deflate-full":
            raise ValueError(
                "decoder='deflate-full' decodes method-1 (entropy) containers "
                "only; pair it with backend='deflate-full'"
            )
        # the lossy pair is likewise a container format: method-2 blobs
        # decode only through their own decoder, and the error bound is
        # part of the config contract, not an optional knob
        if self.backend == "lossy-fz":
            if self.symbol_size != 4:
                raise ValueError(
                    "backend='lossy-fz' quantizes f32 elements: "
                    f"symbol_size must be 4, got {self.symbol_size}"
                )
            eb = self.lossy_eb
            if eb is None or not isinstance(eb, (int, float)):
                raise ValueError(
                    "backend='lossy-fz' requires lossy_eb=<float error "
                    "bound> (0.0 selects the bit-exact lossless mode)"
                )
            if not math.isfinite(eb) or eb < 0:
                raise ValueError(
                    f"lossy_eb must be a finite bound >= 0: {eb}"
                )
            eb32 = float(np.float32(eb))
            if eb32 > 0 and not 2.0**-127 <= eb32 < 2.0**125:
                # the format's integer reciprocal of 2*eb (core/lossy.py
                # ``_rcp``) covers normal f32 results only
                raise ValueError(
                    f"lossy_eb must lie in [2**-127, 2**125) (or be 0): {eb}"
                )
            object.__setattr__(self, "lossy_eb", float(eb))
            inner = resolve_backend(self.lossy_inner)
            if container_method(inner) == fmt.METHOD_LOSSY:
                raise ValueError(
                    f"lossy_inner={self.lossy_inner!r} is not a lossless "
                    "stage; pick a raw or deflate-full backend"
                )
            if self.decoder == "auto":
                object.__setattr__(self, "decoder", "lossy-fz")
        elif self.lossy_eb is not None:
            raise ValueError(
                f"lossy_eb is only consulted by backend='lossy-fz' "
                f"(got backend={self.backend!r})"
            )
        if self.decoder == "lossy-fz" and self.backend != "lossy-fz":
            raise ValueError(
                "decoder='lossy-fz' decodes method-2 (lossy) containers "
                "only; pair it with backend='lossy-fz'"
            )
        if isinstance(self.batch_axis, list):
            # jit static-arg hashability: axis collections must be tuples
            object.__setattr__(self, "batch_axis", tuple(self.batch_axis))
        if self.mesh is None:
            if self.batch_axis is not None:
                raise ValueError("batch_axis requires mesh=...")
            return
        if (
            self.backend not in ("sharded", "deflate-full", "lossy-fz")
            and self.decoder != "sharded"
        ):
            raise ValueError(
                "mesh=... is only consulted by the 'sharded' compressor/"
                "decoder and the batched 'deflate-full'/'lossy-fz' "
                "dispatches; set backend='sharded'/'deflate-full'/'lossy-fz' "
                "and/or decoder='sharded'"
            )
        if self.batch_axis is not None:
            # single source of truth for axis validation (same check the
            # runner applies at dispatch); lazy import to avoid a cycle
            from repro.sharding import batch as shbatch

            shbatch.normalize_batch_axes(self.mesh, self.batch_axis)

    @property
    def min_match(self) -> int:
        return encode.min_match_length(self.symbol_size)


# ------------------------------------------------------------- backends


class CompressorBackend(Protocol):
    """Kernel-I contract: match + select + local prefix sum for all chunks.

    ``kernel1`` maps (nc, C) int32 symbols to a dict (``Kernel1Result``):

      lengths, offsets   (nc, C) int32  best match per position
      emitted            (nc, C) bool   token emitted at this position
      use_match          (nc, C) bool   emitted token is a pointer
      sizes              (nc, C) int32  encoded bytes at this position
      local_off          (nc, C) int32  exclusive prefix sum of sizes
      payload_sizes      (nc,)   int32  compressed payload bytes per chunk
      n_tokens           (nc,)   int32  tokens per chunk (= flag bits)

    A backend may additionally define ``emit(symbols, k1, cfg, orig_bytes)``
    -> ``(buffer u8[cap], total_bytes)`` to own the Kernel-II/III tail
    (global prefix sums + deflate-scatter + header); ``compress_chunks``
    falls back to the shared XLA tail ``emit_xla`` when absent, so
    Kernel-I-only backends keep working unchanged.

    A backend that fuses the *entire* pipeline (there is no Kernel-I/emit
    seam left to split at) may instead define
    ``compress(symbols, cfg, orig_bytes)`` -> ``(buffer u8[cap],
    total_bytes)`` and own the whole single-buffer path — checked before
    ``kernel1``/``emit`` by ``compress_chunks``.  ``fused-mono`` is the
    canonical user.
    """

    name: str

    def kernel1(self, symbols: jnp.ndarray, cfg: LZSSConfig) -> dict: ...


Kernel1Result = Dict[str, jnp.ndarray]

_BACKENDS: Dict[str, CompressorBackend] = {}


def register_backend(
    backend: CompressorBackend, *, overwrite: bool = False
) -> CompressorBackend:
    """Register a backend *instance* under ``backend.name``.

    Duplicate names raise unless ``overwrite=True`` — silently replacing a
    registered backend was an easy way to corrupt a pipeline another module
    had already configured.  Caveat when overwriting: ``compress_chunks``
    jit-caches on the config (which carries only the backend *name*), so
    replacing a backend in place does not invalidate already-traced calls —
    call ``jax.clear_caches()`` after, or register under a fresh name.
    """
    if backend.name in _BACKENDS and not overwrite:
        raise ValueError(
            f"backend {backend.name!r} already registered; "
            f"pass overwrite=True to replace it"
        )
    _BACKENDS[backend.name] = backend
    return backend


def resolve_backend(name: str) -> str:
    """Normalize a backend selector to a registered key.

    Accepts registry keys and ``auto`` (the single-kernel ``fused-mono``
    compressor on TPU, xla elsewhere) — the compress-side mirror of
    ``resolve_decoder``.
    """
    if name == "auto":
        name = default_backend()
    if name not in _BACKENDS:
        raise ValueError(
            f"unknown backend {name!r}; registered: {available_backends()} "
            f"(also accepted: 'auto')"
        )
    return name


def get_backend(name: str) -> CompressorBackend:
    return _BACKENDS[resolve_backend(name)]


def available_backends() -> list:
    return sorted(_BACKENDS)


def _derive_fields(lengths, emitted, use_match, *, symbol_size):
    """The per-position byte sizes implied by a selection."""
    return jnp.where(emitted, jnp.where(use_match, 2, symbol_size), 0).astype(
        jnp.int32
    )


class _XlaBackendBase:
    """Unfused XLA path: matching, selection and prefix sums as separate ops
    staged through HBM — the paper's workflow-(c) baseline."""

    selector = staticmethod(encode.select_tokens_doubling)

    def _matches(self, symbols, cfg):
        return match.find_matches(symbols, window=cfg.window)

    def kernel1(self, symbols, cfg):
        lengths, offsets = self._matches(symbols, cfg)
        emitted = self.selector(lengths, min_match=cfg.min_match)
        fields = encode.token_fields(
            lengths, emitted, min_match=cfg.min_match, symbol_size=cfg.symbol_size
        )
        return dict(lengths=lengths, offsets=offsets, emitted=emitted, **fields)


class XlaBackend(_XlaBackendBase):
    name = "xla"


class XlaScanBackend(_XlaBackendBase):
    """Paper-faithful sequential selection walk (equivalence oracle)."""

    name = "xla-scan"
    selector = staticmethod(encode.select_tokens_scan)


class PallasMatchBackend(_XlaBackendBase):
    """Pallas matching kernel + unfused XLA select/prefix sums."""

    name = "pallas-match"

    def _matches(self, symbols, cfg):
        from repro.kernels import ops  # lazy: kernels are optional at import

        return ops.lz_match(
            symbols, window=cfg.window, chunks_per_block=cfg.chunks_per_block
        )


class FusedBackend:
    """Fused Pallas Kernel I: selection and the local prefix sum stay in
    VMEM with the match intermediates; only the final token metadata is
    written back.  Skips ``encode.select_tokens_*`` and the cumsum in
    ``encode.token_fields`` entirely.  The emit tail stays XLA."""

    name = "fused"

    def kernel1(self, symbols, cfg):
        from repro.kernels import ops  # lazy: kernels are optional at import

        out = ops.lz_kernel1(
            symbols,
            window=cfg.window,
            min_match=cfg.min_match,
            symbol_size=cfg.symbol_size,
            chunks_per_block=cfg.chunks_per_block,
        )
        use_match = out["emitted"] & (out["lengths"] >= cfg.min_match)
        sizes = _derive_fields(
            out["lengths"], out["emitted"], use_match, symbol_size=cfg.symbol_size
        )
        return dict(out, use_match=use_match, sizes=sizes)


class FusedDeflateBackend(FusedBackend):
    """Workflow (d) end to end: fused Kernel I plus the fused Kernel II+III
    (kernels/lz_scatter.py).  One kernel computes both global exclusive
    prefix sums; a second rebuilds the flag/payload sections in VMEM per
    chunk block and scatters each chunk's compact prefix into the blob at
    scalar-prefetched per-chunk offsets — the aligned (nc, C//8)/(nc, C*S)
    section arrays of the XLA tail never materialize in HBM."""

    name = "fused-deflate"

    def emit(self, symbols, k1, cfg, orig_bytes=None):
        from repro.kernels import ops  # lazy: kernels are optional at import

        nc, c = symbols.shape
        s = cfg.symbol_size
        out, flag_total, pay_total = ops.lz_scatter(
            symbols,
            k1["lengths"],
            k1["offsets"],
            k1["emitted"],
            k1["use_match"],
            k1["local_off"],
            k1["n_tokens"],
            k1["payload_sizes"],
            symbol_size=s,
            cap=fmt.max_compressed_bytes(nc * c * s, s, c),
            sec_flags=fmt.HEADER_BYTES + 8 * nc,
            chunks_per_block=cfg.chunks_per_block,
        )
        return _finalize_container(
            out,
            cfg,
            orig_bytes,
            nc=nc,
            c=c,
            n_tokens=k1["n_tokens"],
            payload_sizes=k1["payload_sizes"],
            flag_total=flag_total,
            pay_total=pay_total,
        )


class FusedMonoBackend(FusedBackend):
    """The whole compressor in ONE Pallas kernel (kernels/lz_fused.py):
    Kernel I per chunk block, both global prefix sums as an SMEM carry over
    the sequential grid, section rebuild in VMEM and the blob scatter
    through per-chunk DMA windows into an HBM-resident buffer.  Nothing —
    not even the (nc, C) Kernel-I outputs — round-trips through HBM, and
    the output is tiled, so containers are not bounded by VMEM.

    Owns the full single-buffer path via the ``compress`` hook; the
    inherited ``kernel1`` (fused Kernel I) exists only for callers that
    want the match metadata by itself."""

    name = "fused-mono"

    def compress(self, symbols, cfg, orig_bytes=None):
        from repro.kernels import ops  # lazy: kernels are optional at import

        nc, c = symbols.shape
        s = cfg.symbol_size
        out, n_tokens, payload_sizes, flag_total, pay_total = ops.lz_fused_mono(
            symbols,
            window=cfg.window,
            min_match=cfg.min_match,
            symbol_size=s,
            cap=fmt.max_compressed_bytes(nc * c * s, s, c),
            sec_flags=fmt.HEADER_BYTES + 8 * nc,
            chunks_per_block=cfg.chunks_per_block,
        )
        return _finalize_container(
            out,
            cfg,
            orig_bytes,
            nc=nc,
            c=c,
            n_tokens=n_tokens,
            payload_sizes=payload_sizes,
            flag_total=flag_total,
            pay_total=pay_total,
        )


class ShardedCompressor:
    """Shard-mapped multi-device batch execution (``sharding/batch.py``).

    The batched entry point dispatches here via the optional
    ``compress_many`` hook: the B dimension is partitioned over
    ``cfg.mesh``'s batch axis and every shard runs the auto-resolved
    platform backend — byte-identical to the single-device dispatch by
    construction.  Single-buffer calls (``compress_chunks``) and
    ``mesh=None`` degenerate to the platform backend directly.
    """

    name = "sharded"

    def kernel1(self, symbols, cfg):
        return get_backend("auto").kernel1(symbols, cfg)

    def compress(self, symbols, cfg, orig_bytes=None):
        return _compress_via(get_backend("auto"), symbols, cfg, orig_bytes)

    def compress_many(self, symbols, cfg, orig_bytes):
        from repro.sharding import batch as shbatch  # lazy: avoid cycle

        runner = shbatch.ShardedBatchRunner(cfg.mesh, cfg.batch_axis)
        return runner.compress_many(symbols, cfg, orig_bytes)


class EntropyBackend:
    """Entropy-coded container (core/entropy.py): platform LZSS + canonical
    Huffman over both sections, emitted as a method-1 VERSION-2 container
    with gap-array parallel entry points.  The only backend whose container
    bytes differ from the raw family (``container_method`` marks it);
    ``compress_many`` honors ``cfg.mesh`` so batched entropy compression
    shards exactly like the ``"sharded"`` entry."""

    name = "deflate-full"
    container_method = fmt.METHOD_HUFFMAN

    def kernel1(self, symbols, cfg):
        # the LZSS stage is the platform pipeline; entropy is emit-side only
        return get_backend("auto").kernel1(symbols, cfg)

    def compress(self, symbols, cfg, orig_bytes=None):
        from repro.core import entropy  # lazy: entropy imports this module

        return entropy.compress_entropy(symbols, cfg, orig_bytes)

    def compress_many(self, symbols, cfg, orig_bytes):
        if cfg.mesh is not None:
            from repro.sharding import batch as shbatch  # lazy: avoid cycle

            runner = shbatch.ShardedBatchRunner(cfg.mesh, cfg.batch_axis)
            return runner.compress_many(symbols, cfg, orig_bytes)
        return jax.vmap(lambda s_, o_: compress_chunks(s_, cfg, o_))(
            symbols, orig_bytes
        )


class LossyFzBackend:
    """Error-bounded lossy container (core/lossy.py): cuSZ dual-quant ->
    bitshuffle -> the ``cfg.lossy_inner`` lossless stage, emitted as a
    method-2 container carrying the error bound + exact outlier pairs.
    ``lossy_eb == 0`` selects the bit-exact lossless passthrough mode.
    ``compress_many`` honors ``cfg.mesh`` exactly like the entropy entry."""

    name = "lossy-fz"
    container_method = fmt.METHOD_LOSSY

    def kernel1(self, symbols, cfg):
        # the inner LZSS stage is the platform pipeline; the lossy
        # transform wraps it container-level, not kernel-level
        return get_backend("auto").kernel1(symbols, cfg)

    def compress(self, symbols, cfg, orig_bytes=None):
        from repro.core import lossy  # lazy: lossy imports this module

        return lossy.compress_lossy(symbols, cfg, orig_bytes)

    def compress_many(self, symbols, cfg, orig_bytes):
        if cfg.mesh is not None:
            from repro.sharding import batch as shbatch  # lazy: avoid cycle

            runner = shbatch.ShardedBatchRunner(cfg.mesh, cfg.batch_axis)
            return runner.compress_many(symbols, cfg, orig_bytes)
        return jax.vmap(lambda s_, o_: compress_chunks(s_, cfg, o_))(
            symbols, orig_bytes
        )


register_backend(XlaBackend())
register_backend(XlaScanBackend())
register_backend(PallasMatchBackend())
register_backend(FusedBackend())
register_backend(FusedDeflateBackend())
register_backend(FusedMonoBackend())
register_backend(ShardedCompressor())
register_backend(EntropyBackend())
register_backend(LossyFzBackend())


def container_method(name: str) -> int:
    """The container method a registry entry produces/consumes.

    ``fmt.METHOD_RAW`` for the byte-identical LZSS family,
    ``fmt.METHOD_HUFFMAN`` for the entropy pair — looked up on the
    registered instance (``container_method`` attribute, default raw), so
    tests and benchmarks can pair compressors with decoders generically
    instead of name-matching.  Works for both registries (backend names
    win on collisions only in the sense that methods agree by design).
    """
    entry = _BACKENDS.get(name) or _DECODERS.get(name)
    if entry is None:
        # not a direct key: accept the same selectors the registries do
        # ("auto", legacy decoder aliases) before giving up
        for resolve, table in (
            (resolve_backend, _BACKENDS),
            (resolve_decoder, _DECODERS),
        ):
            try:
                entry = table[resolve(name)]
                break
            except ValueError:
                continue
        else:
            raise ValueError(f"unknown backend/decoder {name!r}")
    return getattr(entry, "container_method", fmt.METHOD_RAW)


# ------------------------------------------------------------- decoders


class DecoderBackend(Protocol):
    """Decode contract: per-chunk aligned sections -> symbols.

    ``decode`` maps the (nc, C//8) int32 flag bytes, (nc, C*S) int32 payload
    bytes and (nc,) int32 token counts (the arrays ``deflate.gather_section``
    rebuilds from a container) to (nc, C) int32 symbols.
    ``chunks_per_block`` pins the kernel block geometry for decoders that
    tile (the Pallas entries); ``None`` defers to the autotuner, and the
    XLA decoders ignore it — it is format-invisible either way.  The kwarg
    is forwarded only to hooks that accept it (``_geometry_kw``), so
    decoders registered against the pre-pin signature keep working.

    A decoder that fuses the section gathers into its kernel may instead
    define ``decode_blob(blob, n_tokens, payload_sizes, *, symbol_size,
    chunk_symbols, n_chunks)`` -> (nc, C) symbols and own the whole
    container->symbols path — checked before the gather+``decode`` split by
    ``decompress_chunks`` (the decode mirror of the compressor's
    ``compress`` hook).  ``fused-mono`` is the canonical user.
    """

    name: str

    def decode(
        self,
        flag_bytes: jnp.ndarray,
        payload: jnp.ndarray,
        n_tokens: jnp.ndarray,
        *,
        symbol_size: int,
        chunks_per_block=None,
    ) -> jnp.ndarray: ...


_DECODERS: Dict[str, DecoderBackend] = {}

# Legacy LZSSConfig.decoder values from before the registry existed.
_DECODER_ALIASES = {"parallel": "xla-parallel", "scan": "xla-scan"}


def register_decoder(
    decoder: DecoderBackend, *, overwrite: bool = False
) -> DecoderBackend:
    """Register a decoder *instance* under ``decoder.name``.

    Duplicate names raise unless ``overwrite=True``, mirroring
    ``register_backend``.  Same jit-cache caveat when overwriting:
    ``decompress_chunks`` caches on the decoder *name*, so replacing a
    registered decoder in place requires ``jax.clear_caches()`` (or a
    fresh name).
    """
    if decoder.name in _DECODERS and not overwrite:
        raise ValueError(
            f"decoder {decoder.name!r} already registered; "
            f"pass overwrite=True to replace it"
        )
    _DECODERS[decoder.name] = decoder
    return decoder


def resolve_decoder(name: str) -> str:
    """Normalize a decoder selector to a registered key.

    Accepts registry keys, the legacy aliases ``parallel``/``scan`` and
    ``auto`` (the single-launch ``fused-mono`` decoder on TPU, xla-parallel
    elsewhere).
    """
    name = _DECODER_ALIASES.get(name, name)
    if name == "auto":
        name = default_decoder()
    if name not in _DECODERS:
        raise ValueError(
            f"unknown decoder {name!r}; registered: {available_decoders()} "
            f"(also accepted: 'auto', {sorted(_DECODER_ALIASES)})"
        )
    return name


def get_decoder(name: str) -> DecoderBackend:
    return _DECODERS[resolve_decoder(name)]


def available_decoders() -> list:
    return sorted(_DECODERS)


class XlaParallelDecoder:
    """Beyond-paper fully parallel XLA decoder (two prefix sums + pointer
    doubling as separate XLA ops — see core/decode.py)."""

    name = "xla-parallel"
    uses_block_geometry = False  # pure XLA: no Pallas tiling to pin/tune

    def decode(
        self, flag_bytes, payload, n_tokens, *, symbol_size, chunks_per_block=None
    ):
        return decode_mod.decode_parallel(
            flag_bytes, payload, n_tokens, symbol_size=symbol_size
        )


class XlaScanDecoder:
    """Paper-faithful sequential token walk (equivalence oracle)."""

    name = "xla-scan"
    uses_block_geometry = False  # pure XLA: no Pallas tiling to pin/tune

    def decode(
        self, flag_bytes, payload, n_tokens, *, symbol_size, chunks_per_block=None
    ):
        return decode_mod.decode_scan(
            flag_bytes, payload, n_tokens, symbol_size=symbol_size
        )


class FusedDecoder:
    """Fused Pallas decoder (kernels/lz_decode.py): flag extraction, the two
    read/write prefix sums, payload gather and pointer-doubling copy
    resolution stay in VMEM per chunk block; decoded symbols are written to
    HBM exactly once."""

    name = "fused"

    def decode(
        self, flag_bytes, payload, n_tokens, *, symbol_size, chunks_per_block=None
    ):
        from repro.kernels import ops  # lazy: kernels are optional at import

        return ops.lz_decode(
            flag_bytes,
            payload,
            n_tokens,
            symbol_size=symbol_size,
            chunks_per_block=chunks_per_block,
        )


class FusedMonoDecoder:
    """Single-launch decoder (kernels/lz_decode_mono.py): the container blob
    stays HBM-resident (``memory_space=ANY``) and each grid step DMAs its
    chunks' flag/payload windows straight into VMEM at scalar-prefetched
    section offsets before running the fused decode chain — the gathers fuse
    into the kernel, so ``deflate.gather_section`` never runs and decode is
    exactly ONE Pallas launch.

    Owns the whole container->symbols path via the ``decode_blob`` hook;
    the section-level ``decode`` (for callers that already gathered the
    sections, e.g. a custom pipeline tail) delegates to the split fused
    kernel — identical symbols either way."""

    name = "fused-mono"

    def decode(
        self, flag_bytes, payload, n_tokens, *, symbol_size, chunks_per_block=None
    ):
        from repro.kernels import ops  # lazy: kernels are optional at import

        return ops.lz_decode(
            flag_bytes,
            payload,
            n_tokens,
            symbol_size=symbol_size,
            chunks_per_block=chunks_per_block,
        )

    def decode_blob(
        self,
        blob,
        n_tokens,
        payload_sizes,
        *,
        symbol_size,
        chunk_symbols,
        n_chunks,
        chunks_per_block=None,
    ):
        from repro.kernels import ops  # lazy: kernels are optional at import

        return ops.lz_decode_mono(
            blob,
            n_tokens,
            payload_sizes,
            symbol_size=symbol_size,
            chunk_symbols=chunk_symbols,
            n_chunks=n_chunks,
            chunks_per_block=chunks_per_block,
        )


class ShardedDecoder:
    """Decode-side mirror of ``ShardedCompressor``: the batched entry point
    dispatches through the optional ``decompress_many`` hook, which shards
    the B dimension over the mesh passed at dispatch and runs the platform
    decoder per shard.  Per-chunk ``decode`` calls (and ``mesh=None``)
    degenerate to the platform decoder directly."""

    name = "sharded"

    def decode(
        self, flag_bytes, payload, n_tokens, *, symbol_size, chunks_per_block=None
    ):
        return get_decoder("auto").decode(
            flag_bytes,
            payload,
            n_tokens,
            symbol_size=symbol_size,
            chunks_per_block=chunks_per_block,
        )

    def decompress_many(
        self,
        blobs,
        n_tokens,
        payload_sizes,
        *,
        symbol_size,
        chunk_symbols,
        n_chunks,
        chunks_per_block,
        mesh,
        batch_axis,
        inner_decoder=None,
        method_params=(),
    ):
        from repro.sharding import batch as shbatch  # lazy: avoid cycle

        runner = shbatch.ShardedBatchRunner(mesh, batch_axis)
        return runner.decompress_many(
            blobs,
            n_tokens,
            payload_sizes,
            symbol_size=symbol_size,
            chunk_symbols=chunk_symbols,
            n_chunks=n_chunks,
            chunks_per_block=chunks_per_block,
            decoder="auto" if inner_decoder is None else inner_decoder,
            method_params=method_params,
        )


class EntropyDecoder:
    """Decoder for method-1 (entropy) containers: gap-array parallel Huffman
    bitstream decode (core/entropy.py) rebuilds the raw flag/payload
    sections, then the platform LZSS decode chain finishes.  Owns the whole
    container->symbols path via ``decode_blob``; the section-level
    ``decode`` (sections already un-entropied by definition) delegates to
    the platform decoder."""

    name = "deflate-full"
    container_method = fmt.METHOD_HUFFMAN

    def decode(
        self, flag_bytes, payload, n_tokens, *, symbol_size, chunks_per_block=None
    ):
        dec = get_decoder("auto")
        return dec.decode(
            flag_bytes,
            payload,
            n_tokens,
            symbol_size=symbol_size,
            **_geometry_kw(dec.decode, chunks_per_block),
        )

    def decode_blob(
        self,
        blob,
        n_tokens,
        payload_sizes,
        *,
        symbol_size,
        chunk_symbols,
        n_chunks,
        chunks_per_block=None,
    ):
        from repro.core import entropy  # lazy: entropy imports this module

        return entropy.decode_blob_entropy(
            blob,
            n_tokens,
            payload_sizes,
            symbol_size=symbol_size,
            chunk_symbols=chunk_symbols,
            n_chunks=n_chunks,
            chunks_per_block=chunks_per_block,
        )


class LossyFzDecoder:
    """Decoder for method-2 (lossy) containers (core/lossy.py): inner
    lossless decode -> bit-plane untranspose -> Lorenzo reconstruction +
    exact-outlier overlay.  Owns the whole container->symbols path via
    ``decode_blob``; the static ``(mode, inner_method)`` pair — trace-shape
    relevant but stored in the container — arrives through the
    ``method_params`` pin, recovered host-side from the header by
    ``static_params`` (see lzss.decompress)."""

    name = "lossy-fz"
    container_method = fmt.METHOD_LOSSY

    def static_params(self, header):
        from repro.core import lossy  # lazy: lossy imports this module

        return lossy.static_params(header)

    def decode(
        self, flag_bytes, payload, n_tokens, *, symbol_size, chunks_per_block=None
    ):
        raise ValueError(
            "lossy-fz containers (method byte 2) have no flag/payload "
            "sections; decode them through decode_blob (lzss.decompress)"
        )

    def decode_blob(
        self,
        blob,
        n_tokens,
        payload_sizes,
        *,
        symbol_size,
        chunk_symbols,
        n_chunks,
        chunks_per_block=None,
        method_params=(),
    ):
        from repro.core import lossy  # lazy: lossy imports this module

        if symbol_size != 4:
            raise ValueError(
                "lossy-fz containers hold f32 element streams "
                f"(symbol_size=4); got symbol_size={symbol_size}"
            )
        if len(method_params) != 2:
            raise ValueError(
                "lossy-fz decode requires method_params=(mode, inner_method) "
                "recovered from the container header; decode through "
                "lzss.decompress, or pass method_params explicitly"
            )
        mode, inner_method = method_params
        return lossy.decode_blob_lossy(
            blob,
            chunk_symbols=chunk_symbols,
            n_chunks=n_chunks,
            mode=mode,
            inner_method=inner_method,
        )


register_decoder(XlaParallelDecoder())
register_decoder(XlaScanDecoder())
register_decoder(FusedDecoder())
register_decoder(FusedMonoDecoder())
register_decoder(ShardedDecoder())
register_decoder(EntropyDecoder())
register_decoder(LossyFzDecoder())


# ------------------------------------------------------- symbol packing


def pack_symbols(data: jnp.ndarray, symbol_size: int) -> jnp.ndarray:
    """(n_bytes,) uint8 -> (n_sym,) int32 little-endian symbols (n_bytes % S == 0)."""
    d = data.reshape(-1, symbol_size).astype(jnp.int32)
    sym = d[:, 0]
    for b in range(1, symbol_size):
        sym = sym | (d[:, b] << (8 * b))
    return sym


def unpack_symbols(symbols: jnp.ndarray, symbol_size: int) -> jnp.ndarray:
    """(n_sym,) int32 -> (n_sym * S,) uint8 little-endian."""
    cols = [((symbols >> (8 * b)) & 0xFF) for b in range(symbol_size)]
    return jnp.stack(cols, axis=-1).reshape(-1).astype(jnp.uint8)


def _geometry_kw(method, chunks_per_block) -> dict:
    """kwargs forwarding the decode-side geometry pin to a decoder hook.

    The registry is an extension point: decoders registered before
    ``chunks_per_block`` reached the decode path don't take the kwarg, and
    must keep working.  The pin is forwarded only when the hook accepts it
    (explicitly or via ``**kwargs``); a decoder without the parameter never
    tiled on it anyway.  Runs at trace time only.
    """
    params = inspect.signature(method).parameters
    accepts = "chunks_per_block" in params or any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()
    )
    return {"chunks_per_block": chunks_per_block} if accepts else {}


def _optional_kw(method, **kv) -> dict:
    """Forward each kwarg only if ``method`` accepts it — the general form
    of ``_geometry_kw`` for registry hooks that predate newer pins
    (``chunks_per_block``, ``method_params``, ...).  Runs at trace time
    only."""
    params = inspect.signature(method).parameters
    var_kw = any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()
    )
    return {k: v for k, v in kv.items() if var_kw or k in params}


# ------------------------------------------------------- jittable cores


def _finalize_container(
    out, cfg, orig_bytes, *, nc, c, n_tokens, payload_sizes, flag_total, pay_total
):
    """Write header + A/B tables into a section-filled byte buffer.

    ``out`` is a (cap,) integer byte buffer whose flag/payload sections are
    already in place and whose header/table region [0, HEADER_BYTES + 8*nc)
    carries no live bytes — every emit tail produces exactly that.  Returns
    the finished ``(buffer u8, total_bytes)``.  The buffer narrows to u8
    before the header writes, so a wide working buffer is never copied.
    """
    s = cfg.symbol_size
    out = fmt.write_header_and_tables(
        out.astype(jnp.uint8),
        symbol_size=s,
        window=cfg.window,
        chunk_symbols=c,
        n_chunks=nc,
        orig_bytes=nc * c * s if orig_bytes is None else orig_bytes,
        payload_total=pay_total,
        flag_total=flag_total,
        n_tokens=n_tokens,
        payload_sizes=payload_sizes,
    )
    total = fmt.HEADER_BYTES + 8 * nc + flag_total + pay_total
    return out, total


def emit_xla(symbols, k1, cfg, orig_bytes=None):
    """Shared workflow-(c) emit tail: Kernels II+III as separate XLA ops.

    Packs flags and builds per-chunk payload buffers in HBM
    (``deflate.pack_flags`` / ``build_chunk_payloads``), runs the two global
    exclusive prefix sums (``deflate.global_offsets``, Kernel II), and
    scatters both sections into the container (``deflate.scatter_section``,
    Kernel III).  Backends without their own ``emit`` use this tail.
    """
    nc, c = symbols.shape
    s = cfg.symbol_size
    flag_bytes, flag_sizes = deflate.pack_flags(
        k1["emitted"], k1["use_match"], n_tokens=k1["n_tokens"]
    )
    payload = deflate.build_chunk_payloads(
        symbols, k1["lengths"], k1["offsets"], k1, symbol_size=s
    )
    pay_off, pay_total, flag_off, flag_total = deflate.global_offsets(
        k1["payload_sizes"], flag_sizes
    )
    cap = fmt.max_compressed_bytes(nc * c * s, s, c)
    sec_flags = fmt.HEADER_BYTES + 8 * nc
    out = jnp.zeros((cap,), jnp.int32)
    out = deflate.scatter_section(out, sec_flags, flag_bytes, flag_sizes, flag_off)
    out = deflate.scatter_section(
        out, sec_flags + flag_total, payload, k1["payload_sizes"], pay_off
    )
    return _finalize_container(
        out,
        cfg,
        orig_bytes,
        nc=nc,
        c=c,
        n_tokens=k1["n_tokens"],
        payload_sizes=k1["payload_sizes"],
        flag_total=flag_total,
        pay_total=pay_total,
    )


@functools.partial(jax.jit, static_argnames=("cfg",))
def compress_chunks(symbols: jnp.ndarray, cfg: LZSSConfig, orig_bytes=None):
    """Jittable core: (nc, C) int32 symbols -> (buffer u8[cap], total_bytes).

    The buffer holds a complete container (header + tables + flags + payload);
    bytes past ``total_bytes`` are zero.  ``orig_bytes`` (scalar, may be
    traced) is the true pre-padding byte count recorded in the header; when
    omitted the padded size ``nc * C * S`` is recorded.

    Both pipeline stages dispatch through the backend registry: Kernel I via
    ``backend.kernel1`` and the emit tail (Kernels II+III + header) via the
    backend's optional ``emit`` method, defaulting to the shared XLA tail
    ``emit_xla``.  A backend with no Kernel-I/emit seam (the single-kernel
    ``fused-mono``) owns the whole path via the optional ``compress`` hook
    instead.
    """
    return _compress_via(get_backend(cfg.backend), symbols, cfg, orig_bytes)


def _compress_via(backend, symbols, cfg, orig_bytes=None):
    """Run one backend's single-buffer pipeline, honoring its hooks."""
    whole = getattr(backend, "compress", None)
    if whole is not None:
        return whole(symbols, cfg, orig_bytes)
    k1 = backend.kernel1(symbols, cfg)
    emit = getattr(backend, "emit", emit_xla)
    return emit(symbols, k1, cfg, orig_bytes)


@functools.partial(
    jax.jit,
    static_argnames=(
        "symbol_size",
        "chunk_symbols",
        "n_chunks",
        "decoder",
        "chunks_per_block",
        "method_params",
    ),
)
def decompress_chunks(
    blob,
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
    """Jittable core: container bytes -> (nc, C) int32 symbols.

    ``blob`` may be any buffer that covers the container's live bytes — the
    section gathers are bounds-checked (clipped + masked), so no worst-case
    zero padding is required.  ``decoder`` is a registry key (or ``"auto"`` /
    a legacy alias), dispatched through ``get_decoder``.
    ``chunks_per_block`` pins the decode kernels' block geometry (``None``
    = the autotuner); it is format-invisible, so the pin only changes this
    function's static jit arguments, never the decoded symbols.

    A decoder owning the whole container->symbols path (the single-launch
    ``fused-mono``) is dispatched through its ``decode_blob`` hook here —
    the split gather+decode path below never runs for it.
    ``method_params`` carries static, trace-shape-relevant per-method
    parameters recovered from the container header (the lossy decoder's
    ``(mode, inner_method)``); it is forwarded only to hooks that accept it.
    """
    c, s, nc = chunk_symbols, symbol_size, n_chunks
    dec = get_decoder(decoder)
    whole = getattr(dec, "decode_blob", None)
    if whole is not None:
        return whole(
            blob,
            n_tokens,
            payload_sizes,
            symbol_size=s,
            chunk_symbols=c,
            n_chunks=nc,
            **_optional_kw(
                whole,
                chunks_per_block=chunks_per_block,
                method_params=method_params,
            ),
        )
    blob = blob.astype(jnp.int32)
    flag_sizes = (n_tokens + 7) // 8
    fcsum = jnp.cumsum(flag_sizes)
    pcsum = jnp.cumsum(payload_sizes)
    flag_off = fcsum - flag_sizes
    pay_off = pcsum - payload_sizes
    sec_flags = fmt.HEADER_BYTES + 8 * nc
    flag_bytes = deflate.gather_section(
        blob, sec_flags, flag_sizes, flag_off, (c + 7) // 8
    )
    payload = deflate.gather_section(
        blob, sec_flags + fcsum[-1], payload_sizes, pay_off, c * s
    )
    return dec.decode(
        flag_bytes,
        payload,
        n_tokens,
        symbol_size=s,
        **_geometry_kw(dec.decode, chunks_per_block),
    )


# --------------------------------------------------------- batched cores


@functools.partial(jax.jit, static_argnames=("cfg",))
def compress_many_chunks(symbols: jnp.ndarray, cfg: LZSSConfig, orig_bytes=None):
    """Batched in-graph compression: (B, nc, C) -> ((B, cap) u8, (B,) totals).

    One dispatch compresses B independent buffers; Kernel I runs for all
    B * nc chunks at once (the backend sees a vmapped batch), which is the
    paper's many-buffer scenario (cf. Sitaridi et al.'s massively-parallel
    batch decompression).  ``orig_bytes`` is an optional (B,) int32 vector of
    true per-buffer byte counts for the headers.

    A backend may own the whole batched dispatch via an optional
    ``compress_many`` method (the multi-device ``"sharded"`` entry partitions
    B over a mesh axis this way); the default is the vmapped single-buffer
    core — the same optional-hook pattern as ``emit``.
    """
    if orig_bytes is None:
        b, nc, c = symbols.shape
        orig_bytes = jnp.full((b,), nc * c * cfg.symbol_size, jnp.int32)
    backend = get_backend(cfg.backend)
    many = getattr(backend, "compress_many", None)
    if many is not None:
        return many(symbols, cfg, orig_bytes)
    return jax.vmap(lambda s_, o_: compress_chunks(s_, cfg, o_))(symbols, orig_bytes)


@functools.partial(
    jax.jit,
    static_argnames=(
        "symbol_size",
        "chunk_symbols",
        "n_chunks",
        "decoder",
        "chunks_per_block",
        "mesh",
        "batch_axis",
        "inner_decoder",
        "method_params",
    ),
)
def decompress_many_chunks(
    blobs,
    n_tokens,
    payload_sizes,
    *,
    symbol_size,
    chunk_symbols,
    n_chunks,
    decoder="auto",
    chunks_per_block=None,
    mesh=None,
    batch_axis=None,
    inner_decoder=None,
    method_params=(),
):
    """Batched inverse: (B, L) blobs + (B, nc) tables -> (B, nc, C) symbols.

    A decoder may own the whole batched dispatch via an optional
    ``decompress_many`` method — ``mesh``/``batch_axis`` are forwarded to it
    (the ``"sharded"`` entry partitions B over the mesh axis; other decoders
    never see them).  The default is the vmapped single-buffer core.
    ``chunks_per_block`` pins the decode kernels' block geometry, exactly
    as on ``decompress_chunks``.  ``inner_decoder`` names the per-shard
    decoder a batch-owning hook should run (``None`` = platform default;
    ``lzss.decompress_many`` sets it to ``"deflate-full"`` when a sharded
    batch holds entropy containers) — forwarded only to hooks that accept
    it, so decoders registered against the older hook signature keep
    working.
    """
    dec = get_decoder(decoder)
    many = getattr(dec, "decompress_many", None)
    if many is not None:
        inner_kw = {}
        if inner_decoder is not None:
            inner_kw = _optional_kw(many, inner_decoder=inner_decoder)
        if method_params:
            inner_kw.update(_optional_kw(many, method_params=method_params))
        return many(
            blobs,
            n_tokens,
            payload_sizes,
            symbol_size=symbol_size,
            chunk_symbols=chunk_symbols,
            n_chunks=n_chunks,
            mesh=mesh,
            batch_axis=batch_axis,
            **_geometry_kw(many, chunks_per_block),
            **inner_kw,
        )
    return jax.vmap(
        lambda b_, t_, p_: decompress_chunks(
            b_,
            t_,
            p_,
            symbol_size=symbol_size,
            chunk_symbols=chunk_symbols,
            n_chunks=n_chunks,
            decoder=decoder,
            chunks_per_block=chunks_per_block,
            method_params=method_params,
        )
    )(blobs, n_tokens, payload_sizes)


def tuned_config(symbol_size: int = 2, window: int = 128, **overrides) -> LZSSConfig:
    """An ``LZSSConfig`` with autotuned (chunk_symbols, chunks_per_block).

    Consults ``autotune.tuned_chunk_geometry`` — the joint sweep — for the
    current accelerator; with tuning disabled (CPU default, or
    ``REPRO_AUTOTUNE=0``) this is exactly ``LZSSConfig(...)`` with the
    static defaults.  ``chunk_symbols`` changes container bytes, so use
    this only when *creating* containers, never to reinterpret existing
    ones (their geometry is in the header).  Explicit ``chunk_symbols`` /
    ``chunks_per_block`` overrides win over the tuner.
    """
    c, g = autotune.tuned_chunk_geometry(symbol_size=symbol_size, window=window)
    overrides.setdefault("chunk_symbols", c)
    overrides.setdefault("chunks_per_block", g)
    return LZSSConfig(symbol_size=symbol_size, window=window, **overrides)


DEFAULT_CONFIG = LZSSConfig()  # paper default: C=2048, S=2, W=128

# window "levels" exposed to users (paper §3.2.3: level 1-4 trade ratio/speed)
WINDOW_LEVELS = {1: 32, 2: 64, 3: 128, 4: 255}
