"""Bit-plane transpose (bitshuffle) over uint16 unit streams.

FZ-GPU's (PAPERS.md) key pre-stage for error-bounded scientific data: after
dual-quant, most uint16 code bits are zero or slowly varying, but they are
*interleaved* across bit positions inside each unit.  Transposing each block
of units into bit planes groups the near-constant high bits into long byte
runs, which is exactly the shape the LZSS/deflate-full backends compress
well.

Layout (fixed, part of the method-2 wire format):

  * the stream is processed in blocks of ``BLOCK_UNITS = 512`` uint16 units
    (1024 bytes); callers pad to a multiple (padding value 0).
  * within a block, output plane ``b`` (b = 0..15, LSB first) is 64 bytes;
    its byte ``j`` packs bit ``b`` of units ``8j .. 8j+7``, unit ``8j`` in
    the byte's LSB.
  * blocks are emitted back to back, planes in order within each block, so
    the output byte count equals the input byte count.

Both directions are fixed-shape and fully in-graph (vmap/shard_map safe).
The Pallas kernels (kernels/lz_bitshuffle.py) are selected on TPU;
``REPRO_BITSHUFFLE_PALLAS=1/0`` overrides (same convention as
``REPRO_ENTROPY_PALLAS``), and the XLA path below is the reference both are
tested byte-identical against.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax import lax

BLOCK_UNITS = 512          # uint16 units per bitshuffle block
BLOCK_BYTES = BLOCK_UNITS * 2
PLANES = 16
PLANE_BYTES = BLOCK_UNITS // 8


def _use_pallas(impl) -> bool:
    """Impl selection, mirroring ``core.entropy._use_pallas``.

    ``impl`` is ``"pallas"`` / ``"xla"`` (explicit) or ``None`` (platform
    default: Pallas on TPU, XLA elsewhere; ``REPRO_BITSHUFFLE_PALLAS=1/0``
    overrides, e.g. to exercise the kernels in interpret mode off-TPU).
    """
    if impl in ("pallas", "xla"):
        return impl == "pallas"
    if impl is not None:
        raise ValueError(f"impl must be 'pallas', 'xla' or None: {impl!r}")
    env = os.environ.get("REPRO_BITSHUFFLE_PALLAS")
    if env is not None:
        return env != "0"
    return jax.default_backend() == "tpu"


def padded_units(n_units: int) -> int:
    """Smallest multiple of BLOCK_UNITS holding ``n_units``."""
    return -(-max(n_units, 1) // BLOCK_UNITS) * BLOCK_UNITS


def shuffle_xla(units: jnp.ndarray) -> jnp.ndarray:
    """(N,) uint16 -> (2N,) uint8 bit-plane transpose; N % 512 == 0."""
    n = units.shape[0]
    nb = n // BLOCK_UNITS
    u = units.reshape(nb, BLOCK_UNITS).astype(jnp.int32)
    planes = lax.broadcasted_iota(jnp.int32, (nb, BLOCK_UNITS, PLANES), 2)
    bits = (u[:, :, None] >> planes) & 1                   # (nb, 512, 16)
    bits = bits.reshape(nb, PLANE_BYTES, 8, PLANES)
    weight = lax.broadcasted_iota(jnp.int32, bits.shape, 2)
    packed = jnp.sum(bits << weight, axis=2)               # (nb, 64, 16)
    out = packed.transpose(0, 2, 1).reshape(nb * BLOCK_BYTES)
    return out.astype(jnp.uint8)


def unshuffle_xla(shuffled: jnp.ndarray) -> jnp.ndarray:
    """(2N,) uint8 -> (N,) uint16 inverse transpose; 2N % 1024 == 0."""
    nb = shuffled.shape[0] // BLOCK_BYTES
    p = shuffled.reshape(nb, PLANES, PLANE_BYTES).astype(jnp.int32)
    pos = lax.broadcasted_iota(
        jnp.int32, (nb, PLANES, PLANE_BYTES, 8), 3
    )
    bits = (p[:, :, :, None] >> pos) & 1                   # (nb, 16, 64, 8)
    bits = bits.transpose(0, 2, 3, 1)                      # (nb, 64, 8, 16)
    weight = lax.broadcasted_iota(jnp.int32, bits.shape, 3)
    vals = jnp.sum(bits << weight, axis=3)                 # (nb, 64, 8)
    return vals.reshape(nb * BLOCK_UNITS).astype(jnp.uint16)


def unshuffle_words_xla(words: jnp.ndarray) -> jnp.ndarray:
    """(N,) int32 byte pairs -> (N,) uint16 inverse transpose; N % 512 == 0.

    Word ``32b + k`` of a block is plane b's bytes 2k, 2k+1 (little-endian),
    so its bit t is bit b of unit 16k + t.
    """
    nb = words.shape[0] // BLOCK_UNITS
    w = words.reshape(nb, PLANES, BLOCK_UNITS // PLANES).astype(jnp.int32)
    t = lax.broadcasted_iota(jnp.int32, (nb, PLANES, BLOCK_UNITS // PLANES, 16), 3)
    bits = ((w[:, :, :, None] >> t) & 1).transpose(0, 2, 3, 1)  # (nb, 32, 16, 16)
    weight = lax.broadcasted_iota(jnp.int32, bits.shape, 3)
    vals = jnp.sum(bits << weight, axis=3)                 # (nb, 32, 16)
    return vals.reshape(nb * BLOCK_UNITS).astype(jnp.uint16)


def shuffle(units: jnp.ndarray, impl=None) -> jnp.ndarray:
    """Bit-plane transpose of a padded uint16 unit stream."""
    if units.shape[0] % BLOCK_UNITS:
        raise ValueError(
            f"bitshuffle input must be a multiple of {BLOCK_UNITS} units: "
            f"{units.shape[0]}"
        )
    if _use_pallas(impl):
        from repro.kernels import ops

        return ops.bitshuffle(units)
    return shuffle_xla(units)


def unshuffle(shuffled: jnp.ndarray, impl=None) -> jnp.ndarray:
    """Inverse of ``shuffle``; input length a multiple of 1024 bytes."""
    if shuffled.shape[0] % BLOCK_BYTES:
        raise ValueError(
            f"bitshuffle inverse input must be a multiple of {BLOCK_BYTES} "
            f"bytes: {shuffled.shape[0]}"
        )
    if _use_pallas(impl):
        from repro.kernels import ops

        return ops.bitunshuffle(shuffled)
    return unshuffle_xla(shuffled)


def unshuffle_words(words: jnp.ndarray, impl=None) -> jnp.ndarray:
    """``unshuffle`` of the shuffled bytes held as little-endian pairs.

    ``words[i]`` is byte 2i | byte 2i+1 << 8 — how the lossy decoder holds
    the stream (the inner container's u16 symbols), so no (N, 2) byte
    interleave is built; length a multiple of ``BLOCK_UNITS``.
    """
    if words.shape[0] % BLOCK_UNITS:
        raise ValueError(
            f"bitshuffle inverse input must be a multiple of {BLOCK_UNITS} "
            f"words: {words.shape[0]}"
        )
    if _use_pallas(impl):
        from repro.kernels import ops

        return ops.bitunshuffle_words(words)
    return unshuffle_words_xla(words)
