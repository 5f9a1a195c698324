"""Pallas bit-plane transpose kernels for the lossy-fz frontend.

FZ-GPU's bitshuffle stage (PAPERS.md) as two tiled TPU kernels mirroring
the layout fixed in core/bitshuffle.py: each 512-unit uint16 block becomes
16 bit planes of 64 bytes (LSB plane first, unit ``8j`` in each packed
byte's LSB).  Both directions are pure per-block permutations, so the grid
is embarrassingly parallel: one grid step transposes ``ROWS_PER_STEP``
independent blocks from a (nb, 512) uint16 view into a (nb, 1024) uint8
view, and back from the bytes taken as (nb, 512) little-endian pairs.

All arithmetic runs widened to int32 inside the kernel — the shift/mask
lattice lowers as plain vector ops; only the final store narrows to uint8 /
uint16.  Both are byte-identical to core/bitshuffle.py's XLA reference by
test (interpret mode on CPU) and compile for TPU v5e at real sizes
(tests/test_tpu_compile.py); the ``REPRO_BITSHUFFLE_PALLAS`` gate selects
them on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core.bitshuffle import BLOCK_BYTES, BLOCK_UNITS, PLANE_BYTES, PLANES

ROWS_PER_STEP = 8


def _shuffle_kernel(u_ref, out_ref):
    u = u_ref[...].astype(jnp.int32)                       # (g, 512)
    g = u.shape[0]
    plane = lax.broadcasted_iota(jnp.int32, (g, BLOCK_UNITS, PLANES), 2)
    bits = (u[:, :, None] >> plane) & 1
    bits = bits.reshape(g, PLANE_BYTES, 8, PLANES)
    weight = lax.broadcasted_iota(jnp.int32, bits.shape, 2)
    packed = jnp.sum(bits << weight, axis=2)               # (g, 64, 16)
    out = packed.transpose(0, 2, 1).reshape(g, BLOCK_BYTES)
    out_ref[...] = out.astype(jnp.uint8)


def _unshuffle_kernel(w_ref, out_ref):
    w = w_ref[...]                                         # (g, 512) int32
    g = w.shape[0]
    # the block's 1024 shuffled bytes as 512 little-endian pairs (byte 2k
    # | byte 2k+1 << 8): plane b's 64 bytes are words 32b .. 32b+31, in
    # lanes (b % 4) * 32 .. +32 of 128-lane block b // 4, and unit 16k + t
    # takes bit t of word 32b + k — one in-vreg gather per plane and 128
    # output units (a reshape to (g, 16, 32, 16) has no Mosaic lowering)
    u = lax.broadcasted_iota(jnp.int32, (g, BLOCK_UNITS), 1)
    vals = jnp.zeros((g, BLOCK_UNITS), jnp.int32)
    for b in range(PLANES):
        blk = w[:, (b // 4) * 128 : (b // 4 + 1) * 128]
        idx = (b % 4) * (PLANE_BYTES // 2) + u // 16
        word = jnp.concatenate(
            [
                jnp.take_along_axis(blk, idx[:, o * 128 : (o + 1) * 128], axis=1)
                for o in range(BLOCK_UNITS // 128)
            ],
            axis=1,
        )
        vals = vals | (((word >> (u % 16)) & 1) << b)
    out_ref[...] = vals.astype(jnp.uint16)


def _pad_rows(x: jnp.ndarray, rows: int) -> tuple[jnp.ndarray, int]:
    nb = x.shape[0]
    padded = -(-nb // rows) * rows
    if padded != nb:
        x = jnp.pad(x, ((0, padded - nb), (0, 0)))
    return x, padded


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitshuffle_pallas(units: jnp.ndarray, *, interpret: bool = False):
    """(N,) uint16 -> (2N,) uint8; N % BLOCK_UNITS == 0."""
    n = units.shape[0]
    nb = n // BLOCK_UNITS
    rows, padded = _pad_rows(units.reshape(nb, BLOCK_UNITS), ROWS_PER_STEP)
    out = pl.pallas_call(
        _shuffle_kernel,
        grid=(padded // ROWS_PER_STEP,),
        in_specs=[
            pl.BlockSpec((ROWS_PER_STEP, BLOCK_UNITS), lambda i: (i, 0))
        ],
        out_specs=pl.BlockSpec((ROWS_PER_STEP, BLOCK_BYTES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, BLOCK_BYTES), jnp.uint8),
        interpret=interpret,
    )(rows)
    return out[:nb].reshape(nb * BLOCK_BYTES)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitunshuffle_words_pallas(words: jnp.ndarray, *, interpret: bool = False):
    """(N,) int32 -> (N,) uint16; N % BLOCK_UNITS == 0.

    ``words`` is the shuffled byte stream as little-endian byte pairs
    (byte 2i | byte 2i+1 << 8) — the form the lossy decoder holds it in,
    as the inner container's u16 symbols.
    """
    nb = words.shape[0] // BLOCK_UNITS
    rows, padded = _pad_rows(
        words.astype(jnp.int32).reshape(nb, BLOCK_UNITS), ROWS_PER_STEP
    )
    spec = pl.BlockSpec((ROWS_PER_STEP, BLOCK_UNITS), lambda i: (i, 0))
    out = pl.pallas_call(
        _unshuffle_kernel,
        grid=(padded // ROWS_PER_STEP,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((padded, BLOCK_UNITS), jnp.uint16),
        interpret=interpret,
    )(rows)
    return out[:nb].reshape(nb * BLOCK_UNITS)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitunshuffle_pallas(shuffled: jnp.ndarray, *, interpret: bool = False):
    """(2N,) uint8 -> (N,) uint16; 2N % BLOCK_BYTES == 0."""
    b = shuffled.astype(jnp.int32)
    # strided halves, not a (N, 2) reshape: on TPU a 2-wide minor
    # dimension tiles to 128 lanes
    return bitunshuffle_words_pallas(b[0::2] | (b[1::2] << 8), interpret=interpret)
