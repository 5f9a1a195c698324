"""Jitted public wrappers for the Pallas kernels.

On non-TPU backends the kernels run in interpret mode (Python execution of the
kernel body) so the whole framework — including the `pallas-match`, `fused`,
`fused-deflate` and `fused-mono` pipeline backends (core/pipeline.py) — is
testable on CPU.  On TPU they compile via Mosaic.

Every wrapper takes ``chunks_per_block=None`` by default: the block geometry
then resolves through core/autotune.py (per-architecture tuned cache on TPU,
the deterministic static fallback elsewhere / under ``REPRO_AUTOTUNE=0``).
Passing an explicit integer pins the geometry and bypasses the autotuner.
"""

from __future__ import annotations

import jax

from repro.core import autotune
from repro.kernels import lz_bitshuffle as _bshuf_impl
from repro.kernels import lz_decode as _dec_impl
from repro.kernels import lz_decode_mono as _dmono_impl
from repro.kernels import lz_entropy as _ent_impl
from repro.kernels import lz_fused as _mono_impl
from repro.kernels import lz_match as _impl
from repro.kernels import lz_scatter as _scat_impl


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _blocks(chunks_per_block, *, symbol_size, chunk_symbols, direction, window=0):
    """Resolve a ``chunks_per_block=None`` default through the autotuner."""
    if chunks_per_block is not None:
        return chunks_per_block
    return autotune.block_geometry(
        symbol_size=symbol_size,
        chunk_symbols=chunk_symbols,
        direction=direction,
        window=window,
    )


def lz_match(
    symbols, *, window, max_len=_impl.MAX_LEN_CAP, chunks_per_block=None
):
    """(nc, C) int32 symbols -> (lengths, offsets)."""
    return _impl.lz_match_pallas(
        symbols,
        window=window,
        max_len=max_len,
        chunks_per_block=_blocks(
            chunks_per_block,
            symbol_size=4,
            chunk_symbols=symbols.shape[1],
            direction="compress",
            window=window,
        ),
        interpret=_interpret(),
    )


def lz_kernel1(
    symbols,
    *,
    window,
    min_match,
    symbol_size,
    max_len=_impl.MAX_LEN_CAP,
    chunks_per_block=None,
):
    """Fused Kernel I (match + select + local prefix sum)."""
    return _impl.lz_kernel1_pallas(
        symbols,
        window=window,
        min_match=min_match,
        symbol_size=symbol_size,
        max_len=max_len,
        chunks_per_block=_blocks(
            chunks_per_block,
            symbol_size=symbol_size,
            chunk_symbols=symbols.shape[1],
            direction="compress",
            window=window,
        ),
        interpret=_interpret(),
    )


def lz_scatter(
    symbols,
    lengths,
    offsets,
    emitted,
    use_match,
    local_off,
    n_tokens,
    payload_sizes,
    *,
    symbol_size,
    cap,
    sec_flags,
    chunks_per_block=None,
):
    """Fused Kernel II+III (global offsets + deflate-scatter).

    Returns ``(blob, flag_total, pay_total)``: a (cap,) int32 byte buffer
    holding the deflated flag/payload sections (header region left zero)
    plus the two traced section totals.
    """
    return _scat_impl.lz_scatter_pallas(
        symbols,
        lengths,
        offsets,
        emitted,
        use_match,
        local_off,
        n_tokens,
        payload_sizes,
        symbol_size=symbol_size,
        cap=cap,
        sec_flags=sec_flags,
        chunks_per_block=_blocks(
            chunks_per_block,
            symbol_size=symbol_size,
            chunk_symbols=symbols.shape[1],
            direction="compress",
        ),
        interpret=_interpret(),
    )


def lz_fused_mono(
    symbols,
    *,
    window,
    min_match,
    symbol_size,
    cap,
    sec_flags,
    max_len=_impl.MAX_LEN_CAP,
    chunks_per_block=None,
):
    """Single-kernel compressor (Kernels I+II+III folded, tiled output).

    Returns ``(blob, n_tokens, payload_sizes, flag_total, pay_total)``: one
    Pallas launch produces the deflated flag/payload sections of a container
    (header region left zero) plus the per-chunk tables and section totals.
    """
    return _mono_impl.lz_fused_mono_pallas(
        symbols,
        window=window,
        min_match=min_match,
        symbol_size=symbol_size,
        cap=cap,
        sec_flags=sec_flags,
        max_len=max_len,
        chunks_per_block=_blocks(
            chunks_per_block,
            symbol_size=symbol_size,
            chunk_symbols=symbols.shape[1],
            direction="compress",
            window=window,
        ),
        interpret=_interpret(),
    )


def lz_decode(
    flag_bytes, payload, n_tokens, *, symbol_size, chunks_per_block=None
):
    """Fused decoder (flag scan + payload gather + copy resolution)."""
    return _dec_impl.lz_decode_pallas(
        flag_bytes,
        payload,
        n_tokens,
        symbol_size=symbol_size,
        chunks_per_block=_blocks(
            chunks_per_block,
            symbol_size=symbol_size,
            chunk_symbols=flag_bytes.shape[1] * 8,
            direction="decompress",
        ),
        interpret=_interpret(),
    )


def byte_histogram(buf, start, length):
    """(n,) int32 byte buffer -> (256,) counts of [start, start+length).

    The entropy stage's code-length front end (core/entropy.py); the
    sequential-grid Pallas reduction, identical counts to the XLA
    scatter-add fallback by test."""
    return _ent_impl.byte_histogram_pallas(
        buf, start, length, interpret=_interpret()
    )


def huffman_gap_decode(blob, wstarts, rems, first, count, base, order, *, sub):
    """Gap-array parallel canonical-Huffman bitstream decode (one launch).

    See kernels/lz_entropy.py; block geometry is fixed (8 sub-block lanes
    per grid step) — sub-block windows are DMA-width-bound, not
    VMEM-budget-bound like the LZSS kernels, so the autotuner is not
    consulted here."""
    return _ent_impl.huffman_gap_decode_pallas(
        blob,
        wstarts,
        rems,
        first,
        count,
        base,
        order,
        sub=sub,
        interpret=_interpret(),
    )


def bitshuffle(units):
    """(N,) uint16 -> (2N,) uint8 bit-plane transpose (lossy-fz frontend).

    Fixed per-block geometry (512-unit blocks, 8 blocks per grid step) —
    a pure permutation with no VMEM-budget trade-off, so the autotuner is
    not consulted."""
    return _bshuf_impl.bitshuffle_pallas(units, interpret=_interpret())


def bitunshuffle(shuffled):
    """(2N,) uint8 -> (N,) uint16 inverse bit-plane transpose."""
    return _bshuf_impl.bitunshuffle_pallas(shuffled, interpret=_interpret())


def bitunshuffle_words(words):
    """(N,) int32 little-endian byte pairs -> (N,) uint16 inverse transpose."""
    return _bshuf_impl.bitunshuffle_words_pallas(words, interpret=_interpret())


def lz_decode_mono(
    blob,
    n_tokens,
    payload_sizes,
    *,
    symbol_size,
    chunk_symbols,
    n_chunks,
    chunks_per_block=None,
):
    """Single-launch decoder: whole container blob -> (nc, C) symbols.

    The flag/payload section gathers are fused into the decode kernel via
    scalar-prefetched per-chunk offsets — no ``deflate.gather_section``."""
    return _dmono_impl.lz_decode_mono_pallas(
        blob,
        n_tokens,
        payload_sizes,
        symbol_size=symbol_size,
        chunk_symbols=chunk_symbols,
        n_chunks=n_chunks,
        chunks_per_block=_blocks(
            chunks_per_block,
            symbol_size=symbol_size,
            chunk_symbols=chunk_symbols,
            direction="decompress",
        ),
        interpret=_interpret(),
    )
