"""Single-launch GPULZ decoder: section gathers fused into ONE Pallas kernel.

The split decode path (core/pipeline.py:decompress_chunks with the
``fused`` decoder) still stages the container through XLA before the kernel
sees it: two ``deflate.gather_section`` gathers materialize the (nc, C//8)
flag and (nc, C*S) payload blocks in HBM, and only then does
kernels/lz_decode.py run.  That is the decode-side analogue of the HBM
round-trip the fused-mono *compressor* (kernels/lz_fused.py) removed — and
decode is the serving-restore / KV-onlining hot path, where Sitaridi et al.
(*Massively-Parallel Lossless Data Decompression*, PAPERS.md) show
end-to-end kernel residency is what moves throughput.

This kernel reads the container blob straight from HBM instead: the blob is
passed whole with ``memory_space=ANY``, the per-chunk flag/payload byte
offsets (derived from the A/B tables core/format.py already carries) ride
scalar prefetch, and each grid step DMAs its block's section windows
directly into VMEM scratch before running the exact ``_decode_values``
chain of kernels/lz_decode.py.  ``deflate.gather_section`` drops out of the
decode path entirely: ONE launch per decompress.

Windows are fixed-width (C//8 flag bytes, C*S payload bytes per chunk —
the aligned per-chunk maxima), so a chunk's window may overrun its compact
section into the next chunk's bytes; lane masks against the true per-chunk
sizes zero those bytes, reproducing gather_section's zero-fill exactly.
HBM rows are tiled in 128-lane units and Mosaic DMAs only whole tiles, so
each byte-granular window is fetched as the vreg-aligned window one vreg
wider that covers it and rotated into place in VMEM (``fetch_window``).
The wrapper pads the blob so every such window stays in bounds.

Geometry (``chunks_per_block``) resolves through core/autotune.py at the
ops.py call site.  Byte-identity with the split decoders is enforced by
tests/test_decode_mono.py (S×W sweep vs the oracle + golden corpus) and the
one-launch property by its pallas-call counter test.

The kernel compiles for TPU v5e at real sizes (tests/test_tpu_compile.py);
``chip_smoke.py`` drives it on a chip and checks it byte-exact against the
input.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import autotune

from repro.core import format as fmt
from repro.kernels.lz_decode import (
    _ceil_log2,
    _decode_values,
    flag_lanes,
)
from repro.kernels.lz_match import LANES, _round_up, batch_by_loop


def fetch_window(src_ref, start, row_ref, sem, width):
    """DMA ``width`` lanes of a (1, L) HBM row from any lane ``start``.

    HBM rows are tiled in 128-lane units, so the copy reads the aligned
    window that covers ``[start, start + width)`` into the (1, width + 128)
    VMEM row ``row_ref`` and returns its lanes rotated down by
    ``start % 128`` (the first ``width`` lanes are the requested bytes).
    """
    r = start % LANES
    wpad = row_ref.shape[1]
    dma = pltpu.make_async_copy(
        src_ref.at[:, pl.ds(pl.multiple_of(start - r, LANES), wpad)],
        row_ref,
        sem,
    )
    dma.start()
    dma.wait()
    return pltpu.roll(row_ref[...], (wpad - r) % wpad, 1)[:, :width]


def _mono_decode_kernel(
    fofs_ref,  # scalar prefetch: (npad,) absolute flag-window byte offsets
    pofs_ref,  # scalar prefetch: (npad,) absolute payload-window byte offsets
    ntok_ref,  # (g, 1) per-chunk token counts
    psz_ref,  # (g, 1) per-chunk payload byte sizes
    blob_ref,  # (1, lpad) int32 container bytes, HBM-resident (ANY)
    out_ref,  # (g, C) decoded symbols
    fbuf,  # (g, flag_lanes) VMEM flag rows
    pbuf,  # (g, bufp) VMEM payload rows
    fwin,  # (1, flag_lanes + 128) DMA landing row
    pwin,  # (1, bufp + 128) DMA landing row
    sems,
    *,
    symbol_size,
    nc,
):
    i = pl.program_id(0)
    g, c = out_ref.shape
    s = symbol_size
    fl = fbuf.shape[1]
    bufp = pbuf.shape[1]

    # ---- fused gather: per-chunk section windows DMA'd straight from HBM --
    # (a row loop, not an unrolled one, keeps the kernel's size independent
    # of g; rows past nc hold zeros)
    def fetch_row(row, _):
        ci = i * g + row

        @pl.when(ci < nc)
        def _():
            fbuf[pl.ds(row, 1), :] = fetch_window(
                blob_ref, fofs_ref[ci], fwin, sems.at[0], fl
            )
            pbuf[pl.ds(row, 1), :] = fetch_window(
                blob_ref, pofs_ref[ci], pwin, sems.at[1], bufp
            )

        @pl.when(ci >= nc)
        def _():
            fbuf[pl.ds(row, 1), :] = jnp.zeros((1, fl), jnp.int32)
            pbuf[pl.ds(row, 1), :] = jnp.zeros((1, bufp), jnp.int32)

        return 0

    lax.fori_loop(0, g, fetch_row, 0)

    # Mask each fixed-width window to its chunk's true section size: the
    # overrun bytes (the next chunk's data) become the zeros
    # deflate.gather_section would have produced.
    nt = ntok_ref[...]
    lane_f = lax.broadcasted_iota(jnp.int32, (g, fl), 1)
    flags = jnp.where(lane_f < (nt + 7) // 8, fbuf[...], 0)
    lane_p = lax.broadcasted_iota(jnp.int32, (g, bufp), 1)
    payload = jnp.where(lane_p < psz_ref[...], pbuf[...], 0)

    out_ref[...] = _decode_values(
        flags, payload, nt, symbol_size=s, chunk_symbols=c
    )


def _cost(nc, c, s):
    lg = _ceil_log2(c)
    flops = nc * c * (8 * lg + s + 12)
    return pl.CostEstimate(
        flops=flops,
        # sections in (via DMA windows) + tables + symbols out
        bytes_accessed=nc * ((c + 7) // 8 + c * s) * 4 + nc * 8 + nc * c * 4,
        transcendentals=0,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "symbol_size",
        "chunk_symbols",
        "n_chunks",
        "chunks_per_block",
        "interpret",
    ),
)
def lz_decode_mono_pallas(
    blob,
    n_tokens,
    payload_sizes,
    *,
    symbol_size,
    chunk_symbols,
    n_chunks,
    chunks_per_block=8,
    interpret=False,
):
    """ONE launch: container byte blob -> (nc, C) int32 symbols.

    ``blob`` is the whole container (any integer dtype, >= the live
    container bytes; trailing padding is ignored), ``n_tokens`` /
    ``payload_sizes`` the (nc,) A/B tables ``format.validate_container``
    returns.  The per-chunk section offsets are reduced to two cumsums here
    and prefetched as scalars — no gathered section arrays ever exist.
    """
    return batch_by_loop(
        functools.partial(
            _decode_mono,
            symbol_size=symbol_size,
            chunk_symbols=chunk_symbols,
            n_chunks=n_chunks,
            chunks_per_block=chunks_per_block,
            interpret=interpret,
        )
    )(blob, n_tokens, payload_sizes)


def _decode_mono(
    blob,
    n_tokens,
    payload_sizes,
    *,
    symbol_size,
    chunk_symbols,
    n_chunks,
    chunks_per_block,
    interpret,
):
    c, s, nc = chunk_symbols, symbol_size, n_chunks
    if c % 8:
        raise ValueError(f"chunk size must be a multiple of 8: {c}")
    g = chunks_per_block
    fl = flag_lanes(c)
    bufp = _round_up(c * s, LANES)

    b = blob.astype(jnp.int32).reshape(1, -1)
    # pad so every aligned fetch window stays in bounds; lane-align
    lpad = _round_up(b.shape[1] + bufp + 2 * LANES, LANES)
    b = jnp.pad(b, ((0, 0), (0, lpad - b.shape[1])))

    nt = n_tokens.astype(jnp.int32)
    psz = payload_sizes.astype(jnp.int32)
    fsz = (nt + 7) // 8
    fcs = jnp.cumsum(fsz)
    pcs = jnp.cumsum(psz)
    sec_flags = fmt.HEADER_BYTES + 8 * nc
    # absolute section starts; the clamp only guards pathological tables
    # (a validated container never comes near it)
    fofs = jnp.clip(sec_flags + fcs - fsz, 0, lpad - fl - LANES)
    pofs = jnp.clip(sec_flags + fcs[-1] + pcs - psz, 0, lpad - bufp - LANES)

    pad = (-nc) % g
    nt = jnp.pad(nt, (0, pad)).reshape(-1, 1)
    psz = jnp.pad(psz, (0, pad)).reshape(-1, 1)
    fofs = jnp.pad(fofs, (0, pad))
    pofs = jnp.pad(pofs, (0, pad))
    npad = nc + pad

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(npad // g,),
        in_specs=[
            pl.BlockSpec((g, 1), lambda i, fo_, po_: (i, 0)),
            pl.BlockSpec((g, 1), lambda i, fo_, po_: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((g, c), lambda i, fo_, po_: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, fl), jnp.int32),
            pltpu.VMEM((g, bufp), jnp.int32),
            pltpu.VMEM((1, fl + LANES), jnp.int32),
            pltpu.VMEM((1, bufp + LANES), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_mono_decode_kernel, symbol_size=s, nc=nc),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((npad, c), jnp.int32),
        cost_estimate=_cost(npad, c, s),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=autotune.VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
    )(fofs, pofs, nt, psz, b)
    return out[:nc]
