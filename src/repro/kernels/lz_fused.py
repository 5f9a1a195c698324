"""Single-kernel GPULZ compressor: Kernels I+II+III in ONE Pallas kernel.

The ``fused-deflate`` pipeline (kernels/lz_match.py + lz_scatter.py) still
splits matching from emit across three kernel launches, so the (nc, C)
match/flag/length intermediates of Kernel I round-trip through HBM before
the deflate-scatter re-reads them — the last HBM round-trip the paper's
workflow (d) removes (Fig. 4(c) vs (d); cf. the end-to-end-residency lesson
of Sitaridi et al., *Massively-Parallel Lossless Data Decompression*).  This
module folds the whole compressor into one kernel:

  * **Kernel I** per chunk block: multi-byte matching, the selection walk
    (on the block transposed once, so each serial step is one compare and
    one select on a row of chunks) and the local prefix sums (shared
    helpers ``_match_values`` / ``_select_and_scan`` from lz_match.py) —
    intermediates never leave VMEM.
  * **Kernel II** as a running carry: TPU grid steps execute sequentially,
    so BOTH global exclusive prefix sums degenerate to two SMEM scalars
    accumulated across blocks (the single-pass analogue of CUB's decoupled
    look-back) — no separate offsets kernel, no (nc,) offset arrays in HBM.
  * **Kernel III** as per-chunk DMA windows: the compact flag/payload bytes
    are rebuilt in VMEM (``_build_sections`` from lz_scatter.py) and
    written to the output blob at the carried offsets.  The blob lives in
    HBM (``memory_space=ANY``) and is only ever touched through per-chunk
    VMEM windows — unlike lz_scatter's (1, cap) VMEM-resident output block,
    so containers are not bounded by VMEM.  HBM rows are tiled in 128-lane
    units and Mosaic DMAs whole tiles only, so each byte-granular write is a
    read-modify-write of the vreg-aligned window covering it
    (``_write_window``).

Layout trick: a chunk's final payload offset depends on the TOTAL flag
section size, which a single forward sweep only knows after the last chunk.
The kernel therefore stages the payload stream at a fixed base past the
worst-case flag section and appends a short *slide* phase to the same grid:
after the last block, ``f_tot`` is known, and the remaining grid steps move
the staged payload down to ``sec_flags + f_tot`` one aligned window at a
time (a lane rotate by the destination's misalignment; windows are masked
to the live payload, so the slide simultaneously zero-fills everything from
the live end to ``cap`` — stale staging bytes included).  Forward order
makes the move hazard-free: every destination window lies at least a vreg
below every source window still to be read.

Byte-identity with the XLA pipeline is enforced by tests/test_pipeline.py,
tests/test_conformance.py and the golden corpus (tests/golden/); the
one-launch property by the pallas-call counter test.  The kernel compiles
for TPU v5e at real sizes (tests/test_tpu_compile.py); ``chip_smoke.py``
drives it on a chip and checks it byte-identical to the XLA backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import autotune

from repro.kernels.lz_decode import flag_lanes
from repro.kernels.lz_match import (
    LANES,
    MAX_LEN_CAP,
    _levels,
    _match_values,
    _pad_chunks,
    _round_up,
    _select_and_scan,
    batch_by_loop,
)
from repro.kernels.lz_scatter import _blend_window, _build_sections


def _write_window(out_ref, win_ref, sem, dst, data, n):
    """Read-modify-write ``n`` lanes of ``data`` at lane ``dst`` of HBM.

    HBM rows are tiled in 128-lane units, so the aligned window covering
    the destination is DMA'd into VMEM, blended, and DMA'd back.
    """
    r = dst % LANES
    lanes = pl.ds(pl.multiple_of(dst - r, LANES), win_ref.shape[1])
    rd = pltpu.make_async_copy(out_ref.at[:, lanes], win_ref, sem)
    rd.start()
    rd.wait()
    _blend_window(win_ref, data, r, n)
    wr = pltpu.make_async_copy(win_ref, out_ref.at[:, lanes], sem)
    wr.start()
    wr.wait()


def _mono_kernel(
    bt_ref,  # scalar prefetch: per-step block index (clamped past phase A)
    sym_ref,
    out_ref,  # (1, cap_alloc) int32 byte blob, HBM-resident (ANY)
    ntok_ref,
    psz_ref,
    tot_ref,
    fbuf,
    pbuf,
    fwin,
    pwin,
    slide_src,
    slide_dst,
    carry,  # SMEM [flag_off, pay_off] running across the sequential grid
    sems,
    *,
    window,
    max_len,
    min_match,
    symbol_size,
    nc,
    nb,
    sec_flags,
    stage,
):
    i = pl.program_id(0)
    g, c = sym_ref.shape
    s = symbol_size
    sw = slide_dst.shape[1]  # slide window: one block's worth of payload

    @pl.when(i == 0)
    def _init():
        carry[0] = 0
        carry[1] = 0

    @pl.when(i < nb)
    def _compress_block():
        # ---- Kernel I: match + select + local prefix sums, all in VMEM ----
        lengths, offsets = _match_values(
            sym_ref[...], window=window, max_len=max_len
        )
        emitted, um, _, local_off, psz, ntok = _select_and_scan(
            lengths, min_match=min_match, symbol_size=s
        )
        ntok_ref[...] = ntok
        psz_ref[...] = psz

        # ---- encode tail: compact section bytes for the whole block -------
        fbyte, prow = _build_sections(
            sym_ref[...],
            lengths,
            offsets,
            emitted.astype(jnp.int32),
            um.astype(jnp.int32),
            local_off,
            ntok,
            psz,
            symbol_size=s,
        )
        fbuf[...] = fbyte
        pbuf[...] = prow

        # ---- Kernels II+III: carry the global offsets, write the windows --
        # Payload goes to a staging base past the worst-case flag section
        # (final placement needs f_tot — see module docstring).  A row loop
        # (not an unrolled one) keeps the kernel's size independent of g.
        def emit_row(row, _):
            @pl.when(i * g + row < nc)
            def _():
                fo = carry[0]
                po = carry[1]
                fsz = (ntok_ref[pl.ds(row, 1), :] + 7) // 8
                prs = psz_ref[pl.ds(row, 1), :]
                _write_window(
                    out_ref,
                    fwin,
                    sems.at[0],
                    sec_flags + fo,
                    fbuf[pl.ds(row, 1), :],
                    fsz,
                )
                _write_window(
                    out_ref,
                    pwin,
                    sems.at[1],
                    stage + po,
                    pbuf[pl.ds(row, 1), :],
                    prs,
                )
                carry[0] = fo + fsz[0, 0]
                carry[1] = po + prs[0, 0]

            return 0

        lax.fori_loop(0, g, emit_row, 0)

    @pl.when(i >= nb)
    def _slide():
        # ---- slide phase: staged payload -> sec_flags + f_tot -------------
        # Output window k covers the aligned lanes [base + k*sw, +sw) with
        # base = dest - dest % 128; it needs staged bytes from k*sw - r on,
        # read through an aligned source window one vreg wider.  Lanes
        # below the destination keep the flag section's tail, lanes past
        # the live payload become zeros.
        k = i - nb
        f_tot = carry[0]
        p_tot = carry[1]

        @pl.when(i == nb)
        def _totals():
            lane = lax.broadcasted_iota(jnp.int32, tot_ref.shape, 1)
            tot_ref[...] = jnp.where(
                lane == 0, f_tot, jnp.where(lane == 1, p_tot, 0)
            )

        dest = sec_flags + f_tot
        r = dest % LANES
        src = pl.multiple_of(stage + k * sw - LANES, LANES)
        dst = pl.multiple_of(dest - r + k * sw, LANES)
        src_lanes = pl.ds(src, sw + LANES)
        dst_lanes = pl.ds(dst, sw)
        rd = pltpu.make_async_copy(
            out_ref.at[:, src_lanes], slide_src, sems.at[2]
        )
        old = pltpu.make_async_copy(
            out_ref.at[:, dst_lanes], slide_dst, sems.at[3]
        )
        rd.start()
        old.start()
        rd.wait()
        old.wait()
        moved = pltpu.roll(slide_src[...], sw + r, 1)[:, :sw]
        t = k * sw - r + lax.broadcasted_iota(jnp.int32, (1, sw), 1)
        slide_dst[...] = jnp.where(
            t < 0, slide_dst[...], jnp.where(t < p_tot, moved, 0)
        )
        wr = pltpu.make_async_copy(
            slide_dst, out_ref.at[:, dst_lanes], sems.at[2]
        )
        wr.start()
        wr.wait()


def _cost(nc, c, s, window, levels):
    # Kernel I dominates: per (position, offset) eq + doubling levels, plus
    # the section rebuild's binary searches and the slide's byte traffic.
    lg = _levels(c, c)
    flops = nc * c * (window * (2 + 3 * levels + 5) + 2 * lg + 8 + 4 * s)
    return pl.CostEstimate(
        flops=flops,
        bytes_accessed=nc * c * 4 + 3 * nc * ((c + 7) // 8 + c * s),
        transcendentals=0,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "window",
        "min_match",
        "symbol_size",
        "cap",
        "sec_flags",
        "max_len",
        "chunks_per_block",
        "interpret",
    ),
)
def lz_fused_mono_pallas(
    symbols,
    *,
    window,
    min_match,
    symbol_size,
    cap,
    sec_flags,
    max_len=MAX_LEN_CAP,
    chunks_per_block=8,
    interpret=False,
):
    """ONE kernel: (nc, C) int32 symbols -> deflated container sections.

    Returns ``(blob, n_tokens, payload_sizes, flag_total, pay_total)``:
    ``blob`` is a (cap,) uint8 buffer with the compact flag section at
    ``sec_flags``, the payload section right after it, zeros from the live
    end to ``cap``, and the header/table region [0, sec_flags) left for the
    caller to fill (``pipeline._finalize_container``); the (nc,) tables and
    the two section totals are the same values the split pipeline computes.
    """
    return batch_by_loop(
        functools.partial(
            _fused_mono,
            window=window,
            min_match=min_match,
            symbol_size=symbol_size,
            cap=cap,
            sec_flags=sec_flags,
            max_len=max_len,
            chunks_per_block=chunks_per_block,
            interpret=interpret,
        )
    )(symbols)


def _fused_mono(
    symbols,
    *,
    window,
    min_match,
    symbol_size,
    cap,
    sec_flags,
    max_len,
    chunks_per_block,
    interpret,
):
    x = symbols.astype(jnp.int32)
    nc, c = x.shape
    if c % 8:
        raise ValueError(f"chunk size must be a multiple of 8: {c}")
    g = chunks_per_block
    x, _ = _pad_chunks(x, g)
    npad = x.shape[0]
    nb = npad // g
    s = symbol_size
    fl = flag_lanes(c)
    bufp = _round_up(c * s, LANES)
    sw = g * bufp
    # staging base: vreg-aligned, one flag window plus a vreg of slack past
    # the worst-case flag section, so no flag window reaches staged payload
    # and the slide's destination stays a vreg below its source
    stage = _round_up(sec_flags + nc * (c // 8) + fl + LANES, LANES) + LANES
    # the slide covers every lane from the flag section's end to ``cap``
    nslide = -(-(cap - sec_flags + LANES) // sw)
    cap_alloc = _round_up(
        max(stage + nslide * sw, stage + nc * c * s + bufp + LANES, cap),
        LANES,
    )
    nsteps = nb + nslide
    bt = jnp.minimum(jnp.arange(nsteps, dtype=jnp.int32), nb - 1)
    col = pl.BlockSpec((g, 1), lambda i, bt_: (bt_[i], 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nsteps,),
        in_specs=[pl.BlockSpec((g, c), lambda i, bt_: (bt_[i], 0))],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            col,
            col,
            pl.BlockSpec((1, 128), lambda i, bt_: (0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, fl), jnp.int32),  # block flag bytes
            pltpu.VMEM((g, bufp), jnp.int32),  # block payload bytes
            pltpu.VMEM((1, fl + LANES), jnp.int32),  # flag write window
            pltpu.VMEM((1, bufp + LANES), jnp.int32),  # payload write window
            pltpu.VMEM((1, sw + LANES), jnp.int32),  # slide source window
            pltpu.VMEM((1, sw), jnp.int32),  # slide destination window
            pltpu.SMEM((2,), jnp.int32),  # running [flag_off, pay_off]
            pltpu.SemaphoreType.DMA((4,)),
        ],
    )
    out, ntok, psz, tot = pl.pallas_call(
        functools.partial(
            _mono_kernel,
            window=window,
            max_len=max_len,
            min_match=min_match,
            symbol_size=s,
            nc=nc,
            nb=nb,
            sec_flags=sec_flags,
            stage=stage,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((1, cap_alloc), jnp.int32),
            jax.ShapeDtypeStruct((npad, 1), jnp.int32),
            jax.ShapeDtypeStruct((npad, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 128), jnp.int32),
        ],
        cost_estimate=_cost(npad, c, s, window, _levels(window, max_len)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=autotune.VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
    )(bt, x)
    # narrowed here, fused with the slice: the int32-per-byte working blob
    # is 4x the container and is never copied whole
    blob = out[0, :cap].astype(jnp.uint8)
    return blob, ntok[:nc, 0], psz[:nc, 0], tot[0, 0], tot[0, 1]
