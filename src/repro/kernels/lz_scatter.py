"""Pallas TPU kernels for fused GPULZ deflating (Kernels II+III).

After Kernel I, the XLA pipeline tail still stages every intermediate
through HBM as separate ops (the paper's workflow (c)):
``deflate.pack_flags`` and ``deflate.build_chunk_payloads`` materialize the
(nc, C//8) flag and (nc, C*S) payload sections, ``deflate.global_offsets``
runs two XLA cumsums (Kernel II), and ``deflate.scatter_section`` re-reads
both sections to assemble the blob (Kernel III).  This module fuses that
whole emit path (workflow (d); cf. the stream-compaction lesson of
Sitaridi et al., *Massively-Parallel Lossless Data Decompression*): the
compressed sections are rebuilt in VMEM per chunk block straight from the
Kernel-I outputs and written to the output blob exactly once — the aligned
(nc, C//8) / (nc, C*S) section arrays never exist in HBM.

Two passes, mirroring the paper's Kernel II -> III split:

  pass 1 (``_offsets_kernel``)   ONE kernel computes BOTH exclusive prefix
      sums over the per-chunk flag/payload sizes (the paper calls CUB
      ``DeviceScan::ExclusiveSum`` twice) via lane-shift doubling, plus the
      two section totals; payload offsets come out pre-based past the flag
      section so pass 2 needs no extra scalar math.
  pass 2 (``_scatter_kernel``)   per chunk block, rebuilds the flag bytes
      and payload bytes in VMEM from the Kernel-I arrays (a rank->position
      binary search — the gather-friendly inverse of ``pack_flags``'s
      scatter-add, which has no efficient Mosaic lowering) and blends each
      chunk's compact prefix into the output blob at its global offset
      (read-modify-write of the vreg-aligned window that covers it).
      The per-chunk offsets ride in as scalar-prefetch operands
      (``pltpu.PrefetchScalarGridSpec``), so every dynamic store address is
      an SMEM scalar read; the blob block is revisited across the grid and
      written back to HBM once.

Like the other kernels, correctness is validated in interpret mode against
the XLA tail (tests/test_kernels.py); byte-identity of full containers is
enforced by tests/test_pipeline.py, and tests/test_tpu_compile.py compiles
it for TPU v5e.  The whole blob is one VMEM-resident block, so containers
are bounded by ``SCATTER_VMEM_BYTES`` (a clear ValueError above it); the
``fused-mono`` compressor has no such bound.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.lz_decode import (
    _ceil_log2,
    _prefix_sum_excl,
    _search_last_le,
    flag_lanes,
)
from repro.kernels.lz_match import (
    LANES,
    _round_up,
    batch_by_loop,
    bit_pack,
    lane_gather,
)


# The deflate-scatter keeps the whole (1, cap) blob in one VMEM block.
SCATTER_VMEM_BYTES = 32 * 2**20

# ---------------------------------------------------- pass 1: Kernel II


def _offsets_kernel(nt_ref, ps_ref, fo_ref, po_ref, tot_ref, *, nc):
    _, n = nt_ref.shape
    idx = lax.broadcasted_iota(jnp.int32, (1, n), 1)
    fs = (nt_ref[...] + 7) // 8
    ps = ps_ref[...]
    f_excl = _prefix_sum_excl(fs, idx, n)
    p_excl = _prefix_sum_excl(ps, idx, n)
    f_tot = f_excl[0, nc - 1] + fs[0, nc - 1]
    p_tot = p_excl[0, nc - 1] + ps[0, nc - 1]
    fo_ref[...] = f_excl
    # payload offsets pre-based past the flag section
    po_ref[...] = p_excl + f_tot
    tot_ref[...] = jnp.where(idx == 0, f_tot, jnp.where(idx == 1, p_tot, 0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def lz_global_offsets_pallas(n_tokens, payload_sizes, *, interpret=False):
    """Fused Kernel II: (nc,) per-chunk sizes -> global section offsets.

    Returns ``(flag_off, pay_off, flag_total, pay_total)``: both exclusive
    prefix sums computed in ONE kernel (flag sizes are derived from
    ``n_tokens`` in-kernel); ``pay_off`` is pre-based past the flag section
    (``flag_total + excl_cumsum(payload_sizes)``).  The offset vectors come
    back at the kernel's 128-lane padding (>= nc); a consumer indexing past
    that (a different grid padding) must extend them itself — see
    ``lz_scatter_pallas``.
    """
    nt = n_tokens.astype(jnp.int32)
    ps = payload_sizes.astype(jnp.int32)
    nc = nt.shape[0]
    npad = -(-nc // 128) * 128
    pad = npad - nc
    if pad:
        nt = jnp.concatenate([nt, jnp.zeros((pad,), jnp.int32)])
        ps = jnp.concatenate([ps, jnp.zeros((pad,), jnp.int32)])
    spec = pl.BlockSpec((1, npad), lambda: (0, 0))
    fo, po, tot = pl.pallas_call(
        functools.partial(_offsets_kernel, nc=nc),
        in_specs=[spec, spec],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct((1, npad), jnp.int32)] * 3,
        interpret=interpret,
    )(nt.reshape(1, npad), ps.reshape(1, npad))
    return fo[0], po[0], tot[0, 0], tot[0, 1]


# --------------------------------------- pass 2: encode tail + Kernel III


def _build_sections(
    sym, lengths, offsets, emitted, um, local_off, ntok, psz, *, symbol_size
):
    """Rebuild the per-chunk compact section bytes from Kernel-I outputs.

    All inputs are int32 values: (g, C) per-position arrays plus the (g, 1)
    per-chunk ``ntok``/``psz`` reductions.  Returns ``(flag_bytes (g,
    flag_lanes(C)), payload (g, round_up(C*S, 128)))`` with zeros past each
    chunk's live size — everything stays in registers/VMEM (rank->position
    binary search instead of ``pack_flags``'s scatter-add, which has no
    efficient Mosaic lowering).  Shared by the deflate-scatter kernel below
    and the single-kernel compressor (lz_fused.py).
    """
    g, c = sym.shape
    s = symbol_size
    cb = c // 8
    bufsz = c * s
    t = lax.broadcasted_iota(jnp.int32, (g, c), 1)

    # token rank -> chunk position: ranks[i] = tokens before position i is
    # nondecreasing, so the position of rank r is the last i with
    # ranks[i] <= r (pack_flags computes the same map as a scatter-add).
    ranks = _prefix_sum_excl(emitted, t, c)
    tok_pos = _search_last_le(ranks, t, c)
    valid_r = t < ntok

    # per-token fields in rank space; pointer bytes (<= 255) and the kind
    # bit share one word so the byte-space expansion below gathers it once
    ptr_r = lane_gather(lengths | (offsets << 8) | (um << 16), tok_pos)
    fbit = jnp.where(valid_r, ptr_r >> 16, 0)
    sym_r = lane_gather(sym, tok_pos)

    # flag bytes: bit j of byte b is token (8b+j)'s kind (format.py layout)
    fbyte = bit_pack(fbit, flag_lanes(c))

    # token write offsets in rank space (sentinel bufsz keeps the row
    # sorted past n_tokens), then payload byte p -> covering token
    tok_off = jnp.where(valid_r, lane_gather(local_off, tok_pos), bufsz)
    p = lax.broadcasted_iota(jnp.int32, (g, _round_up(bufsz, LANES)), 1)
    r_of_p = _search_last_le(tok_off, p, c)
    b_p = p - lane_gather(tok_off, r_of_p)
    ptr_p = lane_gather(ptr_r, r_of_p)
    sym_p = lane_gather(sym_r, r_of_p)
    ptr = jnp.where(b_p == 0, ptr_p & 0xFF, (ptr_p >> 8) & 0xFF)
    lit = (sym_p >> (8 * jnp.clip(b_p, 0, 3))) & 0xFF
    val = jnp.where((ptr_p >> 16) == 1, ptr, lit)
    prow = jnp.where(p < psz, val, 0)
    return fbyte, prow


def _blend_window(win_ref, data, r, n):
    """Write the first ``n`` lanes of ``data`` at lane ``r`` of ``win_ref``.

    ``win_ref`` is a (1, W) VMEM row holding a vreg-aligned window of the
    destination; every other lane keeps what it holds.  A byte-granular
    store built from a lane rotate and a mask — Mosaic has no unaligned
    lane store.
    """
    w = win_ref.shape[1]
    pad = w - data.shape[1]
    wide = jnp.concatenate([data, jnp.zeros((1, pad), data.dtype)], axis=1)
    lane = lax.broadcasted_iota(jnp.int32, (1, w), 1)
    live = (lane >= r) & (lane < r + n)
    win_ref[...] = jnp.where(live, pltpu.roll(wide, r, 1), win_ref[...])


def _scatter_kernel(
    fo_ref,
    po_ref,
    sym_ref,
    len_ref,
    off_ref,
    emit_ref,
    um_ref,
    lo_ref,
    nt_ref,
    ps_ref,
    out_ref,
    fwin,
    pwin,
    *,
    symbol_size,
    sec_flags,
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    g, c = sym_ref.shape
    fbyte, prow = _build_sections(
        sym_ref[...],
        len_ref[...],
        off_ref[...],
        emit_ref[...],
        um_ref[...],
        lo_ref[...],
        nt_ref[...],
        ps_ref[...],
        symbol_size=symbol_size,
    )

    # Kernel III: blend each chunk's compact prefix into the blob at its
    # global offset (read-modify-write of a vreg-aligned window; grid steps
    # run sequentially).  Offsets are SMEM scalar reads.
    nt = nt_ref[...]
    ps = ps_ref[...]
    for row in range(g):
        ci = i * g + row
        for win, dst, data, n in (
            (
                fwin,
                sec_flags + fo_ref[ci],
                fbyte[row : row + 1, :],
                (nt[row : row + 1, :] + 7) // 8,
            ),
            (
                pwin,
                sec_flags + po_ref[ci],
                prow[row : row + 1, :],
                ps[row : row + 1, :],
            ),
        ):
            r = dst % LANES
            lanes = pl.ds(pl.multiple_of(dst - r, LANES), win.shape[1])
            win[...] = out_ref[:, lanes]
            _blend_window(win, data, r, n)
            out_ref[:, lanes] = win[...]


def _cost(nc, c, s):
    lg = _ceil_log2(c)
    # two binary searches + flag pack + payload build per position
    flops = nc * c * (2 * lg + 8 + 4 * s)
    return pl.CostEstimate(
        flops=flops,
        bytes_accessed=nc * c * 4 * 6 + nc * ((c + 7) // 8 + c * s),
        transcendentals=0,
    )


def _pad_rows(x, pad):
    if not pad:
        return x
    zeros = jnp.zeros((pad,) + x.shape[1:], x.dtype)
    return jnp.concatenate([x, zeros], axis=0)


@functools.partial(
    jax.jit,
    static_argnames=(
        "symbol_size",
        "cap",
        "sec_flags",
        "chunks_per_block",
        "interpret",
    ),
)
def lz_scatter_pallas(
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
    chunks_per_block=8,
    interpret=False,
):
    """Fused deflate-scatter: Kernel-I outputs -> (blob, flag_total, pay_total).

    ``blob`` is a (cap,) int32 byte buffer with the compact flag section at
    ``sec_flags`` and the payload section right after it — the bytes
    ``deflate.scatter_section`` would have produced, with the header/table
    region [0, sec_flags) left zero for the caller to fill.
    """
    return batch_by_loop(
        functools.partial(
            _scatter,
            symbol_size=symbol_size,
            cap=cap,
            sec_flags=sec_flags,
            chunks_per_block=chunks_per_block,
            interpret=interpret,
        )
    )(
        symbols,
        lengths,
        offsets,
        emitted,
        use_match,
        local_off,
        n_tokens,
        payload_sizes,
    )


def _scatter(
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
    chunks_per_block,
    interpret,
):
    fo, po, f_tot, p_tot = lz_global_offsets_pallas(
        n_tokens, payload_sizes, interpret=interpret
    )
    nc, c = symbols.shape
    g = chunks_per_block
    pad = (-nc) % g
    # the scatter grid covers nc+pad chunks; when that exceeds pass 1's
    # 128-lane padding (g does not divide 128 and nc is a lane multiple),
    # extend the scalar-prefetch offsets so fo_ref[ci]/po_ref[ci] stay in
    # bounds.  Zero is safe: padded rows have zero-width windows, so their
    # RMW blend at the (clamped, in-bounds) destination stores back what it
    # loaded.
    short = nc + pad - fo.shape[0]
    if short > 0:
        fo = jnp.concatenate([fo, jnp.zeros((short,), jnp.int32)])
        po = jnp.concatenate([po, jnp.zeros((short,), jnp.int32)])
    sym = _pad_rows(symbols.astype(jnp.int32), pad)
    lens = _pad_rows(lengths.astype(jnp.int32), pad)
    offs = _pad_rows(offsets.astype(jnp.int32), pad)
    emit = _pad_rows(emitted.astype(jnp.int32), pad)
    um = _pad_rows(use_match.astype(jnp.int32), pad)
    lo = _pad_rows(local_off.astype(jnp.int32), pad)
    nt = _pad_rows(n_tokens.astype(jnp.int32).reshape(-1, 1), pad)
    ps = _pad_rows(payload_sizes.astype(jnp.int32).reshape(-1, 1), pad)
    npad = nc + pad
    fl = flag_lanes(c)
    bufp = _round_up(c * symbol_size, LANES)
    # the whole blob is one VMEM-resident block, with room for the last
    # window to overhang ``cap``
    cap_alloc = _round_up(cap + bufp + 2 * LANES, LANES)
    if 4 * cap_alloc > SCATTER_VMEM_BYTES:
        raise ValueError(
            f"lz_scatter keeps the whole container in VMEM: cap={cap} bytes "
            f"needs {4 * cap_alloc / 2**20:.1f} MiB, over the "
            f"{SCATTER_VMEM_BYTES / 2**20:.0f} MiB budget; use the fused-mono "
            "compressor, which writes through HBM windows"
        )
    spec2d = pl.BlockSpec((g, c), lambda i, fo_, po_: (i, 0))
    spec1d = pl.BlockSpec((g, 1), lambda i, fo_, po_: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(npad // g,),
        in_specs=[spec2d] * 6 + [spec1d] * 2,
        out_specs=pl.BlockSpec((1, cap_alloc), lambda i, fo_, po_: (0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, fl + LANES), jnp.int32),
            pltpu.VMEM((1, bufp + LANES), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _scatter_kernel,
            symbol_size=symbol_size,
            sec_flags=sec_flags,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, cap_alloc), jnp.int32),
        cost_estimate=_cost(npad, c, symbol_size),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=SCATTER_VMEM_BYTES + 16 * 2**20
        ),
        interpret=interpret,
    )(fo, po, sym, lens, offs, emit, um, lo, nt, ps)
    return out[0, :cap], f_tot, p_tot
