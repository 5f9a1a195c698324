"""Pallas kernels for the entropy container stage (core/entropy.py).

Two kernels back the ``deflate-full`` backend/decoder pair:

  * ``byte_histogram_pallas`` — the code-length front end: a sequential-grid
    reduction over 1024-byte tiles of the section buffer.  Each grid step
    one-hot-compares its tile against the 256 symbol lanes and accumulates
    into a revisited (1, 256) output block (constant index map, initialized
    at step 0) — the Pallas analogue of the XLA 257-slot scatter-add
    fallback in ``core.entropy.byte_histogram``, identical counts by test.

  * ``huffman_gap_decode_pallas`` — the parallel bitstream decoder: the
    container blob stays HBM-resident (``memory_space=ANY``, the
    lz_decode_mono.py idiom) and each grid step DMAs one fixed-width
    bitstream window per gap-array sub-block into VMEM at scalar-prefetched
    byte offsets.  Every sub-block lane then walks exactly ``sub``
    codewords from its entry point: a 24-bit window is read at the
    lane's bit offset, all 15 candidate lengths are range-tested against
    the canonical ``first``/``count`` tables at once (the prefix property
    guarantees a unique hit), and the decode table maps the hit to its
    symbol.  The sequential Huffman constraint lives only *inside* a
    sub-block — sub-blocks are embarrassingly parallel, which is the gap
    array's entire point (Sitaridi et al., PAPERS.md).

The decode table rides in one (8, 128) int32 block: rows 0-2 are the
``first`` / ``count`` / ``base`` per-length tables (16 live lanes), rows
3-4 the 256-entry symbol ``order`` map split across two 128-lane rows.

Mosaic has no per-lane gather across vregs and no single-lane dynamic
store, so table lookups and the 24-bit window read are one-hot lane
reductions, and decoded bytes leave in vreg-aligned 128-lane blocks.  Both
kernels compile for TPU v5e at real sizes (tests/test_tpu_compile.py);
``chip_smoke.py``'s ``deflate-full`` round trips drive them on a chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.lz_decode_mono import fetch_window
from repro.kernels.lz_match import _round_up, batch_by_loop

HIST_TILE = 1024  # bytes per histogram grid step (8 x 128 int32 lanes)
N_SYMBOLS = 256
MAX_CODE_LEN = 15


def window_bytes(sub: int) -> int:
    """Fixed DMA window per sub-block: worst case ``sub`` 15-bit codewords
    starting at any bit phase, plus the 2-byte lookahead of the last
    24-bit window read, lane-aligned."""
    return _round_up((7 + MAX_CODE_LEN * sub) // 8 + 3, 128)


# --------------------------------------------------------------- histogram


def _hist_kernel(start_ref, len_ref, buf_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    tile = buf_ref.shape[1]
    vals = (buf_ref[...].reshape(tile, 1)) & 0xFF
    gidx = i * tile + lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    lo = start_ref[0]
    ok = (gidx >= lo) & (gidx < lo + len_ref[0])
    sym = lax.broadcasted_iota(jnp.int32, (1, N_SYMBOLS), 1)
    eq = (vals == sym) & ok
    out_ref[...] += jnp.sum(eq.astype(jnp.int32), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def byte_histogram_pallas(buf, start, length, *, interpret=False):
    """(n,) int32 byte buffer -> (256,) int32 counts of [start, start+len).

    ``start``/``length`` may be traced; they ride scalar prefetch.  The
    grid is sequential over 1024-byte tiles, accumulating into one
    revisited (1, 256) block.
    """
    return batch_by_loop(functools.partial(_histogram, interpret=interpret))(
        jnp.asarray(buf), jnp.asarray(start), jnp.asarray(length)
    )


def _histogram(buf, start, length, *, interpret):
    b = jnp.asarray(buf, jnp.int32).reshape(1, -1)
    npad = _round_up(max(b.shape[1], 1), HIST_TILE)
    b = jnp.pad(b, ((0, 0), (0, npad - b.shape[1])))
    sarr = jnp.asarray(start, jnp.int32).reshape(1)
    larr = jnp.asarray(length, jnp.int32).reshape(1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(npad // HIST_TILE,),
        in_specs=[pl.BlockSpec((1, HIST_TILE), lambda i, s_, l_: (0, i))],
        out_specs=pl.BlockSpec((1, N_SYMBOLS), lambda i, s_, l_: (0, 0)),
    )
    out = pl.pallas_call(
        _hist_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, N_SYMBOLS), jnp.int32),
        cost_estimate=pl.CostEstimate(
            flops=npad * N_SYMBOLS,
            bytes_accessed=npad * 4 + N_SYMBOLS * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(sarr, larr, b)
    return out[0]


# ------------------------------------------------------ gap-array decoder


def _gap_decode_kernel(
    wstart_ref,  # scalar prefetch: (npad,) absolute window byte starts
    rem_ref,  # (g, 1) entry-point bit remainders within each window
    tab_ref,  # (8, 128) packed decode table (see module docstring)
    blob_ref,  # (1, lpad) container bytes, HBM-resident (ANY)
    out_ref,  # (g, sub) decoded bytes
    wbuf,  # (g, win) VMEM bitstream windows
    wrow,  # (1, win + 128) DMA landing row
    sems,
    *,
    nsub,
):
    i = pl.program_id(0)
    g, sub = out_ref.shape
    win = wbuf.shape[1]

    def fetch_row(row, _):
        li = i * g + row

        @pl.when(li < nsub)
        def _():
            wbuf[pl.ds(row, 1), :] = fetch_window(
                blob_ref, wstart_ref[li], wrow, sems.at[0], win
            )

        @pl.when(li >= nsub)
        def _():
            wbuf[pl.ds(row, 1), :] = jnp.zeros((1, win), jnp.int32)

        return 0

    lax.fori_loop(0, g, fetch_row, 0)

    # Table rows span all 128 lanes; lane l of ``first``/``count``/``base``
    # is code length l, live for l in [1, MAX_CODE_LEN].  Every lookup is a
    # one-hot lane reduction (per-lane scalars cannot index vregs).
    lane = lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    live_len = (lane >= 1) & (lane <= MAX_CODE_LEN)
    first = tab_ref[0:1, :]
    count = tab_ref[1:2, :]
    base = tab_ref[2:3, :]
    order_lo = tab_ref[3:4, :]
    order_hi = tab_ref[4:5, :]
    shift = jnp.clip(MAX_CODE_LEN - lane, 0, MAX_CODE_LEN)
    # 24-bit lookahead at every byte lane: bytes b, b+1, b+2 (zero past win)
    w = wbuf[...] & 0xFF
    wl = lax.broadcasted_iota(jnp.int32, (g, win), 1)
    w24 = (
        (w << 16)
        | (jnp.where(wl < win - 1, pltpu.roll(w, win - 1, 1), 0) << 8)
        | jnp.where(wl < win - 2, pltpu.roll(w, win - 2, 1), 0)
    )

    def codeword(off):
        byte = jnp.clip(off >> 3, 0, win - 1)
        look = jnp.sum(jnp.where(wl == byte, w24, 0), axis=1, keepdims=True)
        win15 = (look >> (9 - (off & 7))) & ((1 << MAX_CODE_LEN) - 1)
        cand = win15 >> shift
        ok = jnp.where(live_len & (cand >= first) & (cand - first < count), 1, 0)
        # unique hit by the canonical prefix property; with no hit, length
        # 1 (argmax's pick) so garbage lanes advance like the XLA fallback
        any_hit = jnp.max(ok, axis=1, keepdims=True)
        hit = jnp.where(any_hit == 1, ok, jnp.where(lane == 1, 1, 0))
        lsel = jnp.sum(hit * lane, axis=1, keepdims=True)
        sidx = jnp.sum(hit * (base + cand - first), axis=1, keepdims=True)
        sidx = jnp.clip(sidx, 0, N_SYMBOLS - 1)
        sym = jnp.sum(
            jnp.where(lane == sidx, order_lo, 0)
            + jnp.where(lane + 128 == sidx, order_hi, 0),
            axis=1,
            keepdims=True,
        )
        return off + lsel, sym

    # decoded bytes go out a vreg-aligned block at a time (no dynamic
    # single-lane stores): each block's lanes fill by one-hot selects
    bw = 128 if sub % 128 == 0 else sub
    blk_lane = lax.broadcasted_iota(jnp.int32, (g, bw), 1)

    def block(b, off):
        def step(j, carry):
            off, acc = carry
            off, sym = codeword(off)
            return off, jnp.where(blk_lane == j, sym, acc)

        off, acc = lax.fori_loop(
            0, bw, step, (off, jnp.zeros((g, bw), jnp.int32))
        )
        out_ref[:, pl.ds(pl.multiple_of(b * bw, bw), bw)] = acc
        return off

    lax.fori_loop(0, sub // bw, block, rem_ref[...])


@functools.partial(
    jax.jit, static_argnames=("sub", "chunks_per_block", "interpret")
)
def huffman_gap_decode_pallas(
    blob,
    wstarts,
    rems,
    first,
    count,
    base,
    order,
    *,
    sub,
    chunks_per_block=8,
    interpret=False,
):
    """Gap-array parallel canonical-Huffman decode, one launch.

    ``blob`` is the whole container as a flat int32 byte buffer (stays in
    HBM); ``wstarts``/``rems`` are the (nsub,) per-sub-block window byte
    starts and bit remainders (``base_byte + gap >> 3`` / ``gap & 7``);
    ``first``/``count``/``base`` are the (16,) canonical per-length tables
    and ``order`` the (256,) symbol map from
    ``entropy.canonical_tables_jax``.  Returns (nsub, sub) int32 decoded
    bytes; lanes beyond a section's live byte count decode garbage the
    caller masks (exactly like the XLA scan fallback).
    """
    return batch_by_loop(
        functools.partial(
            _gap_decode,
            sub=sub,
            chunks_per_block=chunks_per_block,
            interpret=interpret,
        )
    )(
        *(
            jnp.asarray(a)
            for a in (blob, wstarts, rems, first, count, base, order)
        )
    )


def _gap_decode(
    blob,
    wstarts,
    rems,
    first,
    count,
    base,
    order,
    *,
    sub,
    chunks_per_block,
    interpret,
):
    g = chunks_per_block
    win = window_bytes(sub)
    b = jnp.asarray(blob, jnp.int32).reshape(1, -1)
    lpad = _round_up(b.shape[1] + win + 2 * 128, 128)
    b = jnp.pad(b, ((0, 0), (0, lpad - b.shape[1])))

    nsub = wstarts.shape[0]
    ws = jnp.clip(jnp.asarray(wstarts, jnp.int32), 0, lpad - win - 128)
    rm = jnp.asarray(rems, jnp.int32)
    pad = (-nsub) % g
    ws = jnp.pad(ws, (0, pad))
    rm = jnp.pad(rm, (0, pad)).reshape(-1, 1)
    npad = nsub + pad

    tab = jnp.zeros((8, 128), jnp.int32)
    tab = tab.at[0, : MAX_CODE_LEN + 1].set(jnp.asarray(first, jnp.int32))
    tab = tab.at[1, : MAX_CODE_LEN + 1].set(jnp.asarray(count, jnp.int32))
    tab = tab.at[2, : MAX_CODE_LEN + 1].set(jnp.asarray(base, jnp.int32))
    tab = tab.at[3:5, :].set(jnp.asarray(order, jnp.int32).reshape(2, 128))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(npad // g,),
        in_specs=[
            pl.BlockSpec((g, 1), lambda i, w_: (i, 0)),
            pl.BlockSpec((8, 128), lambda i, w_: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((g, sub), lambda i, w_: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, win), jnp.int32),
            pltpu.VMEM((1, win + 128), jnp.int32),
            pltpu.SemaphoreType.DMA((1,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_gap_decode_kernel, nsub=nsub),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((npad, sub), jnp.int32),
        cost_estimate=pl.CostEstimate(
            flops=npad * sub * (2 * MAX_CODE_LEN + 12),
            bytes_accessed=npad * win * 4 + npad * sub * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(ws, rm, tab, b)
    return out[:nsub]
