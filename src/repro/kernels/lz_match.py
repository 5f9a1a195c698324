"""Pallas TPU kernel for GPULZ Kernel I (match + select + local prefix sum).

TPU mapping of the paper's fused kernel (§3.3.2):

  CUDA thread block + shared memory  ->  Pallas grid cell + VMEM block
  one thread per coding position     ->  positions on vector lanes
  window walk per thread             ->  fori over window offsets d = 1..W,
                                         capped log-doubling run lengths
  chunk per thread block             ->  ``chunks_per_block`` chunks stacked on
                                         sublanes (fills the 8x128 VREG tile)
  one encode thread per block        ->  in-kernel selection walk over lanes
                                         (aligned 128-lane blocks, one-hot
                                         lane reductions per position)
  shared-mem local prefix sum        ->  in-VMEM log-doubling prefix sum

Everything between the symbol load and the (len/off/emitted/local-offset)
stores stays in VMEM — the equality rows and run-length intermediates never
touch HBM.  That is precisely the paper's two-pass-prefix-sum + kernel-fusion
insight (their Fig. 4 (c) vs (d)); the unfused XLA pipeline in core/ is the
workflow-(c) baseline we compare against in EXPERIMENTS.md.

Kernels are validated in interpret mode against kernels/ref.py (pure jnp)
and compiled for TPU v5e at real sizes by tests/test_tpu_compile.py.  Only
what Mosaic lowers is used: lane rotates (``pltpu.roll``), gathers within
one vreg (``lane_gather`` walks wider rows block by block), lane slices at
128-aligned offsets, and (g, 1) column blocks for per-chunk tables.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MAX_LEN_CAP = 255
LANES = 128  # TPU vreg lane width: Mosaic gathers and rolls within one


def _levels(window: int, max_len: int) -> int:
    cap = min(window, max_len)
    k = 0
    while (1 << k) < cap:
        k += 1
    return k


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _shift_left_zero(x, stride, idx, c):
    """out[..., i] = x[..., i + stride] with zero fill (roll + mask)."""
    if stride >= c:
        return jnp.zeros_like(x)
    return jnp.where(idx < c - stride, pltpu.roll(x, c - stride, 1), 0)


def _shift_right_zero(x, stride, idx):
    c = x.shape[-1]
    if stride >= c:
        return jnp.zeros_like(x)
    return jnp.where(idx >= stride, pltpu.roll(x, stride, 1), 0)


def byte_spread(rows, c):
    """(g, >=C/8) byte rows -> (g, C) with lane t holding byte t // 8.

    A 0/1 selection matrix on the MXU: bytes are exact in bf16 and the
    f32 accumulator sums one term.  (The equivalent constant-index
    ``take_along_axis`` crashes the TPU compiler for blocks taller than
    one vreg.)"""
    nb = rows.shape[1]
    b = lax.broadcasted_iota(jnp.int32, (nb, c), 0)
    t = lax.broadcasted_iota(jnp.int32, (nb, c), 1)
    sel = (t // 8 == b).astype(jnp.bfloat16)
    out = jnp.dot(
        rows.astype(jnp.bfloat16), sel, preferred_element_type=jnp.float32
    )
    return out.astype(jnp.int32)


def bit_pack(bits, nb):
    """(g, C) 0/1 lanes -> (g, nb) bytes, bit j of byte b = lane 8b + j.

    The inverse of ``byte_spread`` as one MXU product with power-of-two
    weights (exact in bf16; at most eight terms per f32 sum).  Bytes past
    C/8 are zero."""
    c = bits.shape[1]
    t = lax.broadcasted_iota(jnp.int32, (c, nb), 0)
    b = lax.broadcasted_iota(jnp.int32, (c, nb), 1)
    weight = jnp.where(t // 8 == b, 1 << (t % 8), 0).astype(jnp.bfloat16)
    out = jnp.dot(
        bits.astype(jnp.bfloat16), weight, preferred_element_type=jnp.float32
    )
    return out.astype(jnp.int32)


def lane_gather(x, idx):
    """``out[r, j] = x[r, idx[r, j]]`` for (g, n) rows and (g, m) indices.

    Mosaic lowers ``take_along_axis`` only within one 128-lane vreg, so a
    wider row is walked in 128-lane source blocks: each block is staged
    through VMEM scratch, gathered with the low 7 index bits and kept where
    the high bits name that block.  Indices outside ``[0, n)`` give 0.
    Rows of one vreg (and lane widths that are not vreg multiples, which
    only the interpreter sees) gather directly.
    """
    g, n = x.shape
    m = idx.shape[1]
    if (n == m and n <= LANES) or n % LANES or m % LANES:
        return jnp.take_along_axis(x, idx, axis=1, mode="fill", fill_value=0)
    lo = idx & (LANES - 1)
    hi = idx >> 7
    nout = m // LANES

    def run(src):
        src[...] = x

        def block(j, out):
            blk = src[:, pl.ds(pl.multiple_of(j * LANES, LANES), LANES)]
            got = jnp.concatenate(
                [
                    jnp.take_along_axis(
                        blk, lo[:, o * LANES : (o + 1) * LANES], axis=1
                    )
                    for o in range(nout)
                ],
                axis=1,
            )
            return jnp.where(hi == j, got, out)

        return lax.fori_loop(0, n // LANES, block, jnp.zeros((g, m), x.dtype))

    return pl.run_scoped(run, pltpu.VMEM((g, n), x.dtype))


def batch_by_loop(fn):
    """``fn`` whose ``vmap`` is a loop of ``fn`` over the batch.

    Pallas batches a ``pallas_call`` by adding a grid dimension, which the
    TPU lowering refuses for HBM-resident (``memory_space=ANY``) operands;
    the batched entry points (``compress_many``, the KV tier, the gradient
    exchange) vmap the kernels, so those kernels run one buffer at a time.
    Nested vmaps nest the loops.
    """
    looped = jax.custom_batching.custom_vmap(fn)

    @looped.def_vmap
    def rule(axis_size, in_batched, *args):
        def one(batched):
            it = iter(batched)
            return looped(*[next(it) if b else a for a, b in zip(args, in_batched)])

        out = lax.map(one, [a for a, b in zip(args, in_batched) if b])
        return out, jax.tree.map(lambda _: True, out)

    return looped


def _match_values(x, *, window, max_len):
    """(G, C) symbols -> (lengths, offsets) values; runs entirely in VMEM.

    The offset loop is bucketed by ceil(log2 d): candidates are capped at
    min(d, max_len), so offsets in (2^{k-1}, 2^k] only need k doubling
    levels (~15% fewer VPU ops at W=128; see EXPERIMENTS.md §Perf)."""
    g, c = x.shape
    max_levels = _levels(window, max_len)
    idx = lax.broadcasted_iota(jnp.int32, (g, c), 1)
    pack = window + 1

    def body_for(levels):
        def body(d, best):
            # a traced-shift lane rotate (jnp.roll would lower to a
            # dynamic_slice Mosaic lacks); wrapped lanes masked below
            shifted = pltpu.roll(x, d % c, 1)
            eq = ((x == shifted) & (idx >= d)).astype(jnp.int32)
            r = eq
            for k in range(levels):
                stride = 1 << k
                r = r + jnp.where(r == stride, _shift_left_zero(r, stride, idx, c), 0)
            cand = jnp.minimum(r, jnp.minimum(d, max_len))
            return jnp.maximum(best, cand * pack + d)

        return body

    best = jnp.zeros((g, c), jnp.int32)
    lo, k = 1, 0
    while lo <= window:
        k = min(k, max_levels)
        hi = min(window, 1 << k) if k else min(window, 1)
        best = lax.fori_loop(lo, hi + 1, body_for(k), best)
        lo = hi + 1
        k += 1
    lengths = best // pack
    offsets = jnp.where(lengths > 0, best % pack, 0)
    return lengths, offsets


def _match_kernel(x_ref, len_ref, off_ref, *, window, max_len):
    lengths, offsets = _match_values(x_ref[...], window=window, max_len=max_len)
    len_ref[...] = lengths
    off_ref[...] = offsets


def _select_and_scan(len_ref, emit_ref, lengths, *, min_match, symbol_size):
    """Selection walk + local prefix sums, VMEM-resident.

    ``len_ref`` must already hold ``lengths``; ``emit_ref`` is the scratch
    the walk's dynamic-column stores go through and holds the 0/1 emitted
    mask on return.  Returns ``(emitted, use_match, sizes, local_off,
    payload_sizes, n_tokens)`` values — the per-position arrays are (g, C),
    the per-chunk reductions (g, 1).  Shared by the fused Kernel I below and
    the single-kernel compressor (lz_fused.py).
    """
    g, c = len_ref.shape
    w = LANES if c % LANES == 0 else c
    lane = lax.broadcasted_iota(jnp.int32, (g, w), 1)

    # --- encode walk (paper: one thread per block; here: all `g` chunks in
    # lockstep on sublanes).  Mosaic indexes lanes dynamically only at vreg
    # granularity, so the walk loads one aligned 128-lane block, steps
    # through its lanes with one-hot lane reductions, and stores the block's
    # emitted mask back whole.
    def block(b, next_pos):
        start = pl.multiple_of(b * w, w)
        lens = len_ref[:, pl.ds(start, w)]

        def position(j, carry):
            next_pos, emit = carry
            hit = lane == j
            len_j = jnp.sum(jnp.where(hit, lens, 0), axis=1, keepdims=True)
            here = next_pos == start + j
            step = jnp.where(len_j >= min_match, len_j, 1)
            emit = jnp.where(hit & here, 1, emit)
            return jnp.where(here, start + j + step, next_pos), emit

        next_pos, emit = lax.fori_loop(
            0, w, position, (next_pos, jnp.zeros((g, w), jnp.int32))
        )
        emit_ref[:, pl.ds(start, w)] = emit
        return next_pos

    lax.fori_loop(0, c // w, block, jnp.zeros((g, 1), jnp.int32))

    # --- local prefix sum (paper's up/down-sweep == lane-shift doubling) ---
    emitted = emit_ref[...] == 1
    use_match = emitted & (lengths >= min_match)
    sizes = jnp.where(emitted, jnp.where(use_match, 2, symbol_size), 0).astype(
        jnp.int32
    )
    idx = lax.broadcasted_iota(jnp.int32, (g, c), 1)
    incl = sizes
    ntok = emitted.astype(jnp.int32)
    k = 1
    while k < c:
        incl = incl + _shift_right_zero(incl, k, idx)
        ntok = ntok + _shift_right_zero(ntok, k, idx)
        k *= 2
    return emitted, use_match, sizes, incl - sizes, incl[:, c - 1 :], ntok[:, c - 1 :]


def _fused_kernel(
    x_ref,
    len_ref,
    off_ref,
    emit_ref,
    lo_ref,
    paysz_ref,
    ntok_ref,
    *,
    window,
    max_len,
    min_match,
    symbol_size,
):
    lengths, offsets = _match_values(x_ref[...], window=window, max_len=max_len)
    len_ref[...] = lengths
    off_ref[...] = offsets
    _, _, _, local_off, paysz, ntok = _select_and_scan(
        len_ref, emit_ref, lengths, min_match=min_match, symbol_size=symbol_size
    )
    lo_ref[...] = local_off               # exclusive local offsets
    paysz_ref[...] = paysz                # per-chunk compressed payload bytes
    ntok_ref[...] = ntok                  # per-chunk token count (flag bits)


def _pad_chunks(symbols, gsz):
    nc = symbols.shape[0]
    pad = (-nc) % gsz
    if pad:
        symbols = jnp.concatenate(
            [symbols, jnp.zeros((pad, symbols.shape[1]), symbols.dtype)], axis=0
        )
    return symbols, nc


def _cost(nc, c, window, levels):
    # per (position, offset): eq + levels*(cmp+sel+add) + cap/min + pack/max
    flops = nc * c * window * (2 + 3 * levels + 5)
    return pl.CostEstimate(
        flops=flops, bytes_accessed=nc * c * 4 * 3, transcendentals=0
    )


@functools.partial(
    jax.jit,
    static_argnames=("window", "max_len", "chunks_per_block", "interpret"),
)
def lz_match_pallas(
    symbols, *, window, max_len=MAX_LEN_CAP, chunks_per_block=8, interpret=False
):
    """(nc, C) int32 -> (lengths, offsets), each (nc, C) int32."""
    x, nc = _pad_chunks(symbols.astype(jnp.int32), chunks_per_block)
    npad, c = x.shape
    g = chunks_per_block
    grid = (npad // g,)
    spec = pl.BlockSpec((g, c), lambda i: (i, 0))
    lengths, offsets = pl.pallas_call(
        functools.partial(_match_kernel, window=window, max_len=max_len),
        grid=grid,
        in_specs=[spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((npad, c), jnp.int32),
            jax.ShapeDtypeStruct((npad, c), jnp.int32),
        ],
        cost_estimate=_cost(npad, c, window, _levels(window, max_len)),
        interpret=interpret,
    )(x)
    return lengths[:nc], offsets[:nc]


@functools.partial(
    jax.jit,
    static_argnames=(
        "window",
        "max_len",
        "min_match",
        "symbol_size",
        "chunks_per_block",
        "interpret",
    ),
)
def lz_kernel1_pallas(
    symbols,
    *,
    window,
    min_match,
    symbol_size,
    max_len=MAX_LEN_CAP,
    chunks_per_block=8,
    interpret=False,
):
    """Fused Kernel I: -> dict(lengths, offsets, emitted, local_off,
    payload_sizes, n_tokens), shapes (nc, C) / (nc,)."""
    x, nc = _pad_chunks(symbols.astype(jnp.int32), chunks_per_block)
    npad, c = x.shape
    g = chunks_per_block
    grid = (npad // g,)
    spec2d = pl.BlockSpec((g, c), lambda i: (i, 0))
    # per-chunk tables ride as (g, 1) column blocks: Mosaic tiles the last
    # two dims, so a rank-1 (g,) block would need g to be a lane multiple
    spec1d = pl.BlockSpec((g, 1), lambda i: (i, 0))
    sds2 = jax.ShapeDtypeStruct((npad, c), jnp.int32)
    sds1 = jax.ShapeDtypeStruct((npad, 1), jnp.int32)
    out = pl.pallas_call(
        functools.partial(
            _fused_kernel,
            window=window,
            max_len=max_len,
            min_match=min_match,
            symbol_size=symbol_size,
        ),
        grid=grid,
        in_specs=[spec2d],
        out_specs=[spec2d, spec2d, spec2d, spec2d, spec1d, spec1d],
        out_shape=[sds2, sds2, sds2, sds2, sds1, sds1],
        cost_estimate=_cost(npad, c, window, _levels(window, max_len)),
        interpret=interpret,
    )(x)
    lengths, offsets, emitted, local_off, paysz, ntok = out
    return dict(
        lengths=lengths[:nc],
        offsets=offsets[:nc],
        emitted=emitted[:nc] == 1,
        local_off=local_off[:nc],
        payload_sizes=paysz[:nc, 0],
        n_tokens=ntok[:nc, 0],
    )
