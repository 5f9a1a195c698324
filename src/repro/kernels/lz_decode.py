"""Pallas TPU kernel for fused GPULZ decompression.

The XLA reference decoder (core/decode.py:decode_parallel) stages every
intermediate — flag bits, the two read/write prefix sums, per-token
length/offset/literal columns, the token-id fill and ceil(log2 C) rounds of
pointer doubling — through HBM as separate ops.  This kernel keeps the whole
chain resident in VMEM per chunk block (cf. Sitaridi et al.,
*Massively-Parallel Lossless Data Decompression*, PAPERS.md): the only HBM
traffic is the compact flag/payload sections in and the decoded symbols out,
written exactly once.

Algorithm (identical math to decode_parallel, TPU-shaped):

  flag extraction      one gather per position from the chunk's flag bytes
  read offsets         prefix sum over [2 | S] token byte sizes
                       (lane-shift doubling — no HBM cumsum)
  token fields         payload gathers at the read offsets (len/off/literal)
  write offsets        prefix sum over token output lengths
  token-id fill        branchless binary search over the sorted token start
                       positions (log2 C gathers) — replaces decode_parallel's
                       scatter+cumsum, which has no efficient Mosaic lowering
  copy resolution      ceil(log2 C) pointer-doubling gathers; match length <=
                       offset (match.py) makes back-references a forest rooted
                       at literals, so doubling terminates

Like lz_match.py, ``chunks_per_block`` chunks ride the sublane dimension so
the 8x128 VREG tile stays full for small C.  Kernels are validated in
interpret mode against core/decode.py (tests/test_kernels.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.lz_match import (
    LANES,
    _round_up,
    _shift_right_zero,
    byte_spread,
    lane_gather,
)


def _ceil_log2(n: int) -> int:
    k = 0
    while (1 << k) < n:
        k += 1
    return max(1, k)


def _prefix_sum_excl(x, idx, c):
    """Exclusive prefix sum along lanes via log-shift doubling (stays in VMEM)."""
    incl = x
    k = 1
    while k < c:
        incl = incl + _shift_right_zero(incl, k, idx)
        k *= 2
    return incl - x


def _search_last_le(sorted_rows, queries, n):
    """res[., q] = last index i in [0, n) with sorted_rows[., i] <= queries[., q].

    Branchless binary search over a nondecreasing row (log2 n gathers —
    the gather-friendly replacement for scatter+cumsum fills, which have no
    efficient Mosaic lowering).  Defaults to 0 when every element exceeds
    the query; callers mask those lanes.  Shared by the decode kernel's
    token-id fill and the deflate-scatter kernel's rank/offset searches.
    """
    res = jnp.zeros_like(queries)
    for shift in reversed(range(_ceil_log2(n))):
        probe = res + (1 << shift)
        pv = lane_gather(sorted_rows, jnp.clip(probe, 0, n - 1))
        res = jnp.where((probe <= n - 1) & (pv <= queries), probe, res)
    return res


def flag_lanes(c: int) -> int:
    """Lane width of a chunk's flag-byte row: C/8 bytes, vreg-aligned."""
    return _round_up(c // 8, LANES)


def _decode_values(flag_bytes, payload, n_tokens, *, symbol_size, chunk_symbols):
    """(G, >=C/8) flags + (G, >=C*S) payload + (G, 1) counts -> (G, C) symbols.

    Flag and payload rows may be wider than the chunk's sections (vreg
    padding); lanes past a chunk's live bytes must be zero."""
    g = flag_bytes.shape[0]
    c = chunk_symbols
    s = symbol_size
    t = lax.broadcasted_iota(jnp.int32, (g, c), 1)
    active = (t < n_tokens).astype(jnp.int32)

    byte = byte_spread(flag_bytes, c)
    flags = ((byte >> (t % 8)) & 1) * active

    # token read offsets: prefix sum over [2 | S] encoded byte sizes
    read_size = jnp.where(active == 1, jnp.where(flags == 1, 2, s), 0)
    read_off = _prefix_sum_excl(read_size, t, c)
    pay = [
        lane_gather(
            payload, jnp.clip(read_off + k, 0, payload.shape[1] - 1)
        )
        for k in range(max(s, 2))
    ]

    ln = jnp.where(flags == 1, pay[0], 1) * active
    off = jnp.where(flags == 1, pay[1], 0) * active
    lit = jnp.zeros((g, c), jnp.int32)
    for b in range(s):
        lit = lit + (pay[b] << (8 * b))
    lit = jnp.where(flags == 0, lit, 0)

    out_pos = _prefix_sum_excl(ln, t, c)  # token write starts (symbols)

    # Per-output-symbol token id.  Token starts are strictly increasing over
    # active tokens (ln >= 1), so the covering token of output position w is
    # the last token with out_pos <= w (inactive tokens get the sentinel c,
    # keeping the row sorted).
    pos = jnp.where((active == 1) & (ln > 0), out_pos, c)
    token_id = _search_last_le(pos, t, c)

    # flag and offset (<= 255) share one gather
    fo_w = lane_gather(flags | (off << 1), token_id)
    lit_w = lane_gather(lit, token_id)
    src = jnp.where((fo_w & 1) == 1, jnp.clip(t - (fo_w >> 1), 0, c - 1), t)
    for _ in range(_ceil_log2(c)):
        src = lane_gather(src, src)
    return lane_gather(lit_w, src)


def _decode_kernel(flag_ref, pay_ref, ntok_ref, out_ref, *, symbol_size):
    out_ref[...] = _decode_values(
        flag_ref[...],
        pay_ref[...],
        ntok_ref[...],
        symbol_size=symbol_size,
        chunk_symbols=out_ref.shape[1],
    )


def _cost(nc, c, s):
    lg = _ceil_log2(c)
    # per position: flag extract + 2 prefix sums + binary search + doubling
    flops = nc * c * (8 * lg + s + 12)
    return pl.CostEstimate(
        flops=flops,
        bytes_accessed=nc * ((c + 7) // 8 + c * s + 4 + c * 4),
        transcendentals=0,
    )


@functools.partial(
    jax.jit, static_argnames=("symbol_size", "chunks_per_block", "interpret")
)
def lz_decode_pallas(
    flag_bytes,
    payload,
    n_tokens,
    *,
    symbol_size,
    chunks_per_block=8,
    interpret=False,
):
    """Fused decoder: (nc, C//8) flag bytes + (nc, C*S) payload bytes +
    (nc,) token counts -> (nc, C) int32 symbols.

    Inputs are the per-chunk aligned sections produced by
    deflate.gather_section (int-valued; any integer dtype accepted)."""
    nc, cb = flag_bytes.shape
    c = cb * 8
    g = chunks_per_block
    pad = (-nc) % g
    # flag rows padded to whole vregs (lane_gather walks 128-lane blocks)
    f = jnp.pad(flag_bytes.astype(jnp.int32), ((0, pad), (0, flag_lanes(c) - cb)))
    p = jnp.pad(payload.astype(jnp.int32), ((0, pad), (0, 0)))
    nt = jnp.pad(n_tokens.astype(jnp.int32), (0, pad)).reshape(-1, 1)
    npad = nc + pad
    grid = (npad // g,)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, symbol_size=symbol_size),
        grid=grid,
        in_specs=[
            pl.BlockSpec((g, f.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec((g, p.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec((g, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((g, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((npad, c), jnp.int32),
        cost_estimate=_cost(npad, c, symbol_size),
        interpret=interpret,
    )(f, p, nt)
    return out[:nc]
