"""Error-bounded lossy frontend: the ``lossy-fz`` container subsystem.

FZ-GPU's (PAPERS.md) recipe for scientific f32 data, as a method-2
container (core/format.py):

    dual-quant (core/quant.py math, ndim=1 over the flattened element
    stream) -> bitshuffle (core/bitshuffle.py bit-plane transpose of the
    uint16 code stream) -> lossless inner container (the platform LZSS
    backend, or ``deflate-full``)

plus an outlier section (saturated / non-finite elements stored as exact
(u32 index, f32 bits) pairs) and a fixed metadata block carrying the error
bound itself — ``decompress`` reconstructs within the bound from container
bytes alone, no side-channel state.

Guarantees (tested in tests/test_lossy.py / test_properties.py):

  * quant mode (``lossy_eb > 0``): max |x' - x| <= eb for every finite
    element; NaN/±inf elements round-trip bit-exactly through the outlier
    section.  The bound is *f32-deterministic*: the stored eb is the f32
    rounding of the configured bound, and both sides derive 2*eb in f32
    (exact — a power-of-two scale), so encoder and decoder integer chains
    agree bit-for-bit.
  * lossless mode (``lossy_eb == 0``): bit-exact reconstruction including
    NaN payloads — the f32 halves pass through bitshuffle untouched.

Both hooks are fixed-shape and fully in-graph (vmap/shard_map safe): the
inner container sits at a *static* offset so its header/tables parse with
static slices; only the outlier section lives at a dynamic offset (after
the inner container's live bytes) and is written/read with masked
OOB-dropped scatters/gathers, the same pattern as core/entropy.py.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import bitshuffle
from repro.core import format as fmt
from repro.core import quant

assert bitshuffle.BLOCK_UNITS == fmt.LOSSY_BLOCK_UNITS

INT30 = 2.0**30


def eb_to_f32(error_bound: float) -> float:
    """The f32-rounded bound both sides of the format actually honor."""
    return float(np.float32(error_bound))


def _rcp(eb2) -> jnp.ndarray:
    """The format's pre-quant scale: fl32(1 / (2*eb)), correctly rounded.

    The encoder knows eb statically but the decoder reads it from container
    bytes; for their integer chains to agree bit-for-bit, BOTH sides must
    get the same scale on every platform.  Defining pre-quant as
    ``round(x / eb2)`` breaks that: XLA strength-reduces division by a
    *constant* to a reciprocal multiply, which flips ``round`` at
    half-quantum boundaries relative to the decoder's true divide (observed
    on CPU: carry repair off by one quantum between outliers).  A runtime
    ``1 / eb2`` does not settle it either: the TPU's f32 divide is not
    correctly rounded, so a decoder on the chip disagreed by an ulp with the
    encoder's constant-folded reciprocal and mis-anchored the chain after
    every outlier (max error 3*eb on a v5e).  So the reciprocal is computed
    here in integer arithmetic, which every backend and the constant folder
    evaluate alike: the 24-bit quotient of 2^47 by eb2's significand, by
    long division six bits at a time, rounded to nearest even.  eb2 must be
    a normal f32 below 2^126 (``LZSSConfig`` checks the bound), so the
    result is normal.
    """
    bits = lax.bitcast_convert_type(jnp.asarray(eb2, jnp.float32), jnp.int32)
    e = (bits >> 23) & 0xFF
    m = (bits & 0x7FFFFF) | 0x800000
    q = jnp.zeros_like(m)
    r = jnp.full_like(m, 1 << 23)  # 2^23 <= m: the quotient has 24 bits
    for _ in range(4):
        r = r << 6
        d = r // m
        r = r - d * m
        q = (q << 6) + d
    # q = floor(2^47 / m) in [2^23, 2^24]; round on the remainder
    up = (2 * r > m) | ((2 * r == m) & ((q & 1) == 1))
    q = q + up.astype(jnp.int32)
    carry = q >> 24  # 2^47 / m rounded up to 2^24: one binade higher
    q = q >> carry
    out = ((253 - e + carry) << 23) | (q & 0x7FFFFF)
    return lax.bitcast_convert_type(out, jnp.float32)


def _prequant(x: jnp.ndarray, rcp):
    """round/clip pre-quantization, NaN pinned to 0 (core/quant.py rules).

    ``rcp`` must be the ``_rcp`` scalar — identical lowering on the encode
    (static eb) and decode (eb from container bytes) sides is what makes
    the two integer chains agree bit-for-bit.
    """
    qf = jnp.round(x * rcp)
    nan = jnp.isnan(qf)
    q = jnp.clip(jnp.where(nan, 0.0, qf), -INT30, INT30).astype(jnp.int32)
    return qf, nan, q


def static_params(header: fmt.Header) -> tuple:
    """The ``lossy-fz`` decoder's static decode parameters.

    Mode and inner method change the in-graph shapes of the decode trace
    (unit counts, inner geometry, section capacities), so they travel as
    static jit arguments — parsed host-side from the header by
    ``lzss.decompress`` and threaded through ``method_params``.
    """
    return (header.lossy_mode, header.inner_method)


def compress_lossy(symbols, cfg, orig_bytes=None):
    """The ``lossy-fz`` backend's ``compress`` hook.

    ``symbols`` is the (nc, C) int32 S=4 symbol array — each symbol IS one
    little-endian f32 bit pattern.  Returns ``(buffer u8, total_bytes)``
    holding a complete method-2 container.
    """
    from repro.core import pipeline  # lazy: pipeline registers this hook

    nc, c = symbols.shape
    eb32 = eb_to_f32(cfg.lossy_eb)
    mode = (
        fmt.LOSSY_MODE_QUANT if eb32 > 0.0 else fmt.LOSSY_MODE_LOSSLESS
    )
    n_elems, units_pad, inner_nc = fmt.lossy_stream_geometry(nc, c, mode)
    flat = symbols.reshape(-1).astype(jnp.int32)

    if mode == fmt.LOSSY_MODE_QUANT:
        x = lax.bitcast_convert_type(flat, jnp.float32)
        eb2 = jnp.float32(2.0 * eb32)
        qf, nan, q = _prequant(x, _rcp(eb2))
        delta = jnp.diff(q, prepend=q[:1] * 0) + quant.CENTER
        sat = (
            (delta < quant.CODE_MIN)
            | (delta > quant.CODE_MAX)
            | (jnp.abs(qf) >= INT30)
            | nan
        )
        # The decoder reconstructs exactly ``q.astype(f32) * eb2`` (same op
        # sequence, bit-identical) — simulate it and promote any element the
        # f32 round trip pushes past the bound (half-quantum boundaries at
        # large |x|/eb) to an exact outlier.  ``~(err <= eb)`` also catches
        # non-finite x, making the <= eb guarantee strict, not ulp-fuzzy.
        # The two-ulp guard keeps the check conservative if XLA fuses this
        # mul+sub into an FMA (more accurate than the decoder's standalone
        # rounded mul, so an unguarded check could under-promote).
        recon = q.astype(jnp.float32) * eb2
        guard = jnp.abs(recon) * jnp.float32(2.0**-22)
        sat = sat | ~(jnp.abs(recon - x) + guard <= eb32)
        units_live = jnp.where(sat, quant.CENTER, delta)
    else:
        lo = flat & 0xFFFF
        hi = (flat >> 16) & 0xFFFF
        # interleave by interior padding, not an (n, 2) stack (see below)
        units_live = lax.pad(lo, 0, [(0, 1, 1)]) | lax.pad(hi, 0, [(1, 0, 1)])
        sat = None

    units = (
        jnp.zeros((units_pad,), jnp.int32)
        .at[: units_live.shape[0]]
        .set(units_live)
    )
    shuffled = bitshuffle.shuffle(units.astype(jnp.uint16)).astype(jnp.int32)
    # strided halves, not a (N, 2) reshape: on TPU a 2-wide minor
    # dimension tiles to 128 lanes (64x the stream's bytes)
    inner_live = shuffled[0::2] | (shuffled[1::2] << 8)
    inner_c = fmt.LOSSY_INNER_CHUNK_SYMBOLS
    inner_syms = (
        jnp.zeros((inner_nc * inner_c,), jnp.int32)
        .at[:units_pad]
        .set(inner_live)
        .reshape(inner_nc, inner_c)
    )

    inner_name = pipeline.resolve_backend(cfg.lossy_inner)
    inner_method = pipeline.container_method(inner_name)
    inner_cfg = pipeline.LZSSConfig(
        symbol_size=2,
        window=cfg.window,
        chunk_symbols=inner_c,
        backend=inner_name,
    )
    inner_buf, inner_total = pipeline._compress_via(
        pipeline.get_backend(inner_name), inner_syms, inner_cfg, 2 * units_pad
    )
    inner_cap = fmt.lossy_inner_capacity(inner_nc, inner_method)
    assert inner_buf.shape[0] == inner_cap, (
        f"inner backend {inner_name!r} emitted a {inner_buf.shape[0]}-byte "
        f"capacity buffer, format expects {inner_cap}"
    )

    sec_meta = fmt.HEADER_BYTES + 8 * nc
    sec_inner = sec_meta + fmt.LOSSY_META_FIXED
    out_cap = sec_inner + inner_cap + (
        8 * n_elems if mode == fmt.LOSSY_MODE_QUANT else 0
    )
    zeros_nc = jnp.zeros((nc,), jnp.int32)
    out = jnp.zeros((out_cap,), jnp.int32)
    out = fmt.write_header_and_tables(
        out,
        symbol_size=4,
        window=cfg.window,
        chunk_symbols=c,
        n_chunks=nc,
        orig_bytes=nc * c * 4 if orig_bytes is None else orig_bytes,
        payload_total=0,
        flag_total=0,
        n_tokens=zeros_nc,
        payload_sizes=zeros_nc,
        method=fmt.METHOD_LOSSY,
        sub_log2=0,
    )
    out = out.at[sec_inner : sec_inner + inner_cap].set(
        inner_buf.astype(jnp.int32)
    )

    if mode == fmt.LOSSY_MODE_QUANT:
        n_out = jnp.sum(sat).astype(jnp.int32)
        rank = jnp.cumsum(sat) - 1
        obase = sec_inner + inner_total
        base_i = obase + 8 * rank
        idxs = jnp.arange(n_elems, dtype=jnp.int32)
        for j in range(4):  # OOB writes (index out_cap) drop
            pos = jnp.where(sat, base_i + j, out_cap)
            out = out.at[pos].add(jnp.where(sat, (idxs >> (8 * j)) & 0xFF, 0))
        for j in range(4):
            pos = jnp.where(sat, base_i + 4 + j, out_cap)
            out = out.at[pos].add(jnp.where(sat, (flat >> (8 * j)) & 0xFF, 0))
        total = obase + 8 * n_out
        eb_bits = int(np.float32(eb32).view(np.uint32))
    else:
        n_out = jnp.zeros((), jnp.int32)
        total = sec_inner + inner_total
        eb_bits = 0

    meta = (
        fmt._le_bytes(eb_bits, 4)
        + fmt._le_bytes(mode, 1)
        + fmt._le_bytes(1, 1)  # quantization ndim
        + fmt._le_bytes(inner_method, 1)
        + fmt._le_bytes(0, 1)
        + fmt._le_bytes(n_out, 4)
        + fmt._le_bytes(inner_total, 4)
        + fmt._le_bytes(n_elems, 8)
        + fmt._le_bytes(0, 8)
    )
    out = out.at[sec_meta : sec_meta + fmt.LOSSY_META_FIXED].set(
        jnp.stack(meta).astype(jnp.int32)
    )
    return out.astype(jnp.uint8), total


def decode_blob_lossy(
    blob,
    *,
    chunk_symbols: int,
    n_chunks: int,
    mode: int,
    inner_method: int,
):
    """The ``lossy-fz`` decoder's whole-container hook.

    Parses the method-2 metadata at static offsets, decodes the inner
    container through the platform LZSS chain (``deflate-full`` for a
    method-1 inner), inverts the bitshuffle, and (quant mode) integrates
    the delta chain with the outlier-anchored repair before overlaying the
    exact outlier values.  Returns (nc, C) int32 f32-bit-pattern symbols.
    """
    from repro.core import pipeline  # lazy: avoid import cycle

    nc, c = n_chunks, chunk_symbols
    n_elems, units_pad, inner_nc = fmt.lossy_stream_geometry(nc, c, mode)
    inner_cap = fmt.lossy_inner_capacity(inner_nc, inner_method)
    sec_meta = fmt.HEADER_BYTES + 8 * nc
    sec_inner = sec_meta + fmt.LOSSY_META_FIXED
    need = sec_inner + inner_cap + (
        8 * n_elems if mode == fmt.LOSSY_MODE_QUANT else 0
    )
    b32 = jnp.asarray(blob, jnp.int32).reshape(-1) & 0xFF
    if b32.shape[0] < need:  # static pad: every gather below stays in range
        b32 = jnp.pad(b32, (0, need - b32.shape[0]))

    def u32(off):
        return (
            b32[off] | (b32[off + 1] << 8) | (b32[off + 2] << 16)
            | (b32[off + 3] << 24)
        )

    inner_total = u32(sec_meta + 12)
    inner_blob = b32[sec_inner : sec_inner + inner_cap]
    inner_nt, inner_ps = fmt.parse_tables_jax(inner_blob, inner_nc)
    inner_syms = pipeline.decompress_chunks(
        inner_blob,
        inner_nt,
        inner_ps,
        symbol_size=2,
        chunk_symbols=fmt.LOSSY_INNER_CHUNK_SYMBOLS,
        n_chunks=inner_nc,
        decoder=(
            "deflate-full" if inner_method == fmt.METHOD_HUFFMAN else "auto"
        ),
    )
    # the inner symbols ARE the shuffled bytes as little-endian pairs
    words = inner_syms.reshape(-1)[:units_pad]
    units = bitshuffle.unshuffle_words(words).astype(jnp.int32)

    if mode == fmt.LOSSY_MODE_LOSSLESS:
        u = units[: 2 * n_elems]
        return (u[0::2] | (u[1::2] << 16)).reshape(nc, c)

    eb2 = 2.0 * lax.bitcast_convert_type(u32(sec_meta), jnp.float32)
    n_out = u32(sec_meta + 8)
    codes = units[:n_elems]
    q = jnp.cumsum(codes - quant.CENTER)

    # sparse outlier section -> dense mask/values (OOB-dropped scatter)
    k = jnp.arange(n_elems, dtype=jnp.int32)
    live = k < n_out
    pbase = sec_inner + inner_total + 8 * k

    def g(off):
        return jnp.take(b32, pbase + off)

    oidx = g(0) | (g(1) << 8) | (g(2) << 16) | (g(3) << 24)
    obits = g(4) | (g(5) << 8) | (g(6) << 16) | (g(7) << 24)
    tgt = jnp.where(live, jnp.clip(oidx, 0, n_elems - 1), n_elems)
    mask = jnp.zeros((n_elems + 1,), jnp.bool_).at[tgt].set(True)[:n_elems]
    vbits = (
        jnp.zeros((n_elems + 1,), jnp.int32)
        .at[tgt]
        .set(jnp.where(live, obits, 0))[:n_elems]
    )
    ovals = lax.bitcast_convert_type(vbits, jnp.float32)

    # chain repair, mirroring quant.dequantize's ndim=1 path with traced eb
    _, _, q_ref = _prequant(ovals, _rcp(eb2))
    last = lax.cummax(jnp.where(mask, k, -1))
    adj = jnp.where(mask, q_ref - q, 0)
    carry = jnp.take(adj, jnp.maximum(last, 0))
    q = q + jnp.where(last >= 0, carry, 0)
    x = q.astype(jnp.float32) * eb2
    x = jnp.where(mask, ovals, x)
    return lax.bitcast_convert_type(x, jnp.int32).reshape(nc, c)
