"""Canonical-Huffman entropy stage: the ``deflate-full`` container subsystem.

GPULZ deliberately stops at LZSS; Deflate-class *ratio* needs an entropy
stage over the emitted sections.  This module adds one: a byte-level
canonical Huffman code over each of the two compact container sections
(flags, payload), producing a method-1 container (core/format.py VERSION 2)
whose sections are replaced by

    codebooks (nibble-packed code lengths) + bit counts + gap arrays +
    MSB-first bitstreams

The *gap arrays* are the parallel-decode contribution of "Accelerating
Lossless Data Compression with GPUs" (PAPERS.md): one stored bit offset per
``SUB = 1 << format.DEFAULT_SUB_LOG2`` decoded bytes, so decoding is
embarrassingly parallel across sub-blocks (each lane scans exactly SUB
codewords from its stored entry point) while staying sequential — the
fundamental Huffman constraint — only *within* one.

Layering:

  * host tree building (``huffman_code_lengths`` — promoted here from
    benchmarks/huffman.py, which now consumes it) and an in-graph mirror
    (``huffman_code_lengths_jax``) that reproduces the heapq merge order
    *exactly* (ties broken by (count, id), internal ids above leaf ids), so
    host and traced code lengths are equal bit-for-bit.  ``code_lengths``
    is the single API over both: concrete inputs take the host path,
    tracers the in-graph one.
  * length limiting to ``MAX_CODE_LEN`` (deterministic Kraft repair:
    deepest non-max length first, smallest symbol on ties) plus the
    *stored escape* — if the limited code would expand the section past
    8 bits/byte, every symbol is forced to the 8-bit identity code, which
    bounds the bitstream at the raw section size and makes
    ``format.entropy_max_compressed_bytes`` a hard worst case.
  * ``byte_histogram`` — Pallas reduction on TPU (kernels/lz_entropy.py),
    XLA scatter-add fallback elsewhere, ``REPRO_ENTROPY_PALLAS`` forces.
  * ``encode_section`` / ``decode_section`` — fixed-shape, fully in-graph
    (vmap/shard_map safe; no host callbacks anywhere in the compress or
    decode path).  Decode dispatches to the Pallas gap-array kernel on TPU
    and a ``lax.scan`` sub-block decoder elsewhere.
  * ``compress_entropy`` / ``decode_blob_entropy`` — the ``deflate-full``
    backend/decoder hooks registered in core/pipeline.py: LZSS via the
    platform backend, entropy-code the sections, and on decode rebuild the
    per-chunk aligned sections and hand off to the existing in-VMEM LZSS
    decode chain.

Size limit: bit offsets are int32 in-graph (x64 disabled), so one
dispatch's sections must stay under 2**28 bytes (~256 MiB) — the same
slab-split regime as ``format._le_bytes``.
"""

from __future__ import annotations

import heapq
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import format as fmt

MAX_CODE_LEN = 15  # nibble-packed codebook: one hex digit per symbol
STORED_LEN = 8  # escape code length: identity byte code, no expansion
N_SYMBOLS = 256
_TREE = 2 * N_SYMBOLS - 1  # leaves + at most N-1 merge nodes
# np scalar, NOT jnp: a module-level jnp value created while some caller's
# jit trace triggers the first import of this module would leak a tracer
_INF = np.int32(2**31 - 1)


def _use_pallas(impl) -> bool:
    """Impl selection for the histogram / gap-decode stages.

    ``impl`` is ``"pallas"`` / ``"xla"`` (explicit) or ``None`` (platform
    default: Pallas on TPU, XLA elsewhere — the same convention as the LZSS
    kernels; ``REPRO_ENTROPY_PALLAS=1/0`` overrides the default, e.g. to
    exercise the kernels in interpret mode off-TPU).
    """
    if impl in ("pallas", "xla"):
        return impl == "pallas"
    if impl is not None:
        raise ValueError(f"impl must be 'pallas', 'xla' or None: {impl!r}")
    env = os.environ.get("REPRO_ENTROPY_PALLAS")
    if env is not None:
        return env != "0"
    return jax.default_backend() == "tpu"


# ----------------------------------------------------- host tree building


def huffman_code_lengths(counts: np.ndarray, max_len: int | None = None):
    """Code length per symbol (0 for absent symbols), host heapq build.

    Promoted from benchmarks/huffman.py (which now imports it): the
    Table-3 size estimator and the container entropy stage must agree on
    one definition.  ``max_len`` applies ``limit_code_lengths`` on top.
    """
    counts = np.asarray(counts)
    heap = [(int(c), i) for i, c in enumerate(counts) if c > 0]
    if len(heap) == 1:
        lengths = np.zeros(counts.size, np.int64)
        lengths[heap[0][1]] = 1
        return lengths
    heapq.heapify(heap)
    # internal nodes: (count, id); track merges to recover depths
    parent = {}
    next_id = counts.size
    while len(heap) > 1:
        c1, n1 = heapq.heappop(heap)
        c2, n2 = heapq.heappop(heap)
        parent[n1] = next_id
        parent[n2] = next_id
        heapq.heappush(heap, (c1 + c2, next_id))
        next_id += 1
    lengths = np.zeros(counts.size, np.int64)
    for sym in range(counts.size):
        if counts[sym] == 0:
            continue
        d, node = 0, sym
        while node in parent:
            node = parent[node]
            d += 1
        lengths[sym] = d
    if max_len is not None:
        lengths = limit_code_lengths(lengths, max_len)
    return lengths


def limit_code_lengths(lengths: np.ndarray, max_len: int) -> np.ndarray:
    """Clamp code lengths to ``max_len`` and repair the Kraft sum.

    Clamping over-deep leaves oversubscribes the code space; the repair
    deterministically deepens the symbol with the largest length below
    ``max_len`` (smallest symbol id on ties) until Kraft holds again.  The
    result is a valid (not necessarily optimal) prefix code; exactness is
    what the roundtrip needs, optimality is a few permille at L=15.
    """
    l = np.where(lengths > 0, np.minimum(lengths, max_len), 0).astype(np.int64)
    excess = int(np.where(l > 0, 1 << (max_len - l), 0).sum()) - (1 << max_len)
    while excess > 0:
        cand = np.nonzero((l > 0) & (l < max_len))[0]
        deepest = cand[l[cand] == l[cand].max()][0]
        excess -= 1 << (max_len - int(l[deepest]) - 1)
        l[deepest] += 1
    return l


def container_code_lengths(counts: np.ndarray) -> np.ndarray:
    """The code the container writer uses: limited Huffman + stored escape.

    If the limited code would expand the section (more than 8 bits/byte on
    average), every symbol is forced to the 8-bit identity code — the
    canonical code over all-equal lengths is the identity byte mapping, so
    the bitstream is bounded by the raw section size.  This is what makes
    the worst-case container bound in ``format.entropy_max_compressed_bytes``
    unconditional.
    """
    counts = np.asarray(counts, np.int64)
    l = huffman_code_lengths(counts, max_len=MAX_CODE_LEN)
    # bits > 8*n  <=>  sum(counts * (l - 8)) > 0: the delta form also keeps
    # the in-graph int32 mirror overflow-free (|delta| <= 7 per byte)
    if int((counts * (l - STORED_LEN)).sum()) > 0:
        l = np.full(counts.size, STORED_LEN, np.int64)
    return l


# -------------------------------------------------- in-graph tree building


@jax.jit
def huffman_code_lengths_jax(counts):
    """In-graph mirror of ``huffman_code_lengths`` (no ``max_len``).

    255 masked merge steps over a 511-node arena; each step extracts the
    two lexicographically smallest ``(count, id)`` active nodes — argmin
    over a dense key returns the *first* minimum, which is exactly heapq's
    tie order since internal ids (256+) sort after every leaf id.  Depths
    are recovered by parent-pointer doubling.  Equal to the host build
    bit-for-bit (tests/test_entropy.py pins it on adversarial histograms).
    """
    counts = jnp.asarray(counts, jnp.int32)
    n = counts.shape[0]
    t = 2 * n - 1
    cnt = jnp.zeros(t, jnp.int32).at[:n].set(counts)
    act = jnp.concatenate([counts > 0, jnp.zeros(n - 1, bool)])
    parent = jnp.full(t, -1, jnp.int32)
    n_live = jnp.sum((counts > 0).astype(jnp.int32))

    def merge(k, st):
        cnt, act, parent, na = st
        key = jnp.where(act, cnt, _INF)
        i1 = jnp.argmin(key)
        i2 = jnp.argmin(key.at[i1].set(_INF))
        do = na >= 2
        new = n + k
        cnt = cnt.at[new].set(jnp.where(do, cnt[i1] + cnt[i2], cnt[new]))
        act = act.at[i1].set(act[i1] & ~do)
        act = act.at[i2].set(act[i2] & ~do)
        act = act.at[new].set(act[new] | do)
        parent = parent.at[i1].set(jnp.where(do, new, parent[i1]))
        parent = parent.at[i2].set(jnp.where(do, new, parent[i2]))
        return cnt, act, parent, jnp.where(do, na - 1, na)

    _, _, parent, _ = lax.fori_loop(0, n - 1, merge, (cnt, act, parent, n_live))

    # depth = hops to the root: pointer doubling, 2^9 >= max chain length
    jump, dist = parent, (parent >= 0).astype(jnp.int32)
    for _ in range(9):
        src = jnp.clip(jump, 0, t - 1)
        live = jump >= 0
        dist = dist + jnp.where(live, jnp.take(dist, src), 0)
        jump = jnp.where(live, jnp.take(jump, src), -1)
    lengths = jnp.where(counts > 0, dist[:n], 0)
    # a lone symbol has depth 0 but needs a 1-bit code (host convention)
    return jnp.where((n_live == 1) & (counts > 0), 1, lengths)


def limit_code_lengths_jax(lengths, max_len: int = MAX_CODE_LEN):
    """In-graph mirror of ``limit_code_lengths`` (same repair order)."""
    l = jnp.where(lengths > 0, jnp.minimum(lengths, max_len), 0).astype(jnp.int32)
    excess = jnp.sum(jnp.where(l > 0, 1 << (max_len - l), 0)) - (1 << max_len)

    def repair(st):
        ex, l = st
        key = jnp.where((l > 0) & (l < max_len), l, -1)
        i = jnp.argmax(key)  # deepest non-max length, smallest symbol on ties
        ex = ex - (1 << (max_len - l[i] - 1))
        return ex, l.at[i].set(l[i] + 1)

    _, l = lax.while_loop(lambda st: st[0] > 0, repair, (excess, l))
    return l


def container_code_lengths_jax(counts):
    """In-graph mirror of ``container_code_lengths`` (limit + escape)."""
    counts = jnp.asarray(counts, jnp.int32)
    l = limit_code_lengths_jax(huffman_code_lengths_jax(counts))
    over = jnp.sum(counts * (l - STORED_LEN)) > 0
    return jnp.where(over, jnp.full_like(l, STORED_LEN), l)


def code_lengths(counts, max_len: int = MAX_CODE_LEN):
    """Container code lengths behind one API, host or traced.

    Concrete histograms (numpy arrays, python lists, materialized jnp
    arrays) run the host heapq builder; tracers run the in-graph mirror —
    the two are equal bit-for-bit, so callers never branch.  This is the
    "host tree-building fallback behind the same API" seam: the in-graph
    path is what the fused compress hook uses, the host path is free of
    the 255-step fori_loop for eager callers (benchmarks, tools).
    """
    if isinstance(counts, jax.core.Tracer):
        lengths = limit_code_lengths_jax(huffman_code_lengths_jax(counts), max_len)
        over = jnp.sum(jnp.asarray(counts, jnp.int32) * (lengths - STORED_LEN)) > 0
        return jnp.where(over, jnp.full_like(lengths, STORED_LEN), lengths)
    return container_code_lengths(np.asarray(counts))


# ----------------------------------------------------- canonical code maps


def canonical_tables_jax(lengths):
    """Canonical (MSB-first) code tables from a length assignment.

    Returns a dict:
      ``lengths`` (n,)  the input, int32
      ``codes``   (n,)  codeword per symbol (0 for absent symbols)
      ``first``   (L+1,) first codeword of each length
      ``count``   (L+1,) symbols per length
      ``base``    (L+1,) symbols with a shorter (positive) length
      ``order``   (n,)  symbols sorted by (length, symbol) — the decode map

    Decode-side validity of a window ``cand = win >> (L - l)`` is
    ``first[l] <= cand < first[l] + count[l]``; the canonical construction
    guarantees at most one length matches (shorter-length prefixes of
    longer codes always land at or past ``first[l] + count[l]``).

    Sort-free: ``rank``/``order`` come from a counting construction over
    the (length, symbol) grid, not ``jnp.argsort`` — for a 256-symbol
    alphabet the O(L*n) counting form is cheap and needs no sort lowering.
    """
    l = jnp.asarray(lengths, jnp.int32)
    n = l.shape[0]
    sym = jnp.arange(n, dtype=jnp.int32)
    ls = jnp.arange(MAX_CODE_LEN + 1, dtype=jnp.int32)
    live = l > 0
    onehot = (l[None, :] == ls[:, None]) & live[None, :]  # (L+1, n)
    count = jnp.sum(onehot, axis=1).astype(jnp.int32)
    base = jnp.cumsum(count) - count
    lc = jnp.clip(l, 0, MAX_CODE_LEN)
    # stable (length, symbol) rank: bucket base + position within the bucket
    within = jnp.cumsum(onehot.astype(jnp.int32), axis=1) - onehot
    rank_live = jnp.take(base, lc) + jnp.take_along_axis(
        within, lc[None, :], axis=0
    )[0]
    n_live = jnp.sum(live.astype(jnp.int32))
    rank_dead = n_live + jnp.cumsum((~live).astype(jnp.int32)) - 1
    rank = jnp.where(live, rank_live, rank_dead).astype(jnp.int32)
    order = jnp.zeros(n, jnp.int32).at[rank].set(sym)
    firsts = [jnp.zeros((), jnp.int32)]  # index 0: unused placeholder
    f = jnp.zeros((), jnp.int32)
    for ll in range(1, MAX_CODE_LEN + 1):
        if ll > 1:
            f = (f + count[ll - 1]) << 1
        firsts.append(f)
    first = jnp.stack(firsts)
    codes = jnp.where(
        l > 0, jnp.take(first, lc) + rank - jnp.take(base, lc), 0
    )
    return dict(
        lengths=l,
        codes=codes,
        first=first,
        count=count,
        base=base,
        order=order.astype(jnp.int32),
    )


def canonical_codes(lengths: np.ndarray) -> np.ndarray:
    """Host mirror of the encode map (tests / eager tools)."""
    l = np.asarray(lengths, np.int64)
    order = sorted(range(l.size), key=lambda s: (l[s] if l[s] > 0 else 99, s))
    codes = np.zeros(l.size, np.int64)
    code, prev = 0, 0
    for s in order:
        if l[s] == 0:
            break
        code <<= int(l[s]) - prev
        codes[s] = code
        code += 1
        prev = int(l[s])
    return codes


# --------------------------------------------------------------- histogram


def byte_histogram(buf, start, length, *, impl=None):
    """(256,) int32 counts of ``buf[start : start + length]`` byte values.

    ``buf`` is a flat int32 byte buffer (values 0..255); ``start`` /
    ``length`` may be traced.  Pallas reduction on TPU (or when forced),
    XLA scatter-add fallback elsewhere — identical counts by test.
    """
    b32 = jnp.asarray(buf, jnp.int32)
    if _use_pallas(impl):
        from repro.kernels import ops  # lazy: kernels are optional at import

        return ops.byte_histogram(b32, start, length)
    idx = jnp.arange(b32.shape[0], dtype=jnp.int32)
    in_range = (idx >= start) & (idx < start + length)
    slot = jnp.where(in_range, b32 & 0xFF, N_SYMBOLS)
    return jnp.zeros(N_SYMBOLS + 1, jnp.int32).at[slot].add(1)[:N_SYMBOLS]


# ------------------------------------------------------- section transcode


def encode_section(buf, start, length, lengths, *, cap: int, sub: int | None = None):
    """Bit-pack one section with a canonical code; fixed shapes, in-graph.

    ``buf`` is a flat int32 byte buffer holding the section at dynamic
    ``[start, start + length)``; ``cap`` is the static section capacity.
    Returns ``(stream, nbits, gaps)``: a ``(cap + 8,)`` int32 byte buffer
    whose first ``ceil(nbits / 8)`` entries are live (the stored escape in
    ``container_code_lengths`` guarantees ``nbits <= 8 * length``), the
    total bit count, and the ``(ceil(cap / sub),)`` gap array — the bit
    offset of every ``sub``-th byte's codeword, the decoder's parallel
    entry points.

    The pack is three masked scatter-adds: each codeword (<= 15 bits at a
    bit phase <= 7) lands inside a 24-bit window, i.e. three consecutive
    stream bytes; contributions of adjacent codewords touch disjoint bits,
    so byte-wise addition never carries.
    """
    sub = (1 << fmt.DEFAULT_SUB_LOG2) if sub is None else sub
    tabs = canonical_tables_jax(lengths)
    b32 = jnp.asarray(buf, jnp.int32)
    idx = jnp.arange(cap, dtype=jnp.int32)
    valid = idx < length
    byte = jnp.take(b32, jnp.clip(start + idx, 0, b32.shape[0] - 1)) & 0xFF
    l = jnp.where(valid, jnp.take(tabs["lengths"], byte), 0)
    code = jnp.where(valid, jnp.take(tabs["codes"], byte), 0)
    csum = jnp.cumsum(l)
    off = csum - l
    nbits = csum[-1]
    w = code << (24 - l - (off & 7))
    base = off >> 3
    stream = jnp.zeros(cap + 8, jnp.int32)
    for k in range(3):
        stream = stream.at[base + k].add((w >> (8 * (2 - k))) & 0xFF)
    gaps = jnp.take(off, jnp.arange(-(-cap // sub), dtype=jnp.int32) * sub)
    return stream, nbits, gaps


def decode_section(
    blob, base_byte, gaps, lengths, *, count, cap: int, sub: int | None = None,
    impl=None,
):
    """Inverse of ``encode_section``: gap-array parallel bitstream decode.

    ``blob`` is the whole container as a flat int32 byte buffer,
    ``base_byte`` the (dynamic) byte offset of this section's bitstream,
    ``gaps`` the ``(ceil(cap / sub),)`` bit-offset entry points and
    ``count`` the live decoded byte count (static capacity ``cap``).
    Every sub-block decodes independently from its gap entry — the Pallas
    kernel (TPU) DMAs one fixed-width bitstream window per sub-block into
    VMEM; the XLA fallback is a ``lax.scan`` of ``sub`` codeword steps
    vectorized over all sub-blocks.  Returns ``(cap,)`` int32 bytes, zero
    beyond ``count``.
    """
    sub = (1 << fmt.DEFAULT_SUB_LOG2) if sub is None else sub
    tabs = canonical_tables_jax(lengths)
    nsub = gaps.shape[0]
    if _use_pallas(impl):
        from repro.kernels import ops  # lazy: kernels are optional at import

        wstarts = base_byte + (jnp.asarray(gaps, jnp.int32) >> 3)
        rems = jnp.asarray(gaps, jnp.int32) & 7
        syms = ops.huffman_gap_decode(
            blob, wstarts, rems,
            tabs["first"], tabs["count"], tabs["base"], tabs["order"],
            sub=sub,
        )
    else:
        syms = _decode_scan(blob, base_byte, gaps, tabs, sub=sub)
    flat = syms.reshape(nsub * sub)[:cap]
    return jnp.where(jnp.arange(cap, dtype=jnp.int32) < count, flat, 0)


def _decode_scan(blob, base_byte, gaps, tabs, *, sub: int):
    """XLA gap decoder: scan ``sub`` codeword steps over all sub-blocks."""
    b32 = jnp.asarray(blob, jnp.int32) & 0xFF
    top = b32.shape[0] - 1
    ls = jnp.arange(1, MAX_CODE_LEN + 1, dtype=jnp.int32)
    fc = jnp.take(tabs["first"], ls)
    cn = jnp.take(tabs["count"], ls)

    def step(off, _):
        pos = base_byte + (off >> 3)
        w24 = (
            (jnp.take(b32, jnp.clip(pos, 0, top)) << 16)
            | (jnp.take(b32, jnp.clip(pos + 1, 0, top)) << 8)
            | jnp.take(b32, jnp.clip(pos + 2, 0, top))
        )
        win = (w24 >> (9 - (off & 7))) & ((1 << MAX_CODE_LEN) - 1)
        cand = win[:, None] >> (MAX_CODE_LEN - ls)[None, :]
        ok = (cand >= fc[None, :]) & (cand - fc[None, :] < cn[None, :])
        sel = jnp.argmax(ok, axis=1)  # first (shortest) valid length - 1
        lsel = sel + 1
        csel = jnp.take_along_axis(cand, sel[:, None], axis=1)[:, 0]
        sidx = jnp.take(tabs["base"], lsel) + csel - jnp.take(tabs["first"], lsel)
        sym = jnp.take(tabs["order"], jnp.clip(sidx, 0, N_SYMBOLS - 1))
        return off + lsel, sym

    _, syms = lax.scan(step, jnp.asarray(gaps, jnp.int32), None, length=sub)
    return syms.T  # (nsub, sub)


# ------------------------------------------- container-level hooks (v2)


def compress_entropy(symbols, cfg, orig_bytes=None):
    """The ``deflate-full`` backend's ``compress`` hook.

    Runs the platform LZSS backend (``"auto"``: the single-kernel
    ``fused-mono`` on TPU) for the sections, histograms + entropy-codes
    both, and assembles a method-1 VERSION-2 container.  Fully in-graph —
    vmap (``compress_many``) and shard_map (the sharded runner) see plain
    jnp ops, never a callback.
    """
    from repro.core import pipeline  # lazy: pipeline registers this hook

    nc, c = symbols.shape
    s = cfg.symbol_size
    cb = (c + 7) // 8
    sub = 1 << fmt.DEFAULT_SUB_LOG2
    raw, _ = pipeline._compress_via(
        pipeline.get_backend("auto"), symbols, cfg, orig_bytes
    )
    b32 = raw.astype(jnp.int32)
    n_tokens, payload_sizes = fmt.parse_tables_jax(b32, nc)
    fsz = (n_tokens + 7) // 8
    f_tot = jnp.sum(fsz)
    p_tot = jnp.sum(payload_sizes)
    sec = fmt.HEADER_BYTES + 8 * nc
    flag_cap, pay_cap = nc * cb, nc * c * s

    lf = container_code_lengths_jax(byte_histogram(b32, sec, f_tot))
    lp = container_code_lengths_jax(byte_histogram(b32, sec + f_tot, p_tot))
    stream_f, fbits, gaps_f = encode_section(b32, sec, f_tot, lf, cap=flag_cap)
    stream_p, pbits, gaps_p = encode_section(
        b32, sec + f_tot, p_tot, lp, cap=pay_cap
    )

    cap2 = fmt.entropy_max_compressed_bytes(nc * c * s, s, c)
    out = jnp.zeros((cap2,), jnp.int32)
    out = fmt.write_header_and_tables(
        out,
        symbol_size=s,
        window=cfg.window,
        chunk_symbols=c,
        n_chunks=nc,
        orig_bytes=nc * c * s if orig_bytes is None else orig_bytes,
        payload_total=p_tot,
        flag_total=f_tot,
        n_tokens=n_tokens,
        payload_sizes=payload_sizes,
        method=fmt.METHOD_HUFFMAN,
        sub_log2=fmt.DEFAULT_SUB_LOG2,
    )
    # nibble-packed codebooks + bit counts at static offsets
    out = out.at[sec : sec + 128].set(lf[0::2] | (lf[1::2] << 4))
    out = out.at[sec + 128 : sec + 256].set(lp[0::2] | (lp[1::2] << 4))
    out = out.at[sec + 256 : sec + 264].set(jnp.stack(fmt._le_bytes(fbits, 8)))
    out = out.at[sec + 264 : sec + 272].set(jnp.stack(fmt._le_bytes(pbits, 8)))

    nsub_f = (f_tot + sub - 1) // sub
    nsub_p = (p_tot + sub - 1) // sub
    gbase_f = sec + fmt.ENTROPY_META_FIXED
    gbase_p = gbase_f + 4 * nsub_f

    def put_gaps(out, base, gaps, nsub):
        k = jnp.arange(gaps.shape[0], dtype=jnp.int32)
        live = k < nsub
        for j in range(4):
            pos = jnp.where(live, base + 4 * k + j, cap2)  # OOB writes drop
            out = out.at[pos].add(jnp.where(live, (gaps >> (8 * j)) & 0xFF, 0))
        return out

    out = put_gaps(out, gbase_f, gaps_f, nsub_f)
    out = put_gaps(out, gbase_p, gaps_p, nsub_p)

    fbytes = (fbits + 7) // 8
    pbytes = (pbits + 7) // 8
    sbase_f = gbase_p + 4 * nsub_p
    sbase_p = sbase_f + fbytes

    def put_stream(out, base, stream, nbytes):
        i = jnp.arange(stream.shape[0], dtype=jnp.int32)
        live = i < nbytes
        pos = jnp.where(live, base + i, cap2)  # OOB writes drop
        return out.at[pos].add(jnp.where(live, stream, 0))

    out = put_stream(out, sbase_f, stream_f, fbytes)
    out = put_stream(out, sbase_p, stream_p, pbytes)
    total = sbase_p + pbytes
    return out.astype(jnp.uint8), total


def decode_blob_entropy(
    blob,
    n_tokens,
    payload_sizes,
    *,
    symbol_size: int,
    chunk_symbols: int,
    n_chunks: int,
    chunks_per_block=None,
    impl=None,
):
    """The ``deflate-full`` decoder's ``decode_blob`` hook.

    Parses the method-1 metadata at static offsets, gap-decodes both
    bitstreams back to the compact sections, rebuilds the per-chunk
    aligned flag/payload arrays (``deflate.gather_section``) and hands off
    to the platform LZSS decode chain (``"auto"``: the in-VMEM fused
    decoder on TPU).  Fixed shapes throughout; vmap/shard_map safe.

    The gap sub-block size is pinned to ``format.DEFAULT_SUB_LOG2`` (the
    shapes here are static); ``validate_container`` rejects containers
    recorded with any other value before they reach this trace.
    """
    from repro.core import deflate, pipeline  # lazy: avoid import cycle

    c, s, nc = chunk_symbols, symbol_size, n_chunks
    cb = (c + 7) // 8
    sub = 1 << fmt.DEFAULT_SUB_LOG2
    b32 = jnp.asarray(blob, jnp.int32).reshape(-1) & 0xFF
    sec = fmt.HEADER_BYTES + 8 * nc
    flag_cap, pay_cap = nc * cb, nc * c * s

    fsz = ((jnp.asarray(n_tokens, jnp.int32) + 7) // 8).astype(jnp.int32)
    psz = jnp.asarray(payload_sizes, jnp.int32)
    f_tot = jnp.sum(fsz)
    p_tot = jnp.sum(psz)

    cbf = b32[sec : sec + 128]
    cbp = b32[sec + 128 : sec + 256]
    lf = jnp.stack([cbf & 0xF, (cbf >> 4) & 0xF], axis=1).reshape(-1)
    lp = jnp.stack([cbp & 0xF, (cbp >> 4) & 0xF], axis=1).reshape(-1)

    def u32(off):
        return (
            b32[off] | (b32[off + 1] << 8) | (b32[off + 2] << 16)
            | (b32[off + 3] << 24)
        )

    fbits = u32(sec + 256)  # 4 live bytes of the u64 field (<2 GiB sections)
    pbits = u32(sec + 264)

    def gather_gaps(base, nsub_cap):
        pos = base + 4 * jnp.arange(nsub_cap, dtype=jnp.int32)
        top = b32.shape[0] - 1

        def g(o):
            return jnp.take(b32, jnp.clip(pos + o, 0, top))

        return g(0) | (g(1) << 8) | (g(2) << 16) | (g(3) << 24)

    nsub_f = (f_tot + sub - 1) // sub
    nsub_p = (p_tot + sub - 1) // sub
    gbase_f = sec + fmt.ENTROPY_META_FIXED
    gbase_p = gbase_f + 4 * nsub_f
    gaps_f = gather_gaps(gbase_f, -(-flag_cap // sub))
    gaps_p = gather_gaps(gbase_p, -(-pay_cap // sub))
    sbase_f = gbase_p + 4 * nsub_p
    sbase_p = sbase_f + (fbits + 7) // 8

    flag_flat = decode_section(
        b32, sbase_f, gaps_f, lf, count=f_tot, cap=flag_cap, sub=sub, impl=impl
    )
    pay_flat = decode_section(
        b32, sbase_p, gaps_p, lp, count=p_tot, cap=pay_cap, sub=sub, impl=impl
    )

    flag_off = jnp.cumsum(fsz) - fsz
    pay_off = jnp.cumsum(psz) - psz
    flags = deflate.gather_section(flag_flat, 0, fsz, flag_off, cb)
    payload = deflate.gather_section(pay_flat, 0, psz, pay_off, c * s)

    dec = pipeline.get_decoder("auto")
    return dec.decode(
        flags,
        payload,
        jnp.asarray(n_tokens, jnp.int32),
        symbol_size=s,
        **pipeline._geometry_kw(dec.decode, chunks_per_block),
    )
