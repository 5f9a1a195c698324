"""The plain reference of the ``lossless`` check (``bench/checks/lossless.py``).

That check holds a cell to losslessness: every byte read back equals the
byte written.  Two numbers are compared, each against its limit (0: both
comparisons are exact):

- ``roundtrip_mismatch_bytes``: bytes where what the timed decoder returned
  differs from the field the benchmark made, over the last round trip of
  every field in the window.
- ``container_mismatch_bytes``: bytes where this module's own decoder,
  reading the containers the timed compressor emitted, differs from the
  field.  It decodes a sample of chunks drawn from the seed (always the
  first and the last, partial one) and checks every container's header,
  tables and size in full; a container that fails those counts all its
  field's bytes, a chunk whose tokens are malformed all its bytes.

The decoder is written from the container layout alone (header, tables A/B,
flag section C, payload section D; a flag bit 1 is a 2-byte (length,
offset) pointer, 0 an S-byte little-endian literal, length <= offset <= W)
in numpy, and imports nothing of the program.
"""

from __future__ import annotations

import numpy as np

HEADER_BYTES = 48
MAGIC = b"GPLZ"
_SYM = {1: np.dtype("<u1"), 2: np.dtype("<u2"), 4: np.dtype("<u4")}


def _u(blob: np.ndarray, off: int, n: int) -> int:
    return int.from_bytes(blob[off : off + n].tobytes(), "little")


def _within(x, first, row):
    """Exclusive prefix sum of ``x`` restarted at every chunk's first token."""
    excl = np.cumsum(x) - x
    return excl - np.append(excl, 0)[first][row]


def parse(blob: np.ndarray, *, symbol_size, window, chunk_symbols, orig_bytes):
    """Header and tables of a raw (method 0) container, checked in full.

    Returns ``(n_tokens, payload_sizes, sec_flags, sec_payload)`` or raises
    ``ValueError`` naming the first inconsistency.
    """
    s, c = symbol_size, chunk_symbols
    nc = -(-(-(-orig_bytes // s)) // c)
    if blob.size < HEADER_BYTES or blob[:4].tobytes() != MAGIC:
        raise ValueError("no GPLZ header")
    got = dict(
        version=int(blob[4]),
        symbol_size=int(blob[5]),
        window=_u(blob, 6, 2),
        chunk_symbols=_u(blob, 8, 4),
        n_chunks=_u(blob, 12, 4),
        orig_bytes=_u(blob, 16, 8),
        method=int(blob[40]),
    )
    want = dict(
        version=got["version"] if got["version"] in (1, 2) else 2,
        symbol_size=s, window=window, chunk_symbols=c, n_chunks=nc,
        orig_bytes=orig_bytes, method=0,
    )
    if got != want:
        raise ValueError(f"header {got} != expected {want}")
    payload_total, flag_total = _u(blob, 24, 8), _u(blob, 32, 8)
    tables_end = HEADER_BYTES + 8 * nc
    if blob.size < tables_end:
        raise ValueError("container shorter than its tables")
    tables = blob[HEADER_BYTES:tables_end].view("<u4").astype(np.int64)
    n_tokens, payload_sizes = tables[:nc], tables[nc:]
    if (n_tokens > c).any() or (payload_sizes > c * s).any():
        raise ValueError("a table entry exceeds its chunk")
    flag_sizes = (n_tokens + 7) // 8
    if int(flag_sizes.sum()) != flag_total:
        raise ValueError("flag section size != sum of table A")
    if int(payload_sizes.sum()) != payload_total:
        raise ValueError("payload section size != sum of table B")
    if blob.size != tables_end + flag_total + payload_total:
        raise ValueError(
            f"container is {blob.size} bytes, its header says "
            f"{tables_end + flag_total + payload_total}"
        )
    return n_tokens, payload_sizes, tables_end, tables_end + flag_total


def decode_chunks(blob, chunks, *, symbol_size, window, chunk_symbols,
                  orig_bytes):
    """Decode the listed chunks of a container.

    Returns ``(symbols (k, C), bad (k,) bool)``; a bad chunk's row is
    meaningless.  Raises ``ValueError`` where the header or tables are
    inconsistent.
    """
    s, c = symbol_size, chunk_symbols
    n_tokens, payload_sizes, sec_flags, sec_payload = parse(
        blob, symbol_size=s, window=window, chunk_symbols=c,
        orig_bytes=orig_bytes,
    )
    flag_sizes = (n_tokens + 7) // 8
    flag_off = np.cumsum(flag_sizes) - flag_sizes
    pay_off = np.cumsum(payload_sizes) - payload_sizes
    chunks = np.asarray(chunks, np.int64)
    k = chunks.size
    nt = n_tokens[chunks]
    bad = np.zeros(k, bool)

    # one row per token of the chosen chunks
    row = np.repeat(np.arange(k), nt)
    first = np.cumsum(nt) - nt
    t = np.arange(row.size) - first[row]
    flag_byte = blob[sec_flags + flag_off[chunks][row] + t // 8]
    is_match = ((flag_byte >> (t % 8).astype(np.uint8)) & 1).astype(bool)
    size = np.where(is_match, 2, s)
    read = _within(size, first, row)
    bad |= np.bincount(row, size, k) != payload_sizes[chunks]
    pos = sec_payload + pay_off[chunks][row] + read
    top = blob.size - 1

    def at(j):
        return blob[np.minimum(pos + j, top)].astype(np.int64)

    length = np.where(is_match, at(0), 1)
    offset = np.where(is_match, at(1), 0)
    literal = np.zeros(row.size, np.int64)
    for b in range(s):
        literal |= at(b) << (8 * b)
    wstart = _within(length, first, row)
    bad |= np.bincount(row, length, k) != c
    malformed = is_match & (
        (length < 1) | (offset < length) | (offset > window) | (wstart < offset)
    )
    bad |= np.bincount(row, malformed, k) > 0

    good_tok = ~bad[row]
    row, length = row[good_tok], length[good_tok]
    offset, literal = offset[good_tok], literal[good_tok]
    wstart, is_match = wstart[good_tok], is_match[good_tok]
    # every output symbol: its token, then the symbol it copies from
    tok = np.repeat(np.arange(row.size), length)
    dst = row[tok] * c + wstart[tok] + (
        np.arange(tok.size) - (np.cumsum(length) - length)[tok]
    )
    src = np.where(is_match[tok], dst - offset[tok], dst)
    value = np.where(is_match[tok], 0, literal[tok])
    flat_src = np.arange(k * c)
    flat_src[dst] = src
    flat_val = np.zeros(k * c, np.int64)
    flat_val[dst] = value
    # copies never overlap themselves (length <= offset), so each chain
    # ends at a literal within log2(C) + 1 jumps
    while True:
        nxt = flat_src[flat_src]
        if np.array_equal(nxt, flat_src):
            break
        flat_src = nxt
    return flat_val[flat_src].reshape(k, c), bad


def sample_chunks(rng: np.random.Generator, n_chunks: int, k: int) -> np.ndarray:
    """``k`` chunks drawn without replacement, plus the first and last."""
    pick = rng.choice(n_chunks, size=min(k, n_chunks), replace=False)
    return np.unique(np.concatenate([pick, [0, n_chunks - 1]]))


def container_mismatch(blob, original, chunks, *, symbol_size, window,
                       chunk_symbols) -> tuple[int, int]:
    """(mismatched bytes, compared bytes) of one container against its field."""
    s, c = symbol_size, chunk_symbols
    n = original.size
    try:
        sym, bad = decode_chunks(
            blob, chunks, symbol_size=s, window=window, chunk_symbols=c,
            orig_bytes=n,
        )
    except (ValueError, IndexError):
        return n, n
    got = sym.astype(_SYM[s]).view(np.uint8).reshape(len(chunks), c * s)
    mismatched = compared = 0
    for i, ch in enumerate(chunks):
        lo = int(ch) * c * s
        want = original[lo : min(lo + c * s, n)]
        compared += want.size
        if bad[i]:
            mismatched += want.size
        else:
            mismatched += int(np.count_nonzero(got[i, : want.size] != want))
    return mismatched, compared


def roundtrip_mismatch(decoded: np.ndarray, original: np.ndarray) -> int:
    if decoded.size != original.size:
        return max(decoded.size, original.size)
    return int(np.count_nonzero(decoded != original))
