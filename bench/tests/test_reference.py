"""The reference decoder reads the program's containers, and nothing else."""

import numpy as np
import pytest

from bench import reference


def program_container(data: np.ndarray, s: int, c: int, backend="xla"):
    from repro.core import lzss

    cfg = lzss.LZSSConfig(symbol_size=s, chunk_symbols=c, backend=backend)
    return lzss.compress(data, cfg).data


def corpus(seed: int, n_bytes: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    runs = rng.integers(0, 6, -(-n_bytes // 8)).repeat(8)[:n_bytes]  # matches
    noise = rng.integers(0, 256, n_bytes).astype(np.uint8)
    keep = rng.random(n_bytes) < 0.3
    return np.where(keep, noise, runs.astype(np.uint8)).astype(np.uint8)


@pytest.mark.parametrize("s", [1, 2, 4])
def test_reference_decodes_program_containers_exactly(s):
    c = 256
    data = corpus(s, 5 * c * s + 3 * s)  # a partial last chunk
    blob = program_container(data, s, c)
    nc = -(-(data.size // s) // c)
    chunks = np.arange(nc)
    bad, n = reference.container_mismatch(
        blob, data, chunks, symbol_size=s, window=128, chunk_symbols=c)
    assert (bad, n) == (0, data.size)
    sym, flags = reference.decode_chunks(
        blob, chunks, symbol_size=s, window=128, chunk_symbols=c,
        orig_bytes=data.size)
    assert not flags.any()
    got = sym.astype(f"<u{s}").view(np.uint8).reshape(-1)[: data.size]
    assert np.array_equal(got, data)


def test_an_altered_payload_byte_is_found():
    s, c = 2, 256
    data = corpus(9, 4 * c * s)
    blob = program_container(data, s, c).copy()
    blob[-1] ^= 0x01  # the last payload byte: a token of the last chunk
    bad, _ = reference.container_mismatch(
        blob, data, np.arange(4), symbol_size=s, window=128, chunk_symbols=c)
    assert bad > 0


def test_a_wrong_token_count_fails_its_chunk():
    s, c = 2, 256
    data = corpus(3, 4 * c * s)
    blob = program_container(data, s, c).copy()
    blob[48] ^= 0x01  # table A: chunk 0's token count
    bad, _ = reference.container_mismatch(
        blob, data, np.arange(4), symbol_size=s, window=128, chunk_symbols=c)
    assert bad >= c * s


@pytest.mark.parametrize("where", ["magic", "size", "truncate"])
def test_a_broken_container_counts_every_byte(where):
    s, c = 2, 256
    data = corpus(3, 4 * c * s)
    blob = program_container(data, s, c).copy()
    if where == "magic":
        blob[0] ^= 0xFF
    elif where == "size":
        blob[16] ^= 0x01  # the header's original byte count
    else:
        blob = blob[:-1]
    bad, n = reference.container_mismatch(
        blob, data, np.arange(4), symbol_size=s, window=128, chunk_symbols=c)
    assert bad == n == data.size


def test_roundtrip_mismatch_counts_bytes_and_size():
    a = np.arange(10, dtype=np.uint8)
    b = a.copy()
    b[3] = 0
    assert reference.roundtrip_mismatch(a, a) == 0
    assert reference.roundtrip_mismatch(b, a) == 1
    assert reference.roundtrip_mismatch(a[:5], a) == 10


def test_sample_holds_first_and_last_chunk_and_follows_the_seed():
    a = reference.sample_chunks(np.random.default_rng(1), 1000, 8)
    b = reference.sample_chunks(np.random.default_rng(1), 1000, 8)
    assert np.array_equal(a, b) and a[0] == 0 and a[-1] == 999
    assert len(reference.sample_chunks(np.random.default_rng(1), 3, 8)) == 3
