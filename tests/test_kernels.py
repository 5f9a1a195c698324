"""Pallas kernels vs pure-jnp oracles — shape/dtype/config sweeps in
interpret mode (TPU is the compile target; interpret executes the kernel
body on CPU for correctness).  Integer outputs => exact equality."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import decode, deflate
from repro.core import format as fmt
from repro.core.pipeline import LZSSConfig, get_backend
from repro.kernels import lz_decode as kdec, lz_match as kmod, ref
from repro.kernels import lz_scatter as kscat


def _data(nc, c, vocab, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, vocab, size=(nc, c)).astype(np.int32))


@pytest.mark.parametrize("c", [128, 256, 512])
@pytest.mark.parametrize("w", [8, 32, 128])
@pytest.mark.parametrize("g", [2, 8])
def test_match_kernel_sweep(c, w, g):
    syms = _data(5, c, 4, c + w)
    got_l, got_o = kmod.lz_match_pallas(
        syms, window=w, chunks_per_block=g, interpret=True
    )
    exp_l, exp_o = ref.lz_match(syms, window=w)
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(exp_l))
    np.testing.assert_array_equal(np.asarray(got_o), np.asarray(exp_o))


@pytest.mark.parametrize("w", [17, 255])
def test_match_kernel_odd_windows(w):
    syms = _data(3, 192, 2, w)
    got_l, got_o = kmod.lz_match_pallas(
        syms, window=w, chunks_per_block=4, interpret=True
    )
    exp_l, exp_o = ref.lz_match(syms, window=w)
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(exp_l))
    np.testing.assert_array_equal(np.asarray(got_o), np.asarray(exp_o))


@pytest.mark.parametrize("s,mm", [(1, 3), (2, 2), (4, 1)])
@pytest.mark.parametrize("c", [128, 512])
def test_fused_kernel1_sweep(s, mm, c):
    syms = _data(4, c, 6, s * c)
    got = kmod.lz_kernel1_pallas(
        syms, window=32, min_match=mm, symbol_size=s,
        chunks_per_block=4, interpret=True,
    )
    exp = ref.lz_kernel1(syms, window=32, min_match=mm, symbol_size=s)
    for k in exp:
        np.testing.assert_array_equal(
            np.asarray(got[k]), np.asarray(exp[k]), err_msg=f"field {k}"
        )


def _walk_pattern(pattern, nc, c, s, seed):
    """(nc, C) symbols that steer the selection walk through one edge case.

    ``unique`` is distinct within any 256 symbols (no match at W=255);
    the other patterns plant copies into it or replace it."""
    rng = np.random.default_rng(seed)
    j = np.arange(c)
    x = np.stack([(j + 37 * r) % 256 for r in range(nc)])
    mm = max(1, 2 // s + 1)
    if pattern == "max_run":
        # a 255-symbol block repeated: length-255 matches at offset 255
        # that cross several 128-lane blocks and the chunk end
        block = rng.integers(0, 1 << min(8 * s, 16), size=(nc, 255))
        x = np.tile(block, -(-c // 255) + 1)[:, :c]
    elif pattern == "block_edge":
        # matches of 10 that end exactly on each 128-lane block and the chunk
        # end, so the walk lands on the block's first position
        for t in list(range(128, c, 128)) + [c]:
            if t >= 26:
                x[:, t - 10 : t] = x[:, t - 26 : t - 16]
    elif pattern == "min_match_edge":
        # copies of exactly min_match symbols and of one fewer, at offset 5
        for i, p in enumerate(range(12, c - 8, 9)):
            n = mm if i % 2 else mm - 1
            x[:, p : p + n] = x[:, p - 5 : p - 5 + n]
    return jnp.asarray(x.astype(np.int32))


@pytest.mark.parametrize("s", [1, 2, 4])
@pytest.mark.parametrize(
    "pattern", ["unique", "max_run", "block_edge", "min_match_edge"]
)
@pytest.mark.parametrize("g", [1, 8])
@pytest.mark.parametrize("c", [64, 200, 2048])
def test_selection_walk_matches_reference(c, g, pattern, s):
    """The transposed walk emits exactly the reference's tokens."""
    mm = max(1, 2 // s + 1)
    syms = _walk_pattern(pattern, g + 1, c, s, c + g)
    got = kmod.lz_kernel1_pallas(
        syms, window=255, min_match=mm, symbol_size=s,
        chunks_per_block=g, interpret=True,
    )
    exp = ref.lz_kernel1(syms, window=255, min_match=mm, symbol_size=s)
    for k in exp:
        np.testing.assert_array_equal(
            np.asarray(got[k]), np.asarray(exp[k]), err_msg=f"field {k}"
        )


def test_kernel_symbol_dtypes():
    """Symbols packed from u8/u16/u32 views (incl. negative int32 patterns)."""
    rng = np.random.default_rng(0)
    raw = rng.integers(0, 2**32 - 1, size=(2, 256), dtype=np.uint32)
    raw[:, 50:70] = raw[:, 10:30]  # plant repeats
    syms = jnp.asarray(raw.view(np.int32))
    got_l, got_o = kmod.lz_match_pallas(syms, window=64, interpret=True)
    exp_l, exp_o = ref.lz_match(syms, window=64)
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(exp_l))
    np.testing.assert_array_equal(np.asarray(got_o), np.asarray(exp_o))


def test_kernel_grid_padding():
    """nc not divisible by chunks_per_block."""
    syms = _data(3, 128, 3, 1)
    got_l, _ = kmod.lz_match_pallas(
        syms, window=16, chunks_per_block=8, interpret=True
    )
    exp_l, _ = ref.lz_match(syms, window=16)
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(exp_l))


# ------------------------------------------------------- fused decoder


def _decode_sections(nc, c, s, seed):
    """Real per-chunk aligned flag/payload sections via the encode pipeline."""
    rng = np.random.default_rng(seed)
    raw = np.repeat(rng.integers(0, 6, nc * c // 2), 2)[: nc * c]
    syms = jnp.asarray(raw.reshape(nc, c).astype(np.int32))
    cfg = LZSSConfig(symbol_size=s, window=16, chunk_symbols=c)
    k1 = get_backend("xla").kernel1(syms, cfg)
    flag_bytes, _ = deflate.pack_flags(
        k1["emitted"], k1["use_match"], n_tokens=k1["n_tokens"]
    )
    payload = deflate.build_chunk_payloads(
        syms, k1["lengths"], k1["offsets"], k1, symbol_size=s
    )
    return flag_bytes, payload, k1["n_tokens"], syms


@pytest.mark.parametrize("s", [1, 2, 4])
@pytest.mark.parametrize("c", [64, 128])
@pytest.mark.parametrize("g", [2, 8])
def test_decode_kernel_sweep(s, c, g):
    fb, pay, ntok, syms = _decode_sections(5, c, s, seed=s * c + g)
    got = kdec.lz_decode_pallas(
        fb, pay, ntok, symbol_size=s, chunks_per_block=g, interpret=True
    )
    exp = decode.decode_parallel(fb, pay, ntok, symbol_size=s)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(syms))


def test_decode_kernel_non_pow2_chunk_and_padding():
    """C not a power of two + nc not divisible by chunks_per_block."""
    fb, pay, ntok, syms = _decode_sections(3, 72, 2, seed=9)
    got = kdec.lz_decode_pallas(
        fb, pay, ntok, symbol_size=2, chunks_per_block=8, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(syms))


def test_offsets_kernel_matches_global_offsets():
    """Fused Kernel II == deflate.global_offsets (both prefix sums + totals)."""
    rng = np.random.default_rng(3)
    ntok = jnp.asarray(rng.integers(1, 100, 11).astype(np.int32))
    paysz = jnp.asarray(rng.integers(0, 256, 11).astype(np.int32))
    flag_sizes = (ntok + 7) // 8
    exp_po, exp_pt, exp_fo, exp_ft = deflate.global_offsets(paysz, flag_sizes)
    fo, po, ft, pt = kscat.lz_global_offsets_pallas(ntok, paysz, interpret=True)
    assert int(ft) == int(exp_ft)
    assert int(pt) == int(exp_pt)
    np.testing.assert_array_equal(np.asarray(fo)[:11], np.asarray(exp_fo))
    # pay offsets come out pre-based past the flag section
    np.testing.assert_array_equal(
        np.asarray(po)[:11], np.asarray(exp_po) + int(exp_ft)
    )


def _scatter_reference(syms, k1, s):
    """The unfused XLA tail's section bytes (Kernels II+III), header left 0."""
    nc, c = syms.shape
    flag_bytes, flag_sizes = deflate.pack_flags(
        k1["emitted"], k1["use_match"], n_tokens=k1["n_tokens"]
    )
    payload = deflate.build_chunk_payloads(
        syms, k1["lengths"], k1["offsets"], k1, symbol_size=s
    )
    pay_off, pay_total, flag_off, flag_total = deflate.global_offsets(
        k1["payload_sizes"], flag_sizes
    )
    cap = fmt.max_compressed_bytes(nc * c * s, s, c)
    sec_flags = fmt.HEADER_BYTES + 8 * nc
    out = jnp.zeros((cap,), jnp.int32)
    out = deflate.scatter_section(out, sec_flags, flag_bytes, flag_sizes, flag_off)
    out = deflate.scatter_section(
        out, sec_flags + flag_total, payload, k1["payload_sizes"], pay_off
    )
    return out, flag_total, pay_total, cap, sec_flags


@pytest.mark.parametrize("s", [1, 2, 4])
@pytest.mark.parametrize("c", [64, 128])
@pytest.mark.parametrize("g", [2, 8])
def test_scatter_kernel_sweep(s, c, g):
    """Fused Kernel II+III == the XLA deflate tail, byte for byte."""
    rng = np.random.default_rng(s * c + g)
    raw = np.repeat(rng.integers(0, 6, 5 * c // 2), 2)[: 5 * c]
    syms = jnp.asarray(raw.reshape(5, c).astype(np.int32))
    cfg = LZSSConfig(symbol_size=s, window=16, chunk_symbols=c)
    k1 = get_backend("xla").kernel1(syms, cfg)
    exp, exp_ft, exp_pt, cap, sec_flags = _scatter_reference(syms, k1, s)
    got, ft, pt = kscat.lz_scatter_pallas(
        syms, k1["lengths"], k1["offsets"], k1["emitted"], k1["use_match"],
        k1["local_off"], k1["n_tokens"], k1["payload_sizes"],
        symbol_size=s, cap=cap, sec_flags=sec_flags, chunks_per_block=g,
        interpret=True,
    )
    assert int(ft) == int(exp_ft)
    assert int(pt) == int(exp_pt)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


def test_scatter_kernel_grid_padding_exceeds_offset_lanes():
    """nc a multiple of 128 with a chunks_per_block that does not divide 128:
    the scatter grid (129 chunk rows) outruns pass 1's 128-lane offset
    padding, which must be extended — a regression for an OOB scalar-prefetch
    read."""
    rng = np.random.default_rng(11)
    raw = np.repeat(rng.integers(0, 5, 128 * 16), 2)[: 128 * 32]
    syms = jnp.asarray(raw.reshape(128, 32).astype(np.int32))
    cfg = LZSSConfig(symbol_size=1, window=8, chunk_symbols=32)
    k1 = get_backend("xla").kernel1(syms, cfg)
    exp, exp_ft, exp_pt, cap, sec_flags = _scatter_reference(syms, k1, 1)
    got, ft, pt = kscat.lz_scatter_pallas(
        syms, k1["lengths"], k1["offsets"], k1["emitted"], k1["use_match"],
        k1["local_off"], k1["n_tokens"], k1["payload_sizes"],
        symbol_size=1, cap=cap, sec_flags=sec_flags, chunks_per_block=3,
        interpret=True,
    )
    assert int(ft) == int(exp_ft)
    assert int(pt) == int(exp_pt)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


def test_scatter_kernel_all_literal_worst_case():
    """Noise input (all-literal chunks fill the worst-case capacity): the
    grid-padded rows' clamped windows must stay in bounds and write nothing."""
    rng = np.random.default_rng(7)
    syms = jnp.asarray(rng.integers(0, 2**16, (3, 64)).astype(np.int32))
    cfg = LZSSConfig(symbol_size=2, window=16, chunk_symbols=64)
    k1 = get_backend("xla").kernel1(syms, cfg)
    exp, exp_ft, exp_pt, cap, sec_flags = _scatter_reference(syms, k1, 2)
    got, ft, pt = kscat.lz_scatter_pallas(
        syms, k1["lengths"], k1["offsets"], k1["emitted"], k1["use_match"],
        k1["local_off"], k1["n_tokens"], k1["payload_sizes"],
        symbol_size=2, cap=cap, sec_flags=sec_flags, chunks_per_block=8,
        interpret=True,
    )
    assert int(pt) == int(exp_pt)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


def test_decode_kernel_empty_and_full_chunks():
    """All-zero chunks (max matches) and token counts of zero decode cleanly."""
    fb, pay, ntok, syms = _decode_sections(2, 64, 1, seed=1)
    # zero out the second chunk's tokens: kernel must emit zero symbols
    ntok = ntok.at[1].set(0)
    fb = fb.at[1].set(0)
    pay = pay.at[1].set(0)
    got = kdec.lz_decode_pallas(
        fb, pay, ntok, symbol_size=1, chunks_per_block=2, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(got)[0], np.asarray(syms)[0])
    np.testing.assert_array_equal(np.asarray(got)[1], np.zeros(64, np.int32))
