"""Compile the default-path Pallas kernels for a described TPU v5e.

Interpret mode (every other kernel test) runs the kernel bodies as plain
XLA on the CPU, so it cannot see what Mosaic refuses: lane gathers wider
than one vreg, unaligned lane slices and DMA windows, rank-1 blocks, or a
block that overflows VMEM.  These tests lower and compile each kernel at
real sizes for one chip of a described ``v5e:2x2`` topology — nothing
runs, no chip is needed.  The topology is described only inside the
module-scoped fixture below (never at import), so under several test
workers only the worker that runs this file loads the TPU compiler.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import autotune, format as fmt
from repro.kernels import (
    lz_bitshuffle,
    lz_decode,
    lz_decode_mono,
    lz_entropy,
    lz_fused,
    lz_match,
    lz_scatter,
)

C = autotune.DEFAULT_CHUNK_SYMBOLS
G = autotune.DEFAULT_CHUNKS_PER_BLOCK
W = 128  # LZSSConfig's default window
NBYTES = 64 * 2**20  # input bytes per compile: a 64 MiB field


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip cannot read back what it writes to the persistent
    # cache, so keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _nc(symbol_size, c=C):
    return NBYTES // (symbol_size * c)


def _compress(symbol_size, c, g):
    nc = _nc(symbol_size, c)
    sec_flags = fmt.HEADER_BYTES + 8 * nc
    cap = fmt.max_compressed_bytes(nc * c * symbol_size, symbol_size, c)

    def fn(x):
        return lz_fused.lz_fused_mono_pallas(
            x,
            window=W,
            min_match=max(1, 2 // symbol_size + 1),
            symbol_size=symbol_size,
            cap=cap,
            sec_flags=sec_flags,
            chunks_per_block=g,
        )

    return fn, ((nc, c), jnp.int32)


def _decompress(symbol_size, c, g):
    nc = _nc(symbol_size, c)
    cap = fmt.max_compressed_bytes(nc * c * symbol_size, symbol_size, c)

    def fn(blob, nt, psz):
        return lz_decode_mono.lz_decode_mono_pallas(
            blob,
            nt,
            psz,
            symbol_size=symbol_size,
            chunk_symbols=c,
            n_chunks=nc,
            chunks_per_block=g,
        )

    return fn, ((cap,), jnp.uint8), ((nc,), jnp.int32), ((nc,), jnp.int32)


@pytest.mark.parametrize("symbol_size", [1, 2, 4])
def test_fused_mono_compressor_compiles(one_chip, symbol_size):
    fn, shape = _compress(symbol_size, C, G)
    _compile(fn, one_chip, shape)


@pytest.mark.parametrize("symbol_size", [1, 2, 4])
def test_fused_mono_decoder_compiles(one_chip, symbol_size):
    fn, *shapes = _decompress(symbol_size, C, G)
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("direction", ["compress", "decompress"])
def test_largest_autotune_candidate_compiles(one_chip, direction):
    """The autotuner compiles every candidate on its first TPU call, so the
    largest one it lets through must fit the chip, not just the estimate.
    At the default C the candidates differ only in g, and S=4 carries the
    widest payload rows, so its largest g bounds S=1 and S=2 too."""
    s = 4
    key = autotune.TuneKey("TPU_v5_lite", "u32", s, W, direction, C)
    c, g = max(autotune.candidates(key), key=lambda cg: cg[1])
    make = _compress if direction == "compress" else _decompress
    fn, *shapes = make(s, c, g)
    _compile(fn, one_chip, *shapes)


def test_largest_chunk_candidate_compiles(one_chip):
    """The largest C the autotuner admits, at its largest g, compresses:
    the selection walk's two transposed (C, g) scratches grow with C, so
    (4096, 16) carries the most of them next to the rebuild's rows."""
    s = 4
    c = max(autotune.CHUNK_SYMBOL_CANDIDATES)
    key = autotune.TuneKey("TPU_v5_lite", "u32", s, W, "compress", c)
    c, g = max(autotune.candidates(key), key=lambda cg: cg[1])
    fn, *shapes = _compress(s, c, g)
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("direction", ["compress", "decompress"])
def test_batched_codec_compiles(one_chip, direction):
    """compress_many, the KV tier and the gradient exchange vmap the
    kernels; Pallas cannot batch an HBM-resident (ANY) operand on TPU, so
    the kernels loop over the batch instead (``lz_match.batch_by_loop``)."""
    make = _compress if direction == "compress" else _decompress
    fn, *shapes = make(2, C, G)
    _compile(jax.vmap(fn), one_chip, *(((2,) + s, d) for s, d in shapes))


def test_kernel1_and_scatter_compile(one_chip):
    s = 2
    nc = 64  # the scatter keeps the whole container in VMEM
    sec_flags = fmt.HEADER_BYTES + 8 * nc
    cap = fmt.max_compressed_bytes(nc * C * s, s, C)

    def fn(x):
        k1 = lz_match.lz_kernel1_pallas(
            x, window=W, min_match=2, symbol_size=s, chunks_per_block=G
        )
        return lz_scatter.lz_scatter_pallas(
            x,
            k1["lengths"],
            k1["offsets"],
            k1["emitted"],
            k1["emitted"] & (k1["lengths"] >= 2),
            k1["local_off"],
            k1["n_tokens"],
            k1["payload_sizes"],
            symbol_size=s,
            cap=cap,
            sec_flags=sec_flags,
            chunks_per_block=G,
        )

    _compile(fn, one_chip, ((nc, C), jnp.int32))


def test_split_decoder_compiles(one_chip):
    s = 4
    nc = _nc(s)

    def fn(flags, payload, nt):
        return lz_decode.lz_decode_pallas(
            flags, payload, nt, symbol_size=s, chunks_per_block=G
        )

    _compile(
        fn,
        one_chip,
        ((nc, C // 8), jnp.int32),
        ((nc, C * s), jnp.int32),
        ((nc,), jnp.int32),
    )


def test_gap_decoder_compiles(one_chip):
    sub = 1 << fmt.DEFAULT_SUB_LOG2
    nsub = NBYTES // sub

    def fn(blob, wstarts, rems, first, count, base, order):
        return lz_entropy.huffman_gap_decode_pallas(
            blob, wstarts, rems, first, count, base, order, sub=sub
        )

    _compile(
        fn,
        one_chip,
        ((NBYTES,), jnp.int32),
        ((nsub,), jnp.int32),
        ((nsub,), jnp.int32),
        ((16,), jnp.int32),
        ((16,), jnp.int32),
        ((16,), jnp.int32),
        ((256,), jnp.int32),
    )


def test_byte_histogram_compiles(one_chip):
    def fn(buf, start, length):
        return lz_entropy.byte_histogram_pallas(buf, start, length)

    _compile(
        fn, one_chip, ((NBYTES,), jnp.int32), ((), jnp.int32), ((), jnp.int32)
    )


def test_bitshuffle_both_directions_compile(one_chip):
    n = NBYTES // 2
    _compile(lz_bitshuffle.bitshuffle_pallas, one_chip, ((n,), jnp.uint16))
    _compile(lz_bitshuffle.bitunshuffle_pallas, one_chip, ((2 * n,), jnp.uint8))
    _compile(
        lz_bitshuffle.bitunshuffle_words_pallas, one_chip, ((n,), jnp.int32)
    )


HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.mark.parametrize("direction", ["compress", "decompress"])
def test_lossy_programs_fit_the_chip(one_chip, monkeypatch, direction):
    """The whole ``lossy-fz`` program, TPU branches taken, at 64 MiB.

    A 2- or 4-wide minor dimension tiles to 128 lanes on TPU, so an
    ``(n, 2)`` byte-pair interleave or an ``(n, 4)`` table reshape the
    simplifier hoists onto the padded blob costs 32-64x the bytes (a 100 MB
    field's decode once asked for 32 GB, its compress 12 GB).  A quarter of
    the chip leaves room for what the caller keeps on the device.
    """
    from repro.core import pipeline

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv(autotune.ENABLE_ENV, "0")
    nc = _nc(4)
    if direction == "compress":
        cfg = pipeline.LZSSConfig(symbol_size=4, backend="lossy-fz", lossy_eb=1e-2)

        def fn(x):
            return pipeline.compress_chunks(x, cfg, jnp.int32(NBYTES))

        shapes = [((nc, C), jnp.int32)]
    else:
        # a 30% container: the decoder pads it to the worst-case capacity

        def fn(blob, nt, psz):
            return pipeline.decompress_chunks(
                blob,
                nt,
                psz,
                symbol_size=4,
                chunk_symbols=C,
                n_chunks=nc,
                decoder="lossy-fz",
                method_params=(fmt.LOSSY_MODE_QUANT, fmt.METHOD_RAW),
            )

        shapes = [
            ((NBYTES * 3 // 10,), jnp.uint8),
            ((nc,), jnp.int32),
            ((nc,), jnp.int32),
        ]
    m = _compile(fn, one_chip, *shapes).memory_analysis()
    used = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
    assert used < HBM_BYTES // 4, used
