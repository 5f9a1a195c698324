"""Error-bounded lossy frontend (``lossy-fz``): the bound is a hard invariant.

The method-2 container subsystem (core/lossy.py + core/bitshuffle.py) rides
the same backend/decoder registries as the lossless pipeline; this suite pins
its contract:

  * quant mode (``lossy_eb > 0``): ``max |x' - x| <= eb`` for every finite
    element — strictly, on every adversarial corpus the lossless conformance
    suite uses — and NaN/±inf elements round-trip bit-exactly through the
    outlier section.
  * lossless mode (``lossy_eb == 0``): bit-exact reconstruction, NaN
    payloads included.
  * a lossy blob handed to a lossless decoder (and vice versa) is a clean
    ValueError naming the method byte — mirroring the method-1 entropy
    routing — never silent garbage.
  * ``decompress`` needs container bytes only: the bound and all decode
    geometry are parsed from the header/metadata, no side-channel state.

The hypothesis twin of the bound property lives in tests/test_properties.py
(optional extra); THIS file is what always runs in the CI ``lossy`` lane.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_conformance as conf  # same-dir pytest import
from repro.core import bitshuffle, format as fmt, lossy, lzss, pipeline

EB_SWEEP = [1e-2, 1e-4]


def lossy_cfg(eb, inner="auto", window=64, chunk_symbols=256, **kw):
    return lzss.LZSSConfig(
        symbol_size=4, window=window, chunk_symbols=chunk_symbols,
        backend="lossy-fz", lossy_eb=eb, lossy_inner=inner, **kw,
    )


def assert_within_bound(x: np.ndarray, raw_out: np.ndarray, eb: float):
    """The format's guarantee: finite elements within eb, non-finite exact."""
    rec = raw_out.view(np.float32)
    assert rec.size == x.size
    fin = np.isfinite(x)
    np.testing.assert_array_equal(
        rec[~fin].view(np.uint32), x[~fin].view(np.uint32),
        err_msg="non-finite elements must round-trip bit-exactly",
    )
    if fin.any():
        err = np.max(np.abs(rec[fin] - x[fin]))
        assert err <= np.float32(eb), f"max err {err} > eb {eb}"
    return rec


def smooth_field(n=700, seed=0):
    rng = np.random.default_rng(seed)
    return (
        np.cumsum(rng.normal(size=n)).astype(np.float32) * 0.03
        + np.sin(np.linspace(0, 20, n)).astype(np.float32)
    )


# ------------------------------------------------------------- registry


def test_lossy_pair_registered_both_sides():
    assert "lossy-fz" in lzss.available_backends()
    assert "lossy-fz" in lzss.available_decoders()
    assert pipeline.container_method("lossy-fz") == fmt.METHOD_LOSSY


def test_config_validation():
    with pytest.raises(ValueError, match="lossy_eb"):
        lzss.LZSSConfig(symbol_size=4, backend="lossy-fz")  # no bound
    with pytest.raises(ValueError, match="finite bound"):
        lzss.LZSSConfig(symbol_size=4, backend="lossy-fz", lossy_eb=-1.0)
    with pytest.raises(ValueError, match="finite bound"):
        lzss.LZSSConfig(symbol_size=4, backend="lossy-fz", lossy_eb=np.inf)
    with pytest.raises(ValueError, match="f32"):
        lzss.LZSSConfig(symbol_size=2, backend="lossy-fz", lossy_eb=1e-3)
    for eb in (2.0**-128, 2.0**125, 1e38):
        with pytest.raises(ValueError, match="2\\*\\*-127"):
            lzss.LZSSConfig(symbol_size=4, backend="lossy-fz", lossy_eb=eb)
    for eb in (2.0**-127, 1e37, 1e-46):  # 1e-46 rounds to f32 0: lossless
        lzss.LZSSConfig(symbol_size=4, backend="lossy-fz", lossy_eb=eb)
    with pytest.raises(ValueError, match="lossy_eb is only consulted"):
        lzss.LZSSConfig(symbol_size=4, backend="xla", lossy_eb=1e-3)
    with pytest.raises(ValueError, match="not a lossless"):
        lzss.LZSSConfig(symbol_size=4, backend="lossy-fz", lossy_eb=1e-3,
                        lossy_inner="lossy-fz")
    with pytest.raises(ValueError, match="pair it with backend='lossy-fz'"):
        lzss.LZSSConfig(symbol_size=4, decoder="lossy-fz")
    # decoder='auto' pins to the pair's decoder so round-trips self-route
    assert lossy_cfg(1e-3).decoder == "lossy-fz"


# ------------------------------------------- the bound, corpus x eb sweep


@pytest.mark.parametrize("eb", EB_SWEEP)
def test_bound_on_adversarial_corpora(eb):
    """max |x' - x| <= eb on every corpus of the lossless conformance pool
    (incl. nan-inf runs), reinterpreted as f32 element streams."""
    for name, data in conf.corpora(np.float32, 64).items():
        x = np.ascontiguousarray(data, np.float32)
        res = lzss.compress(x, lossy_cfg(eb))
        rec = assert_within_bound(x, lzss.decompress(res.data), eb)
        assert rec.dtype == np.float32, name


def test_bound_on_smooth_field_and_it_compresses():
    x = smooth_field(4096)
    res = lzss.compress(x, lossy_cfg(1e-3, chunk_symbols=1024))
    assert_within_bound(x, lzss.decompress(res.data), 1e-3)
    # the point of the frontend: smooth f32 fields compress well
    assert res.total_bytes < x.nbytes / 2, res.total_bytes


def test_eb_zero_bit_exact():
    """Lossless passthrough mode: bit-exact, NaN payloads included."""
    x = smooth_field(500)
    x[7] = np.nan
    x[8] = np.float32(np.uint32(0x7FC12345).view(np.float32))  # NaN payload
    x[9:12] = [np.inf, -np.inf, 0.0]
    res = lzss.compress(x, lossy_cfg(0.0))
    out = lzss.decompress(res.data)
    np.testing.assert_array_equal(out, x.view(np.uint8))
    h = fmt.parse_header(np.asarray(res.data))
    assert h.lossy_mode == fmt.LOSSY_MODE_LOSSLESS


def test_outlier_saturation_edge_cases():
    """All-outlier input, eb larger than the data range, denormals."""
    eb = 1e-3
    # every element saturates the i16 delta range -> all-outlier container
    rng = np.random.default_rng(5)
    x = (rng.normal(size=600) * 1e9).astype(np.float32)
    res = lzss.compress(x, lossy_cfg(eb))
    rec = assert_within_bound(x, lzss.decompress(res.data), eb)
    np.testing.assert_array_equal(rec, x)  # outliers are stored exactly
    h = fmt.parse_header(np.asarray(res.data))
    # >=: the first zero-padding element after a saturated tail value can
    # itself saturate the delta chain and join the outlier section
    assert h.n_outliers >= x.size
    # eb larger than the whole data range: everything quantizes to 0
    x = rng.uniform(-0.4, 0.4, 512).astype(np.float32)
    res = lzss.compress(x, lossy_cfg(1.0))
    assert_within_bound(x, lzss.decompress(res.data), 1.0)
    # denormal floats are within eb of 0 for any eb > 0
    x = np.full(512, 1e-42, np.float32)
    x[::7] = -4e-44
    res = lzss.compress(x, lossy_cfg(eb))
    assert_within_bound(x, lzss.decompress(res.data), eb)


def test_header_metadata_and_static_params():
    eb = 2.5e-3
    x = smooth_field(300)
    x[13] = np.inf
    res = lzss.compress(x, lossy_cfg(eb))
    blob = np.asarray(res.data)
    h = fmt.parse_header(blob)
    assert h.method == fmt.METHOD_LOSSY
    assert h.version == fmt.VERSION
    assert h.symbol_size == 4
    assert h.lossy_mode == fmt.LOSSY_MODE_QUANT
    # the stored bound is the f32 rounding of the configured one
    assert np.uint32(h.lossy_eb_bits).view(np.float32) == np.float32(eb)
    assert h.inner_method == pipeline.container_method(
        pipeline.resolve_backend("auto")
    )
    assert h.n_outliers >= 1  # the inf at least
    fmt.validate_container(blob, h)
    dec = pipeline.get_decoder("lossy-fz")
    assert dec.static_params(h) == (h.lossy_mode, h.inner_method)


@pytest.mark.parametrize("eb", [0.0, 1e-3])
def test_deflate_full_inner_stage(eb):
    """The inner lossless stage is pluggable: entropy-coded inner container."""
    x = smooth_field(900, seed=3)
    x[50:54] = np.nan
    res = lzss.compress(x, lossy_cfg(eb, inner="deflate-full"))
    h = fmt.parse_header(np.asarray(res.data))
    assert h.inner_method == fmt.METHOD_HUFFMAN
    out = lzss.decompress(res.data)
    if eb == 0.0:
        np.testing.assert_array_equal(out, x.view(np.uint8))
    else:
        assert_within_bound(x, out, eb)


# --------------------------------------------------- method-byte routing


def test_lossy_blob_rejected_by_lossless_decoders():
    """Satellite: a lossy container fed to a lossless decoder is a clean
    ValueError naming the method byte, mirroring the entropy routing."""
    res = lzss.compress(smooth_field(300), lossy_cfg(1e-3))
    for decoder in lzss.available_decoders():
        if decoder in ("lossy-fz", "sharded"):
            continue
        with pytest.raises(ValueError, match="method byte 2"):
            lzss.decompress(res.data, decoder=decoder)
    # 'auto' routes by the method byte instead of raising
    assert_within_bound(
        smooth_field(300), lzss.decompress(res.data, decoder="auto"), 1e-3
    )


def test_lossless_blob_rejected_by_lossy_decoder():
    data = np.arange(1200, dtype=np.uint8)
    for backend in ("xla", "deflate-full"):
        res = lzss.compress(
            data, lzss.LZSSConfig(symbol_size=1, window=32,
                                  chunk_symbols=256, backend=backend)
        )
        # the raw container names the lossy decoder's method restriction;
        # the entropy container names its own method byte first — either
        # way the mismatch is explicit, never silent garbage
        with pytest.raises(ValueError, match="method-[12]"):
            lzss.decompress(res.data, decoder="lossy-fz")


# ----------------------------------------------------- batched dispatch


def test_batched_roundtrip_ragged():
    eb = 1e-3
    items = [smooth_field(n, seed=n) for n in (300, 1500, 64)]
    items[1][7:9] = [np.inf, np.nan]
    cfg = lossy_cfg(eb)
    batch = lzss.compress_many(items, cfg)
    # the largest item sets the batch's padded chunk geometry, so ITS
    # container is byte-identical to per-item compression (smaller items
    # pad up to the shared geometry, same as the lossless batched path)
    solo = lzss.compress(items[1], cfg)
    assert batch[1].total_bytes == solo.total_bytes
    np.testing.assert_array_equal(
        np.asarray(batch[1].data)[: batch[1].total_bytes],
        np.asarray(solo.data)[: solo.total_bytes],
    )
    outs = lzss.decompress_many([r.data for r in batch])
    for item, out in zip(items, outs):
        assert_within_bound(item, out, eb)


def test_decompress_many_rejects_mixed_and_inhomogeneous_batches():
    x = smooth_field(300)
    lossy = lzss.compress(x, lossy_cfg(1e-3))
    raw = lzss.compress(
        x.view(np.uint8),
        lzss.LZSSConfig(symbol_size=4, window=64, chunk_symbols=256),
    )
    with pytest.raises(ValueError, match="homogeneous"):
        lzss.decompress_many([lossy.data, raw.data])
    # same method but different static decode params is also inhomogeneous
    other = lzss.compress(x, lossy_cfg(1e-3, inner="deflate-full"))
    with pytest.raises(ValueError, match="homogeneous lossy batch"):
        lzss.decompress_many([lossy.data, other.data])
    # an explicit lossless decoder on a lossy batch names the method byte
    with pytest.raises(ValueError, match="method byte 2"):
        lzss.decompress_many([lossy.data], decoder="fused-mono")
    with pytest.raises(ValueError, match="method-2"):
        lzss.decompress_many([raw.data], decoder="lossy-fz")


# --------------------------------------- corruption / truncation guards


@pytest.fixture(scope="module")
def lossy_container():
    x = smooth_field(600, seed=9)
    x[11] = np.inf
    res = lzss.compress(x, lossy_cfg(1e-3))
    return np.asarray(res.data)[: res.total_bytes].copy(), x


def test_truncated_lossy_blob_raises(lossy_container):
    blob, _ = lossy_container
    for cut in (1, 9, blob.size // 2):
        with pytest.raises(ValueError):
            lzss.decompress(blob[:-cut])


def test_corrupted_lossy_metadata_raises(lossy_container):
    blob, _ = lossy_container
    h = fmt.parse_header(blob)
    sec_meta = h.sec_meta
    bad = blob.copy()
    bad[sec_meta + 4] = 7  # lossy mode byte out of range
    with pytest.raises(ValueError, match="lossy mode"):
        lzss.decompress(bad)
    bad = blob.copy()
    bad[sec_meta : sec_meta + 4] = 0  # quant mode with eb bits == 0
    with pytest.raises(ValueError, match="error bound"):
        lzss.decompress(bad)


def test_padded_lossy_blob_still_accepted(lossy_container):
    blob, x = lossy_container
    padded = np.concatenate([blob, np.zeros(257, np.uint8)])
    assert_within_bound(x, lzss.decompress(padded), 1e-3)


# -------------------------------------------------- bitshuffle substage


def test_bitshuffle_wire_layout():
    """Plane b's byte j packs bit b of units 8j..8j+7, unit 8j in the LSB —
    the fixed method-2 wire layout."""
    units = np.zeros(bitshuffle.BLOCK_UNITS, np.uint16)
    units[8 * 3 + 5] = 1 << 11  # bit 11 of unit 29 -> plane 11, byte 3, bit 5
    out = np.asarray(bitshuffle.shuffle(units, impl="xla"))
    assert out.size == bitshuffle.BLOCK_BYTES
    expect = np.zeros_like(out)
    expect[11 * bitshuffle.PLANE_BYTES + 3] = 1 << 5
    np.testing.assert_array_equal(out, expect)


def test_bitshuffle_roundtrip_and_pallas_parity():
    rng = np.random.default_rng(2)
    units = rng.integers(0, 1 << 16, 2 * bitshuffle.BLOCK_UNITS).astype(
        np.uint16
    )
    xla = np.asarray(bitshuffle.shuffle(units, impl="xla"))
    np.testing.assert_array_equal(
        np.asarray(bitshuffle.unshuffle(xla, impl="xla")), units
    )
    # the Pallas kernels (interpret mode off-TPU) are byte-identical
    pal = np.asarray(bitshuffle.shuffle(units, impl="pallas"))
    np.testing.assert_array_equal(pal, xla)
    np.testing.assert_array_equal(
        np.asarray(bitshuffle.unshuffle(pal, impl="pallas")), units
    )
    with pytest.raises(ValueError, match="multiple"):
        bitshuffle.shuffle(units[:100])
    with pytest.raises(ValueError, match="impl"):
        bitshuffle.shuffle(units, impl="cuda")


def test_prequant_scale_is_the_ieee_reciprocal():
    """The pre-quant scale is fl32(1/eb2) on every backend: integer long
    division, not the platform's divide (TPU's is not correctly rounded,
    and an ulp between encoder and decoder mis-anchors the delta chain)."""
    rng = np.random.default_rng(4)
    lo, hi = 1 << 23, 253 << 23  # normal eb2 below 2**126
    bits = rng.integers(lo, hi, 200_000, dtype=np.int64).astype(np.int32)
    edges = np.arange(1, 253, dtype=np.int32) << 23  # powers of two
    bits = np.concatenate([bits, edges, edges | 1, edges | 0x7FFFFF])
    eb2 = bits.view(np.float32)
    got = np.asarray(jax.jit(jax.vmap(lossy._rcp))(jnp.asarray(eb2)))
    np.testing.assert_array_equal(
        got.view(np.int32), (np.float32(1) / eb2).view(np.int32)
    )


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_bitunshuffle_words_inverts_shuffle(impl):
    """The decoder's form: shuffled bytes as little-endian pairs."""
    rng = np.random.default_rng(3)
    units = rng.integers(0, 1 << 16, 3 * bitshuffle.BLOCK_UNITS).astype(
        np.uint16
    )
    b = np.asarray(bitshuffle.shuffle(units, impl="xla")).astype(np.int32)
    words = b[0::2] | (b[1::2] << 8)
    np.testing.assert_array_equal(
        np.asarray(bitshuffle.unshuffle_words(words, impl=impl)), units
    )
    with pytest.raises(ValueError, match="multiple"):
        bitshuffle.unshuffle_words(words[:100], impl=impl)


# ------------------------------------------------------------ consumers


def test_kv_store_lossy_codec():
    from repro.serving.kvcache import KVBlockStore

    eb = 1e-3
    store = KVBlockStore(lossy_eb=eb)
    block = smooth_field(64 * 32).reshape(64, 32)
    block[3, 7] = np.nan
    store.evict_many([("a", block)])
    rec = store.restore_many(["a"])[0]
    assert rec.shape == block.shape and rec.dtype == np.float32
    assert_within_bound(block.reshape(-1), rec.reshape(-1).view(np.uint8), eb)
    assert store.stats.eviction_ratio > 1.0
    # non-f32 blocks cannot carry the bound: clean rejection, data kept
    with pytest.raises(ValueError, match="float32 blocks only"):
        store.evict_many([("b", np.zeros((8, 8), np.float16))])


def test_kv_store_mixed_codec_rounds_restore_in_separate_groups():
    from repro.serving.kvcache import KVBlockStore

    lossless = KVBlockStore()
    ints = (smooth_field(1024) * 100).astype(np.int16).reshape(32, 32)
    lossless.evict_many([("i", ints)])
    lossy = KVBlockStore(lossy_eb=1e-3)
    f32 = smooth_field(1024, seed=4).reshape(32, 32)
    lossy.evict_many([("f", f32)])
    # emulate a store whose codec changed between eviction rounds
    lossless._store["f"] = lossy._store.pop("f")
    out = lossless.restore_many(["i", "f"])
    np.testing.assert_array_equal(out[0], ints)
    assert np.max(np.abs(out[1] - f32)) <= 1e-3
    assert lossless.stats.restore_dispatches == 2


def test_grad_exchange_lossy_wire():
    import jax.numpy as jnp

    from repro.optim import grad_compress

    eb = 1e-4
    lcfg = grad_compress.lossy_grad_config(eb)
    assert lcfg.backend == "lossy-fz" and lcfg.lossy_eb == eb
    g = jnp.asarray(smooth_field(4096, seed=6) * 0.01)
    wire = grad_compress.compress_leaf(g, ratio_cap=1.0, lossy_eb=eb)
    out = grad_compress.decompress_leaf(
        wire, g.shape, ratio_cap=1.0, lossy_eb=eb
    )
    used_lz = np.asarray(wire["used_lz"])
    assert used_lz.all(), "smooth gradients must fit the lossy wire budget"
    err = float(np.max(np.abs(np.asarray(out) - np.asarray(g))))
    assert err <= eb, err


def test_checkpoint_lossy_f32_leaves(tmp_path):
    from repro.checkpoint.manager import CheckpointManager

    eb = 1e-3
    state = {
        "w": smooth_field(8192, seed=8).reshape(128, 64),
        "emb": (smooth_field(2048, seed=9) * 50).astype(np.int16),
    }
    mgr = CheckpointManager(str(tmp_path), lz_lossy_eb=eb)
    mgr.save(state, 1)
    restored, step = mgr.restore_latest(state)
    assert step == 1
    np.testing.assert_array_equal(restored["emb"], state["emb"])  # lossless
    err = np.max(np.abs(restored["w"] - state["w"]))
    assert restored["w"].dtype == np.float32 and err <= eb
    # lossy leaves CRC the stored blob: corruption still fails the restore
    import json

    d = tmp_path / "step_00000001"
    man = json.loads((d / "manifest.json").read_text())
    entry = {e["name"]: e for e in man["leaves"]}["w"]
    assert entry["lossy"] is True
    blob_path = d / entry["file"]
    buf = bytearray(blob_path.read_bytes())
    buf[len(buf) // 2] ^= 0xFF
    blob_path.write_bytes(bytes(buf))
    with pytest.raises(IOError, match="CRC mismatch"):
        mgr.restore(state, 1)
