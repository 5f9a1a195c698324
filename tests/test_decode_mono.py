"""Single-launch decoder (``fused-mono``, kernels/lz_decode_mono.py) and the
chunk-geometry autotuner (core/autotune.py).

Covers what is unique to the decode-mono path: the one-Pallas-launch /
zero-gather property (counter tests), symbol identity against the
paper-faithful scan oracle and the reference decoders across the S x W
sweep, golden-corpus blobs decoded through fused-mono, and the autotuner's
cache determinism (second call hits the cache, no re-sweep), corrupted-file
recovery, disabled-mode bit-exactness and geometry validation.  The generic
every-decoder sweeps in tests/test_decoders.py / test_conformance.py pick
``fused-mono`` up automatically via the registry."""

import json
import pathlib

import numpy as np
import pytest

from repro.core import autotune, decode as decode_mod, deflate
from repro.core import format as fmt, lzss, pipeline
from repro.kernels import ops

GOLDEN_DIR = pathlib.Path(__file__).resolve().parent / "golden"


def _corpus(seed, n=1500, dtype=np.uint16):
    rng = np.random.default_rng(seed)
    runs = np.repeat(rng.integers(0, 16, n // 4), rng.integers(1, 8, n // 4))
    noise = rng.integers(0, 256, n // 4)
    return np.concatenate([runs, noise, runs]).astype(dtype)[:n]


# -------------------------------------------- one launch, zero gathers


def _count_pallas_and_gathers(fn, monkeypatch):
    """Run ``fn`` counting pallas_call sites AND deflate.gather_section
    calls executed (at trace time — callers must use fresh geometry so jit
    caches don't swallow the entries)."""
    from jax.experimental import pallas as pl_mod

    calls = {"pallas": 0, "gather": 0}
    real_pc = pl_mod.pallas_call
    real_gs = deflate.gather_section

    def counting_pc(*args, **kwargs):
        calls["pallas"] += 1
        return real_pc(*args, **kwargs)

    def counting_gs(*args, **kwargs):
        calls["gather"] += 1
        return real_gs(*args, **kwargs)

    monkeypatch.setattr(pl_mod, "pallas_call", counting_pc)
    monkeypatch.setattr(deflate, "gather_section", counting_gs)
    fn()
    return calls["pallas"], calls["gather"]


def test_fused_mono_decode_is_exactly_one_pallas_call(monkeypatch):
    """Decode via fused-mono must be ONE kernel launch with the section
    gathers fused in (zero deflate.gather_section calls); the split paths
    issue two HBM-staged gathers each (plus the decode kernel for
    ``fused``) — at least two dispatches where fused-mono has one."""
    data = _corpus(31)
    # unusual geometry => fresh jit traces, so kernel entries are observed
    cfg = lzss.LZSSConfig(symbol_size=2, window=31, chunk_symbols=88)
    res = lzss.compress(data, cfg)  # xla backend: no pallas in compress

    n_pallas, n_gather = _count_pallas_and_gathers(
        lambda: lzss.decompress(res.data, decoder="fused-mono"), monkeypatch
    )
    assert (n_pallas, n_gather) == (1, 0)

    n_pallas, n_gather = _count_pallas_and_gathers(
        lambda: lzss.decompress(res.data, decoder="fused"), monkeypatch
    )
    assert (n_pallas, n_gather) == (1, 2)  # split path: gathers + kernel

    n_pallas, n_gather = _count_pallas_and_gathers(
        lambda: lzss.decompress(res.data, decoder="xla-parallel"), monkeypatch
    )
    assert (n_pallas, n_gather) == (0, 2)


def test_decode_mono_routes_through_kernel(monkeypatch):
    """decoder='fused-mono' must enter ops.lz_decode_mono; the split
    decoders must not."""
    calls = {"n": 0}
    real = ops.lz_decode_mono

    def counting(*args, **kwargs):
        calls["n"] += 1
        return real(*args, **kwargs)

    monkeypatch.setattr(ops, "lz_decode_mono", counting)
    data = _corpus(32)
    cfg = lzss.LZSSConfig(symbol_size=2, window=34, chunk_symbols=96)
    res = lzss.compress(data, cfg)
    lzss.decompress(res.data, decoder="xla-parallel")
    lzss.decompress(res.data, decoder="fused")
    assert calls["n"] == 0
    out = lzss.decompress(res.data, decoder="fused-mono")
    assert calls["n"] == 1
    assert np.array_equal(out, data.view(np.uint8).reshape(-1))


# ------------------------------------------- symbol identity, S x W sweep


@pytest.mark.parametrize("symbol_size", [1, 2, 4])
@pytest.mark.parametrize("window", [32, 255])
def test_decode_mono_symbol_identity_sweep(symbol_size, window):
    """fused-mono must be symbol-identical to xla-parallel AND the original
    bytes across the S x W grid (small C keeps interpret mode fast)."""
    data = _corpus(symbol_size * 10 + window, n=1200)
    cfg = lzss.LZSSConfig(
        symbol_size=symbol_size, window=window, chunk_symbols=64
    )
    res = lzss.compress(data, cfg)
    raw = data.view(np.uint8).reshape(-1)
    mono = lzss.decompress(res.data, decoder="fused-mono")
    assert np.array_equal(
        mono, lzss.decompress(res.data, decoder="xla-parallel")
    )
    assert np.array_equal(mono, raw)


def test_decode_mono_matches_scan_oracle_on_sections():
    """Kernel-level oracle check: the one-launch kernel's symbols must equal
    the paper-faithful sequential walk (decode_scan) run on the explicitly
    gathered sections of the same container."""
    import jax.numpy as jnp

    data = _corpus(33, n=2000)
    cfg = lzss.LZSSConfig(symbol_size=2, window=64, chunk_symbols=128)
    res = lzss.compress(data, cfg)
    h, n_tokens, payload_sizes = fmt.validate_container(res.data)
    blob = jnp.asarray(res.data).astype(jnp.int32)
    nt = jnp.asarray(n_tokens)
    psz = jnp.asarray(payload_sizes)
    fsz = (nt + 7) // 8
    fcs = jnp.cumsum(fsz)
    pcs = jnp.cumsum(psz)
    sec_flags = fmt.HEADER_BYTES + 8 * h.n_chunks
    flag_bytes = deflate.gather_section(
        blob, sec_flags, fsz, fcs - fsz, (h.chunk_symbols + 7) // 8
    )
    payload = deflate.gather_section(
        blob,
        sec_flags + fcs[-1],
        psz,
        pcs - psz,
        h.chunk_symbols * h.symbol_size,
    )
    want = decode_mod.decode_scan(
        flag_bytes, payload, nt, symbol_size=h.symbol_size
    )
    got = ops.lz_decode_mono(
        blob,
        nt,
        psz,
        symbol_size=h.symbol_size,
        chunk_symbols=h.chunk_symbols,
        n_chunks=h.n_chunks,
    )
    assert np.array_equal(np.asarray(got), np.asarray(want))


def _golden_cases():
    # method-0 (raw) blobs only: method-1 entropy containers route
    # exclusively through the "deflate-full" decoder by design (the
    # mismatch ValueError has its own test in tests/test_decoders.py)
    cases = [
        p
        for p in sorted(GOLDEN_DIR.glob("*.gplz"))
        if fmt.parse_header(np.frombuffer(p.read_bytes(), np.uint8)).method
        == fmt.METHOD_RAW
    ]
    assert cases, f"raw golden cases missing under {GOLDEN_DIR}"
    return cases


@pytest.mark.parametrize("gold", _golden_cases(), ids=lambda p: p.stem)
def test_golden_corpus_decodes_through_fused_mono(gold):
    """The checked-in golden blobs (the pinned wire format) must decode
    through the single-launch path — not just freshly produced containers."""
    inp = gold.with_name(f"{gold.stem}.input.bin")
    data = np.frombuffer(inp.read_bytes(), np.uint8)
    blob = np.frombuffer(gold.read_bytes(), np.uint8)
    assert np.array_equal(lzss.decompress(blob, decoder="fused-mono"), data)


def test_decode_mono_batched_ragged_roundtrip():
    """decompress_many through fused-mono (the vmapped decode_blob hook)
    reconstructs a ragged batch exactly."""
    rng = np.random.default_rng(34)
    items = [
        np.repeat(rng.integers(0, 8, 60), rng.integers(1, 6, 60)).astype(
            np.uint8
        ),
        rng.integers(0, 4, 900).astype(np.uint8),
        np.zeros(200, np.uint8),
    ]
    cfg = lzss.LZSSConfig(symbol_size=1, window=32, chunk_symbols=128)
    batch = lzss.compress_many(items, cfg)
    outs = lzss.decompress_many(batch, decoder="fused-mono")
    for item, out in zip(items, outs):
        assert np.array_equal(out, item)


# ------------------------------------------------------------- autotuner


@pytest.fixture
def tuned_env(tmp_path, monkeypatch):
    """Tuning force-enabled against an isolated cache file."""
    path = tmp_path / "autotune.json"
    monkeypatch.setenv(autotune.ENABLE_ENV, "1")
    monkeypatch.setenv(autotune.CACHE_ENV, str(path))
    autotune.reset()
    yield path
    autotune.reset()


def _key(chunk_symbols=64):
    return autotune.TuneKey(
        device_kind=autotune.device_kind(),
        dtype="u16",
        symbol_size=2,
        window=0,
        direction="decompress",
        chunk_symbols=chunk_symbols,
    )


def test_autotune_cache_written_then_hit_no_resweep(tuned_env):
    """First call sweeps and persists; the second (memo) and a fresh-process
    load (reset + reread) both return the same geometry with ZERO further
    measure calls — the determinism contract restore paths rely on."""
    key = _key()
    calls = {"n": 0}

    def measure(c, g):
        calls["n"] += 1
        return 1.0 / (c * g)  # deterministic: biggest candidate wins

    geom = autotune.best_geometry(key, measure)
    n_sweep = len(autotune.candidates(key))
    assert calls["n"] == n_sweep
    assert tuned_env.exists()
    autotune.validate_cache(json.loads(tuned_env.read_text()))

    # second call: in-process memo hit, no re-sweep
    assert autotune.best_geometry(key, measure) == geom
    assert calls["n"] == n_sweep

    # fresh process simulated: memo dropped, the persisted file answers
    autotune.reset()
    assert autotune.best_geometry(key, measure) == geom
    assert calls["n"] == n_sweep


def test_autotune_corrupted_cache_recovers(tuned_env):
    """A truncated/garbage cache file must be treated as empty — re-tuned
    and rewritten valid, never crashed on or trusted."""
    tuned_env.write_text('{"version": 1, "entries": {"k": "garbage"')
    key = _key()
    calls = {"n": 0}

    def measure(c, g):
        calls["n"] += 1
        return 1.0 / (c * g)

    geom = autotune.best_geometry(key, measure)
    assert calls["n"] == len(autotune.candidates(key))  # re-swept
    assert geom in autotune.candidates(key)
    autotune.validate_cache(json.loads(tuned_env.read_text()))  # rewritten


def test_autotune_disabled_is_static_geometry(monkeypatch):
    """REPRO_AUTOTUNE=0 must reproduce the pre-autotuner static geometry —
    and the containers it yields — bit-exactly."""
    data = _corpus(35)
    cfg = lzss.LZSSConfig(symbol_size=2, window=32, chunk_symbols=64)
    baseline = lzss.compress(data, cfg)

    monkeypatch.setenv(autotune.ENABLE_ENV, "0")
    autotune.reset()
    try:
        assert not autotune.enabled()
        assert autotune.best_geometry(_key()) == (
            64,
            autotune.DEFAULT_CHUNKS_PER_BLOCK,
        )
        pinned = lzss.compress(data, cfg)
        assert np.array_equal(pinned.data, baseline.data)
        assert np.array_equal(
            lzss.decompress(pinned.data, decoder="fused-mono"),
            data.view(np.uint8).reshape(-1),
        )
    finally:
        autotune.reset()


def test_autotune_never_sweeps_inside_a_trace(tuned_env):
    """A best_geometry call staged under jit must NOT run the timed sweep:
    block_until_ready no-ops on tracers, so perf_counter would time tracing
    overhead and the persisted 'winner' would be noise governing all future
    runs.  Under a trace an untuned key serves the deterministic fallback,
    unpersisted; a previously (eagerly) tuned key serves its cache hit."""
    import jax
    import jax.numpy as jnp

    key = _key()
    calls = {"n": 0}

    def measure(c, g):
        calls["n"] += 1
        return 1.0 / (c * g)

    got = []

    def traced(x):
        got.append(autotune.best_geometry(key, measure))
        return x

    jax.jit(traced)(jnp.zeros(()))
    assert calls["n"] == 0  # no sweep staged into the trace
    assert got == [autotune.fallback(key)]
    assert not tuned_env.exists()  # nothing persisted

    # an eager call still tunes (the in-trace fallback was not memoized) …
    geom = autotune.best_geometry(key, measure)
    assert calls["n"] == len(autotune.candidates(key))
    # … and a subsequent in-trace call now serves that tuned result
    got.clear()
    jax.jit(traced)(jnp.zeros((2,)))  # new shape => genuine retrace
    assert got == [geom] and calls["n"] == len(autotune.candidates(key))


def test_autotune_sweep_runs_eagerly_from_host_entry_points(tuned_env, monkeypatch):
    """lzss.compress/decompress must resolve tuned geometry OUTSIDE their
    jitted cores: every measure() the sweep runs executes with a clean
    trace state (kernels really run, timings are real), and the winner is
    persisted."""
    import jax

    states = []
    real = autotune._default_measure

    def spying_measure_factory(key):
        m = real(key)

        def measure(c, g):
            states.append(autotune.trace_state_clean())
            return m(c, g)

        return measure

    monkeypatch.setattr(autotune, "_default_measure", spying_measure_factory)
    data = _corpus(40, n=600)
    cfg = lzss.LZSSConfig(symbol_size=2, window=33, chunk_symbols=64)
    res = lzss.compress(data, cfg)
    assert states and all(states)  # compress-side sweep ran, eagerly
    autotune.validate_cache(json.loads(tuned_env.read_text()))
    assert json.loads(tuned_env.read_text())["entries"]

    states.clear()
    out = lzss.decompress(res.data, decoder="fused-mono")
    assert np.array_equal(out, data.view(np.uint8).reshape(-1))
    assert states and all(states)  # decode-side sweep ran, eagerly


def test_autotune_xla_decoder_skips_decode_sweep(tuned_env, monkeypatch):
    """A pure-XLA decoder never tiles a kernel: resolving geometry for it
    must not burn a sweep (uses_block_geometry=False)."""
    calls = {"n": 0}

    def factory(key):
        calls["n"] += 1
        return lambda c, g: 1.0

    monkeypatch.setattr(autotune, "_default_measure", factory)
    data = _corpus(42, n=500)
    cfg = lzss.LZSSConfig(symbol_size=1, window=32, chunk_symbols=56)
    res = lzss.compress(data, cfg)
    compress_sweeps = calls["n"]
    out = lzss.decompress(res.data, decoder="xla-parallel")
    assert np.array_equal(out, data.view(np.uint8).reshape(-1))
    assert calls["n"] == compress_sweeps  # no decode-direction sweep


def test_autotune_cache_hit_revalidates_vmem_fit(tuned_env):
    """A schema-valid but oversized entry (shared REPRO_AUTOTUNE_CACHE,
    hand-edited file, or a budget change) must never flow into Pallas: the
    hit is re-checked against the VMEM budget, dropped, and re-swept."""
    key = _key()
    tuned_env.write_text(json.dumps({
        "version": autotune.CACHE_VERSION,
        "entries": {key.cache_key(): {
            "chunk_symbols": 64,
            "chunks_per_block": 1 << 20,  # passes validate_cache, cannot fit
            "seconds_per_call": 1e-3,
        }},
    }))
    autotune.validate_cache(json.loads(tuned_env.read_text()))  # schema-valid
    calls = {"n": 0}

    def measure(c, g):
        calls["n"] += 1
        return 1.0 / (c * g)

    geom = autotune.best_geometry(key, measure)
    assert calls["n"] == len(autotune.candidates(key))  # re-swept, not trusted
    assert autotune._fits(*geom, key.symbol_size)
    # the rewritten entry is served on the next fresh-process load
    autotune.reset()
    assert autotune.best_geometry(key, measure) == geom
    assert calls["n"] == len(autotune.candidates(key))


def test_block_vmem_bytes_counts_the_walk_scratch():
    """The selection walk's two transposed (C, g) int32 scratches pad g to
    128 lanes: the bound holds them on top of the rebuild's rows, the
    calibrated geometries still fit, and (4096, 32, 4) still does not."""
    for c, g in [(2048, 8), (2048, 32), (4096, 16)]:
        walk = 2 * 4 * c * 128
        rebuild = 4 * g * c * (24 + 16 * 4)
        assert autotune.block_vmem_bytes(c, g, 4) >= rebuild + walk
        autotune.validate_block_geometry(c, g, 4)
    with pytest.raises(ValueError, match="4096"):
        autotune.validate_block_geometry(4096, 32, 4)


def test_autotune_cache_hit_revalidates_fixed_c(tuned_env):
    """An entry whose chunk_symbols disagrees with a fixed-C key (stale or
    corrupted cache) must be ignored — the call site's shapes are already
    committed to its C."""
    key = _key(chunk_symbols=64)
    tuned_env.write_text(json.dumps({
        "version": autotune.CACHE_VERSION,
        "entries": {key.cache_key(): {
            "chunk_symbols": 2048,  # not this key's C
            "chunks_per_block": 8,
            "seconds_per_call": 1e-3,
        }},
    }))
    c, g = autotune.best_geometry(key, lambda c_, g_: 1.0 / (c_ * g_))
    assert c == 64
    assert (c, g) in autotune.candidates(key)


def test_autotune_default_gating(monkeypatch):
    """Unset env: tuning only on real TPU (interpret timings mean nothing),
    so CPU CI always runs the deterministic fallback."""
    import jax

    monkeypatch.delenv(autotune.ENABLE_ENV, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert not autotune.enabled()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert autotune.enabled()
    monkeypatch.setenv(autotune.ENABLE_ENV, "0")
    assert not autotune.enabled()


def test_tuned_config_disabled_matches_defaults(monkeypatch):
    monkeypatch.setenv(autotune.ENABLE_ENV, "0")
    autotune.reset()
    try:
        cfg = pipeline.tuned_config(symbol_size=2, window=128)
        assert cfg.chunk_symbols == autotune.DEFAULT_CHUNK_SYMBOLS
        assert cfg.chunks_per_block == autotune.DEFAULT_CHUNKS_PER_BLOCK
        # explicit overrides beat the tuner
        cfg = pipeline.tuned_config(window=64, chunk_symbols=256)
        assert cfg.chunk_symbols == 256 and cfg.window == 64
    finally:
        autotune.reset()


# -------------------------------------------------- geometry validation


def test_config_rejects_oversized_block_geometry():
    """A (chunk_symbols, chunks_per_block) pair that cannot fit the VMEM
    block budget must fail at config time, naming the pair — not as an
    opaque Mosaic allocation error inside Pallas."""
    with pytest.raises(ValueError, match=r"chunk_symbols=65536.*chunks_per_block=32"):
        lzss.LZSSConfig(chunk_symbols=65536, chunks_per_block=32)
    with pytest.raises(ValueError, match="chunks_per_block"):
        lzss.LZSSConfig(chunks_per_block=0)
    with pytest.raises(ValueError, match="chunks_per_block"):
        lzss.LZSSConfig(chunks_per_block=-2)
    # an oversized C is caught even with the default (autotuned) g
    with pytest.raises(ValueError, match="chunk_symbols"):
        lzss.LZSSConfig(chunk_symbols=1 << 22)


def test_pinned_chunks_per_block_is_format_invisible():
    """Block geometry tiles kernel execution only: pinning g must produce
    byte-identical containers and symbols across values — in BOTH
    directions (decode takes the same pin as its own argument)."""
    data = _corpus(36, n=900)
    outs = []
    for g in (1, 4, 8):
        cfg = lzss.LZSSConfig(
            symbol_size=2,
            window=32,
            chunk_symbols=64,
            chunks_per_block=g,
            backend="fused-mono",
        )
        res = lzss.compress(data, cfg)
        outs.append(res.data)
        assert np.array_equal(
            lzss.decompress(res.data, decoder="fused-mono", chunks_per_block=g),
            data.view(np.uint8).reshape(-1),
        )
    assert np.array_equal(outs[0], outs[1])
    assert np.array_equal(outs[1], outs[2])


# ------------------------------------------- decode-side geometry pinning


def test_decode_pin_reaches_mono_and_split_kernels(monkeypatch):
    """A pinned chunks_per_block must reach the decode kernels — pinning
    only the compress direction would silently hand a reproducibility-
    pinned restore path to the autotuner."""
    seen = {}
    real_mono, real_split = ops.lz_decode_mono, ops.lz_decode

    def spy_mono(*args, **kwargs):
        seen["mono"] = kwargs.get("chunks_per_block")
        return real_mono(*args, **kwargs)

    def spy_split(*args, **kwargs):
        seen["split"] = kwargs.get("chunks_per_block")
        return real_split(*args, **kwargs)

    monkeypatch.setattr(ops, "lz_decode_mono", spy_mono)
    monkeypatch.setattr(ops, "lz_decode", spy_split)
    data = _corpus(41, n=700)
    # unusual geometry => fresh jit traces, so the spies observe the calls
    cfg = lzss.LZSSConfig(symbol_size=2, window=35, chunk_symbols=104)
    res = lzss.compress(data, cfg)
    raw = data.view(np.uint8).reshape(-1)

    out = lzss.decompress(res.data, decoder="fused-mono", chunks_per_block=2)
    assert seen.pop("mono") == 2
    assert np.array_equal(out, raw)

    out = lzss.decompress(res.data, decoder="fused", chunks_per_block=2)
    assert seen.pop("split") == 2
    assert np.array_equal(out, raw)


def test_decode_pin_threads_through_batched_and_checkpoint_restore(
    monkeypatch, tmp_path
):
    """CheckpointManager.lz_chunks_per_block documents pinning 'the Pallas
    kernels' block geometry' — that must include the restore direction,
    through decompress_many and the decode_blob hook."""
    from repro.checkpoint.manager import CheckpointManager

    seen = []
    real = ops.lz_decode_mono

    def spy(*args, **kwargs):
        seen.append(kwargs.get("chunks_per_block"))
        return real(*args, **kwargs)

    monkeypatch.setattr(ops, "lz_decode_mono", spy)
    mgr = CheckpointManager(
        directory=str(tmp_path),
        lz_window=31,
        lz_chunk=112,
        lz_decoder="fused-mono",
        lz_chunks_per_block=2,
    )
    rng = np.random.default_rng(43)
    state = {"w": np.repeat(rng.integers(0, 8, 400), 4).astype(np.float32)}
    mgr.save(state, step=1)
    restored, step = mgr.restore(
        template={"w": np.zeros(1600, np.float32)}, step=1
    )
    assert step == 1
    assert np.array_equal(restored["w"], state["w"])
    assert seen and all(g == 2 for g in seen)
