"""Architecture-adaptive chunk-geometry autotuner.

GPULZ's third contribution is "maximizing shared memory utilization by
adapting data partitions to different GPU architectures" (PAPER.md §1) —
on TPU the analogous knobs are ``chunk_symbols`` (C, the per-chunk symbol
count: VMEM block width) and ``chunks_per_block`` (g, how many chunks ride
the sublane dimension of one grid step).  Until this module every kernel
hardcoded C=2048 / g=8; this is the tile chooser that adapts them per
architecture, in the spirit of the ``triton.Config`` candidate lists of
Triton autotuners (pow-2 candidate grids, timed sweep, best kept).

Design:

  * ``TuneKey`` — one tuning problem: (device kind, dtype, S, W, direction,
    C).  ``direction`` is ``"compress"`` (the single-kernel compressor,
    kernels/lz_fused.py) or ``"decompress"`` (the single-launch decoder,
    kernels/lz_decode_mono.py; its cost is W-independent, so decode keys
    carry ``window=0``).  ``chunk_symbols`` is the fixed container C when
    only g is tunable (every kernel call site — the shapes are already
    committed) or ``None`` for the joint (C, g) sweep behind
    ``tuned_chunk_geometry`` / ``pipeline.tuned_config``.
  * ``best_geometry(key)`` — cache lookup, then (if tuning is enabled) a
    timed sweep over ``candidates(key)`` on a deterministic synthetic
    workload, persisted to a JSON on-disk cache; otherwise the
    deterministic ``fallback`` table.
  * The cache is a JSON file at ``$REPRO_AUTOTUNE_CACHE`` (default
    ``~/.cache/gpulz-repro/autotune.json``), schema-checked on load
    (``validate_cache``); a corrupted file is treated as empty and
    rewritten, never crashed on.  Entries are memoized per process, so a
    jitted pipeline sees one stable geometry per key for the lifetime of
    the process (jit caches trace on config, not on geometry).

Gating: ``REPRO_AUTOTUNE=1`` forces tuning on, ``REPRO_AUTOTUNE=0`` forces
the deterministic fallback (bit-exact with the pre-autotuner static
geometry C=2048/g=8 — what tests and reproducibility-pinned runs want);
unset, tuning runs only on real TPU — interpret-mode timings on CPU are
meaningless, so CI and CPU containers stay on the fallback automatically.

The timed sweep additionally only ever runs *outside* a jax trace: inside
``jit``/``vmap`` tracing, ``block_until_ready`` no-ops on tracers and
``time.perf_counter`` would measure tracing overhead, not kernel runtime —
a winner picked there is noise, and persisting it would poison the cache
for every future run.  ``best_geometry`` therefore serves memo/cache hits
(re-validated against the VMEM budget) or the deterministic fallback when
called under a trace, and the non-jitted entry points
(``lzss.compress``/``decompress`` and their batched forms) resolve geometry
eagerly — ``pipeline.resolve_chunk_geometry`` — before crossing the jit
boundary, so real sweeps happen eagerly on real devices.

``validate_block_geometry`` is the shared geometry validator: it rejects a
``(chunk_symbols, chunks_per_block)`` pair whose VMEM block footprint
cannot fit, naming the offending pair — ``LZSSConfig.__post_init__`` calls
it so a bad geometry fails at config construction instead of as an opaque
Mosaic allocation error inside Pallas.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Callable, Dict, Optional, Tuple

import jax

# The pre-autotuner static geometry: every kernel's historical default and
# the deterministic fallback when tuning is disabled.
DEFAULT_CHUNK_SYMBOLS = 2048
DEFAULT_CHUNKS_PER_BLOCK = 8

# Scoped VMEM the single-kernel compressor and decoder request per grid
# step (``vmem_limit_bytes``; a v5e core has 128 MiB of VMEM, the compiler's
# default scope is 16 MiB).  ``block_vmem_bytes`` must stay within it.
VMEM_LIMIT_BYTES = 32 * 2**20

CACHE_VERSION = 1
CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
ENABLE_ENV = "REPRO_AUTOTUNE"

# Candidate grids, triton.Config style: pow-2 ladders around the defaults.
# Candidates that overflow the VMEM budget are filtered per key.
CHUNK_SYMBOL_CANDIDATES = (512, 1024, 2048, 4096)
CHUNKS_PER_BLOCK_CANDIDATES = (8, 16, 32)

# Deterministic per-architecture fallback rows: (device-kind prefix,
# direction) -> (chunk_symbols, chunks_per_block).  Populated as real-TPU
# sweeps land (ROADMAP); an absent row falls back to the historical static
# geometry, so disabling tuning is always bit-exact with the pre-autotuner
# pipeline.
FALLBACK_TABLE: Dict[Tuple[str, str], Tuple[int, int]] = {}

_MEMO: Dict[str, Tuple[int, int]] = {}  # per-process: cache_key -> (C, g)
_SWEEPS: Dict[str, int] = {}  # telemetry (tests assert on it): key -> sweeps


@dataclasses.dataclass(frozen=True)
class TuneKey:
    """One tuning problem; hashable, stable string form via ``cache_key``."""

    device_kind: str
    dtype: str
    symbol_size: int
    window: int  # 0 on the decode side: decode cost is W-independent
    direction: str  # "compress" | "decompress"
    chunk_symbols: Optional[int]  # fixed C, or None for the joint (C, g) sweep

    def cache_key(self) -> str:
        c = "auto" if self.chunk_symbols is None else str(self.chunk_symbols)
        return (
            f"{self.device_kind}|{self.dtype}|s{self.symbol_size}"
            f"|w{self.window}|{self.direction}|c{c}"
        )


def device_kind() -> str:
    """Normalized accelerator kind (e.g. ``TPU_v5_lite``, ``cpu``)."""
    return str(jax.devices()[0].device_kind).replace(" ", "_")


def default_dtype(symbol_size: int) -> str:
    return {1: "u8", 2: "u16", 4: "u32"}[symbol_size]


def enabled() -> bool:
    """Whether timed sweeps run (vs the deterministic fallback table)."""
    flag = os.environ.get(ENABLE_ENV)
    if flag is not None:
        return flag != "0"
    return jax.default_backend() == "tpu"  # interpret timings are meaningless


def trace_state_clean() -> bool:
    """True when no jax trace is being staged (sweeps are only valid then)."""
    return jax.core.trace_ctx.is_top_level()


def cache_path() -> str:
    return os.environ.get(
        CACHE_ENV,
        os.path.join(
            os.path.expanduser("~"), ".cache", "gpulz-repro", "autotune.json"
        ),
    )


# ------------------------------------------------------------- validation


def block_vmem_bytes(
    chunk_symbols: int, chunks_per_block: int, symbol_size: int
) -> int:
    """Per-grid-step VMEM footprint of one (g, C) block, an upper bound.

    The compressor's section rebuild dominates: some sixteen payload-width
    (g, C*S) int32 intermediates of the byte-space search live at once, next
    to ~24 C-width rows of matching, walk and prefix-sum state; the decoder
    needs less.  Calibrated on compiles for TPU v5e: (C, g, S) = (2048, 32,
    4) and (4096, 16, 4) fit ``VMEM_LIMIT_BYTES``, (4096, 32, 4) does not
    (tests/test_tpu_compile.py compiles the largest default-C candidate).
    """
    g, c, s = chunks_per_block, chunk_symbols, symbol_size
    return 4 * g * c * (24 + 16 * s)


def validate_block_geometry(
    chunk_symbols: int, chunks_per_block: int, symbol_size: int
) -> None:
    """Reject a (C, g) pair Pallas could not run, naming the pair.

    Shared by ``LZSSConfig.__post_init__`` and the candidate filter, so an
    oversized geometry fails at config time with the offending numbers in
    the message instead of as an opaque Mosaic VMEM-allocation error.
    """
    c, g = chunk_symbols, chunks_per_block
    if not isinstance(g, int) or isinstance(g, bool) or g < 1:
        raise ValueError(
            f"chunks_per_block must be a positive int: got "
            f"(chunk_symbols={c}, chunks_per_block={g!r})"
        )
    need = block_vmem_bytes(c, g, symbol_size)
    if need > VMEM_LIMIT_BYTES:
        raise ValueError(
            f"block geometry (chunk_symbols={c}, chunks_per_block={g}) needs "
            f"~{need / 2**20:.1f} MiB of VMEM per grid step at "
            f"symbol_size={symbol_size}, over the {VMEM_LIMIT_BYTES / 2**20:.0f}"
            f" MiB budget — shrink chunk_symbols or chunks_per_block"
        )


def _fits(c: int, g: int, s: int) -> bool:
    return block_vmem_bytes(c, g, s) <= VMEM_LIMIT_BYTES


# --------------------------------------------------------------- choices


def fallback(key: TuneKey) -> Tuple[int, int]:
    """Deterministic geometry when tuning is disabled (or as sweep seed)."""
    c, g = None, None
    for (prefix, direction), row in FALLBACK_TABLE.items():
        if key.direction == direction and key.device_kind.startswith(prefix):
            c, g = row
            break
    if c is None:
        c = DEFAULT_CHUNK_SYMBOLS
        g = DEFAULT_CHUNKS_PER_BLOCK
    if key.chunk_symbols is not None:
        c = key.chunk_symbols  # C already committed by the caller's shapes
    while g > 1 and not _fits(c, g, key.symbol_size):
        g //= 2
    return c, g


def candidates(key: TuneKey):
    """VMEM-filtered (C, g) candidate list for one key."""
    cs = (
        CHUNK_SYMBOL_CANDIDATES
        if key.chunk_symbols is None
        else (key.chunk_symbols,)
    )
    out = [
        (c, g)
        for c in cs
        for g in CHUNKS_PER_BLOCK_CANDIDATES
        if _fits(c, g, key.symbol_size)
    ]
    return out or [fallback(key)]


# ------------------------------------------------------------- the cache


def validate_cache(obj) -> None:
    """Schema check for an on-disk cache object; raises ``ValueError``.

    Rides ``make check-bench`` via the artifact-schema tests, and gates
    ``_load_cache`` — a corrupted file is treated as empty, never trusted.
    """
    if not isinstance(obj, dict):
        raise ValueError("autotune cache: not a JSON object")
    if obj.get("version") != CACHE_VERSION:
        raise ValueError(
            f"autotune cache: version {obj.get('version')!r} != {CACHE_VERSION}"
        )
    entries = obj.get("entries")
    if not isinstance(entries, dict):
        raise ValueError("autotune cache: 'entries' must be an object")
    for k, e in entries.items():
        if not isinstance(e, dict):
            raise ValueError(f"autotune cache: entry {k!r} is not an object")
        for field in ("chunk_symbols", "chunks_per_block"):
            v = e.get(field)
            if not isinstance(v, int) or v < 1:
                raise ValueError(
                    f"autotune cache: entry {k!r} field {field!r} must be a "
                    f"positive int, got {v!r}"
                )
        spc = e.get("seconds_per_call")
        if not isinstance(spc, (int, float)) or spc <= 0:
            raise ValueError(
                f"autotune cache: entry {k!r} seconds_per_call must be a "
                f"positive number, got {spc!r}"
            )


def _entry_geometry(cache: dict, key: TuneKey) -> Optional[Tuple[int, int]]:
    """Validated geometry from a persisted cache entry, or ``None``.

    ``validate_cache`` only proves the schema ("positive ints"); an entry
    can still be unusable at *this* call site — the cache file is shareable
    (``REPRO_AUTOTUNE_CACHE``), hand-editable, and survives changes to
    ``VMEM_LIMIT_BYTES`` / ``block_vmem_bytes``.  Re-check on every hit
    that the pair still fits the VMEM budget and that a fixed-C key only
    adopts an entry tuned for that same C; a failing entry is ignored (and
    overwritten by the next eager sweep) instead of flowing into Pallas as
    the opaque Mosaic allocation error the validator exists to prevent.
    """
    entry = cache["entries"].get(key.cache_key())
    if entry is None:
        return None
    c, g = int(entry["chunk_symbols"]), int(entry["chunks_per_block"])
    if key.chunk_symbols is not None and c != key.chunk_symbols:
        return None
    if not _fits(c, g, key.symbol_size):
        return None
    return c, g


def _load_cache(path: str) -> dict:
    try:
        with open(path) as f:
            obj = json.load(f)
        validate_cache(obj)
        return obj
    except FileNotFoundError:
        return {"version": CACHE_VERSION, "entries": {}}
    except (json.JSONDecodeError, ValueError, OSError):
        # corrupted / stale-schema cache: recover by re-tuning, never crash
        return {"version": CACHE_VERSION, "entries": {}}


def _store_cache(path: str, cache: dict) -> None:
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(cache, f, indent=2, sort_keys=True)
    os.replace(tmp, path)  # atomic publish, mirroring checkpoint/manager.py


def reset() -> None:
    """Drop per-process memoized geometry (tests / env changes)."""
    _MEMO.clear()
    _SWEEPS.clear()


# --------------------------------------------------------------- tuning


def _time(fn: Callable[[], object], warmup: int = 1, iters: int = 2) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _default_measure(key: TuneKey) -> Callable[[int, int], float]:
    """Deterministic synthetic workload for one key: (C, g) -> seconds.

    Compress times the single-kernel compressor on a run-heavy corpus;
    decompress times the single-launch decoder on a worst-case all-literal
    container built in place (every flag/payload window at full width).
    Inputs are seeded, so re-sweeps on the same machine are reproducible.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.core import encode
    from repro.core import format as fmt

    s = key.symbol_size
    interpret = jax.default_backend() != "tpu"

    if key.direction == "compress":
        from repro.kernels import lz_fused

        window = key.window or DEFAULT_CHUNK_SYMBOLS // 16
        min_match = encode.min_match_length(s)

        def measure(c: int, g: int) -> float:
            nc = max(2 * g, 16)
            rng = np.random.default_rng(0)
            syms = np.repeat(
                rng.integers(0, 1 << min(8 * s, 16), nc * c // 4), 4
            ).astype(np.int32)[: nc * c].reshape(nc, c)
            cap = fmt.max_compressed_bytes(nc * c * s, s, c)
            x = jnp.asarray(syms)

            def fn():
                return lz_fused.lz_fused_mono_pallas(
                    x,
                    window=window,
                    min_match=min_match,
                    symbol_size=s,
                    cap=cap,
                    sec_flags=fmt.HEADER_BYTES + 8 * nc,
                    chunks_per_block=g,
                    interpret=interpret,
                )

            return _time(fn)

    else:
        from repro.kernels import lz_decode_mono

        def measure(c: int, g: int) -> float:
            nc = max(2 * g, 16)
            cb = c // 8
            rng = np.random.default_rng(0)
            sec_flags = fmt.HEADER_BYTES + 8 * nc
            blob = np.zeros(sec_flags + nc * cb + nc * c * s, np.uint8)
            blob[sec_flags + nc * cb :] = rng.integers(
                0, 256, nc * c * s, dtype=np.int64
            ).astype(np.uint8)
            nt = jnp.full((nc,), c, jnp.int32)  # all-literal: worst case
            psz = jnp.full((nc,), c * s, jnp.int32)
            b = jnp.asarray(blob)

            def fn():
                return lz_decode_mono.lz_decode_mono_pallas(
                    b,
                    nt,
                    psz,
                    symbol_size=s,
                    chunk_symbols=c,
                    n_chunks=nc,
                    chunks_per_block=g,
                    interpret=interpret,
                )

            return _time(fn)

    return measure


def best_geometry(
    key: TuneKey, measure: Optional[Callable[[int, int], float]] = None
) -> Tuple[int, int]:
    """(chunk_symbols, chunks_per_block) for one key.

    Resolution order: deterministic fallback when tuning is disabled;
    per-process memo; the persisted JSON cache (entries re-validated
    against the VMEM budget on every hit — see ``_entry_geometry``);
    finally a timed sweep over ``candidates(key)`` whose winner is written
    back to the cache.  The result is memoized, so a jitted pipeline sees
    one stable geometry per key for the process lifetime.

    The sweep never runs while a jax trace is being staged: the kernel
    calls in ``measure`` would be staged into the surrounding trace
    (``block_until_ready`` no-ops on tracers) and the timings would be
    tracing overhead, not kernel runtime.  Under a trace an untuned key
    gets the deterministic fallback — unmemoized and unpersisted, so a
    later eager call can still tune it.  The non-jitted entry points
    resolve geometry eagerly (``pipeline.resolve_chunk_geometry``) exactly
    so the hot paths never hit this case.
    """
    if not enabled():
        return fallback(key)
    ck = key.cache_key()
    if ck in _MEMO:
        return _MEMO[ck]
    path = cache_path()
    cache = _load_cache(path)
    geom = _entry_geometry(cache, key)
    if geom is not None:
        _MEMO[ck] = geom
        return geom
    if not trace_state_clean():
        return fallback(key)  # in-trace timings are noise: never sweep here
    # sweep: time every candidate, keep the fastest, persist
    if measure is None:
        measure = _default_measure(key)
    cands = candidates(key)
    timed = [(measure(c, g), c, g) for c, g in cands]
    _SWEEPS[ck] = _SWEEPS.get(ck, 0) + 1
    best_t, c, g = min(timed)
    cache["entries"][ck] = {
        "chunk_symbols": c,
        "chunks_per_block": g,
        "seconds_per_call": best_t,
        "device_kind": key.device_kind,
        "direction": key.direction,
        "swept": len(timed),
    }
    _store_cache(path, cache)
    _MEMO[ck] = (c, g)
    return c, g


# ----------------------------------------------------- call-site helpers


def block_geometry(
    *,
    symbol_size: int,
    chunk_symbols: int,
    direction: str,
    window: int = 0,
    dtype: Optional[str] = None,
) -> int:
    """``chunks_per_block`` for a kernel call site whose C is committed.

    This is what ``kernels/ops.py`` resolves a ``chunks_per_block=None``
    default through — the fused compressor and the single-launch decoder
    both consume it.
    """
    key = TuneKey(
        device_kind=device_kind(),
        dtype=dtype or default_dtype(symbol_size),
        symbol_size=symbol_size,
        window=window if direction == "compress" else 0,
        direction=direction,
        chunk_symbols=chunk_symbols,
    )
    return best_geometry(key)[1]


def tuned_chunk_geometry(
    *, symbol_size: int, window: int, dtype: Optional[str] = None
) -> Tuple[int, int]:
    """Joint (chunk_symbols, chunks_per_block) sweep for new containers.

    Unlike ``block_geometry`` this also chooses C — a *format-visible*
    parameter (it changes container bytes), so it is only consulted when a
    config is being built (``pipeline.tuned_config``), never to reinterpret
    an existing container.
    """
    key = TuneKey(
        device_kind=device_kind(),
        dtype=dtype or default_dtype(symbol_size),
        symbol_size=symbol_size,
        window=window,
        direction="compress",
        chunk_symbols=None,
    )
    return best_geometry(key)
