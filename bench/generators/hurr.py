"""Generator ``hurr``: one time step of Hurricane ISABEL surrogate fields.

A configuration names its generator (``"generator": "hurr"``); the harness
calls this module's ``time_step(cfg, seed)``, which returns the step's
fields as device arrays of ``cfg["field_shape"]`` and ``cfg["dtype"]``.

A copy, made on the device, of the hurr surrogate in
``repro/data/datasets.py`` (``_hurr_raw_field``) and of the dual-quant
Lorenzo steps of ``repro/core/quant.py`` it uses (``quantize``,
``relative_error_bound``), so that a later change to the program cannot
change the benchmark's inputs.  Each z-level of a field is the surrogate's
2-D slice: a smooth front ``sin(6 pi x) cos(4 pi y) * 30`` plus a random
walk along x with N(0, 0.1) steps.

What the seed changes.  The fields themselves come from the configuration's
``base_seed``; ``--seed`` relabels their symbol values.  Every chunk of C
symbols (the codec's independent unit: no match crosses a chunk) is XORed
with its own constant in the low half of the symbol (8 bits of a u16 code,
16 mantissa bits of an f32), and the last, partial chunk is left as it is.
A relabelling within a chunk keeps which symbols are equal, so every seed
gives the codec the same matches and the same container sizes, and so the
same compiled shapes and the same work, with other bytes.  So the match
structure, and with it the ratio, is the surrogate's at ``base_seed``: one
structure on every seed, not that of the SDRBench files.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CENTER = 1 << 15  # quant code of a zero Lorenzo delta (quant.CENTER)
_BITS_DTYPE = {"uint16": jnp.uint16, "float32": jnp.uint32}
_LOW_HALF = {"uint16": 0xFF, "float32": 0xFFFF}


def split_seed(seed: int) -> tuple[np.uint32, np.uint32]:
    """A seed of up to 64 bits as two u32 words (traced, so one program)."""
    seed = int(seed)
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed must be in [0, 2**64): {seed}")
    return np.uint32(seed & 0xFFFFFFFF), np.uint32(seed >> 32)


def _hurr_slices(key, shape):
    """(Z, Y, X) f32: each z-level is the repo surrogate's 2-D field."""
    z, y, x = shape
    yy = jnp.arange(y, dtype=jnp.float32)[:, None] / y
    xx = jnp.arange(x, dtype=jnp.float32)[None, :] / x
    front = jnp.sin(6 * jnp.pi * xx) * jnp.cos(4 * jnp.pi * yy) * 30
    walk = jnp.cumsum(jax.random.normal(key, shape, jnp.float32) * 0.1, axis=2)
    return front[None] + walk


def _quant_codes(field, rel_eb: float, ndim: int):
    """u16 dual-quant Lorenzo codes over the last ``ndim`` axes.

    ``quant.relative_error_bound`` then ``quant.quantize``: the pre-quant is
    saturated to int30, codes outside u16 become CENTER (outliers, stored
    apart by SZ and not part of the code stream).
    """
    rng = jnp.max(field) - jnp.min(field)
    eb = jnp.maximum(rel_eb * rng, np.finfo(np.float32).tiny)
    qf = jnp.round(field / (2.0 * eb))
    q = jnp.clip(qf, -(2.0**30), 2.0**30).astype(jnp.int32)
    delta = q
    for ax in range(field.ndim - ndim, field.ndim):
        delta = jnp.diff(delta, axis=ax, prepend=jnp.zeros_like(
            jax.lax.slice_in_dim(delta, 0, 1, axis=ax)))
    delta = delta + CENTER
    saturated = (delta < 0) | (delta > 0xFFFF) | (jnp.abs(qf) >= 2.0**30)
    return jnp.where(saturated, CENTER, delta).astype(jnp.uint16)


def _relabel(field, key, *, dtype: str, chunk_symbols: int):
    """XOR each whole chunk's symbols with a per-chunk low-half constant."""
    bits_t = _BITS_DTYPE[dtype]
    bits = jax.lax.bitcast_convert_type(field, bits_t).reshape(-1)
    n = bits.size
    full = n // chunk_symbols
    masks = jax.random.bits(key, (full,), bits_t) & _LOW_HALF[dtype]
    masks = jnp.concatenate([masks, jnp.zeros((1,), bits_t)])
    chunk = jnp.minimum(jnp.arange(n, dtype=jnp.int32) // chunk_symbols, full)
    out = bits ^ masks[chunk]
    return jax.lax.bitcast_convert_type(out, jnp.dtype(dtype)).reshape(
        field.shape
    )


@functools.partial(jax.jit, static_argnames=("spec",))
def _time_step(seed_lo, seed_hi, *, spec):
    (shape, n_fields, dtype, base_seed, rel_eb, ndim, chunk_symbols) = spec
    base = jax.random.key(base_seed)
    relabel = jax.random.fold_in(jax.random.fold_in(base, seed_lo), seed_hi)

    def one(i):
        field = _hurr_slices(jax.random.fold_in(base, i), shape)
        if dtype == "uint16":
            field = _quant_codes(field, rel_eb, ndim)
        return _relabel(
            field,
            jax.random.fold_in(jax.random.fold_in(relabel, 0x5EED), i),
            dtype=dtype,
            chunk_symbols=chunk_symbols,
        )

    stacked = jax.lax.map(one, jnp.arange(n_fields, dtype=jnp.uint32))
    return tuple(stacked[i] for i in range(n_fields))


def spec_of(cfg: dict) -> tuple:
    """The hashable generator parameters of a configuration."""
    return (
        tuple(cfg["field_shape"]),
        int(cfg["fields_per_step"]),
        cfg["dtype"],
        int(cfg["base_seed"]),
        float(cfg.get("rel_error_bound", 0.0)),
        int(cfg.get("lorenzo_ndim", 0)),
        int(cfg["chunk_symbols"]),
    )


def time_step(cfg: dict, seed: int) -> tuple:
    """The configuration's time step for ``seed``: one device array a field,
    made in one jitted call."""
    if cfg["dtype"] not in _BITS_DTYPE:
        raise ValueError(f"unsupported field dtype {cfg['dtype']!r}")
    lo, hi = split_seed(seed)
    return _time_step(lo, hi, spec=spec_of(cfg))
