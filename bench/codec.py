"""What every placement shares: the program's configuration and the device
programs of one round trip.

Both take the codec's registry defaults (``"auto"``) and the deterministic
block geometry (``chunks_per_block=None`` with ``REPRO_AUTOTUNE=0``).  A
field's symbols are its own dtype view of ``symbol_size`` bytes, widened to
int32 as ``lzss._pack_padded`` packs them on the host, so any dtype whose
item is 1, 2 or 4 bytes goes through unchanged.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

_BITS = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.int32}


def field_bytes(cfg: dict) -> int:
    return math.prod(cfg["field_shape"]) * np.dtype(cfg["dtype"]).itemsize


def lz_config(cfg: dict, **overrides):
    """The program's ``LZSSConfig`` for a configuration file."""
    from repro.core import lzss

    kw = dict(
        symbol_size=int(cfg["symbol_size"]),
        window=int(cfg["window"]),
        chunk_symbols=int(cfg["chunk_symbols"]),
        chunks_per_block=cfg.get("chunks_per_block"),
        backend=cfg["backend"],
        decoder=cfg["decoder"],
    )
    kw.update(overrides)
    return lzss.LZSSConfig(**kw)


def _to_symbols(field):
    """Field -> flat int32 symbols: its item's bits, zero-extended."""
    bits = jax.lax.bitcast_convert_type(
        field.reshape(-1), _BITS[field.dtype.itemsize])
    return bits.astype(jnp.int32)


def _from_symbols(sym, dtype):
    bits = sym.astype(_BITS[dtype.itemsize])
    return jax.lax.bitcast_convert_type(bits, dtype)


def device_programs(cfg: dict, backend: str, decoder: str,
                    method_params: tuple = (), **lz_overrides):
    """The two jitted programs of a round trip in HBM.

    ``backend``/``decoder`` are registry keys, already resolved;
    ``method_params`` are the decoder's static container parameters (the
    lossy decoder's).  Returns ``(compress, decompress)``:
    ``compress(field) -> (container u8[cap], total_bytes)`` and
    ``decompress(container) -> field``.
    """
    from repro.core import format as fmt
    from repro.core import pipeline

    shape = tuple(cfg["field_shape"])
    dtype = jnp.dtype(cfg["dtype"])
    s, c = int(cfg["symbol_size"]), int(cfg["chunk_symbols"])
    if dtype.itemsize != s:
        raise ValueError(f"a {dtype} field has {dtype.itemsize}-byte symbols, "
                         f"not symbol_size {s}")
    n_sym = math.prod(shape)
    nc = -(-n_sym // c)
    orig_bytes = n_sym * s
    lz = lz_config(cfg, backend=backend, decoder=decoder, **lz_overrides)

    @jax.jit
    def compress(field):
        sym = jnp.pad(_to_symbols(field), (0, nc * c - n_sym)).reshape(nc, c)
        return pipeline.compress_chunks(sym, lz, jnp.int32(orig_bytes))

    @jax.jit
    def decompress(container):
        tables = container[: fmt.HEADER_BYTES + 8 * nc].astype(jnp.int32)
        n_tokens, payload_sizes = fmt.parse_tables_jax(tables, nc)
        sym = pipeline.decompress_chunks(
            container,
            n_tokens,
            payload_sizes,
            symbol_size=s,
            chunk_symbols=c,
            n_chunks=nc,
            decoder=decoder,
            chunks_per_block=lz.chunks_per_block,
            method_params=method_params,
        )
        return _from_symbols(sym.reshape(-1)[:n_sym], dtype).reshape(shape)

    return compress, decompress
