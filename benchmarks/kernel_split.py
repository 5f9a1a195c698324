"""Split the compress kernel's time into matching, walk and emit, on a chip.

Times three entry points on one field of a benchmark configuration
(``bench/configs/<name>.json``), each a prefix of the next:

  match   ``lz_match.lz_match_pallas``       matching only
  kernel1 ``lz_match.lz_kernel1_pallas``     + selection walk + local prefix sums
  mono    ``lz_fused.lz_fused_mono_pallas``  + section rebuild, DMAs, slide

so ``kernel1 - match`` prices the walk and ``mono - kernel1`` the rebuild and
the writes, per grid block of ``chunks_per_block`` chunks.  Each call ends in
``block_until_ready``; a call is timed after one warm-up call.  A SHA-256 of
each entry point's outputs lets two commits be checked byte-identical.

    PYTHONPATH=src:. python benchmarks/kernel_split.py --config hurr-quant-u16

prints one JSON line.  The geometry is the benchmark's (``chunk_symbols``
of the configuration, ``chunks_per_block`` 8).  Without a TPU it refuses
to run, unless ``--rehearse`` is given: then the kernels run in interpret
mode on the field's first 64 Ki symbols, and the times are the
interpreter's.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.generators import hurr
from repro.core import autotune, encode, format as fmt
from repro.kernels import lz_fused, lz_match

_BITS = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.int32}
REHEARSAL_SYMBOLS = 1 << 16


def _symbols(cfg: dict, seed: int, n_symbols: int | None):
    field = hurr.time_step(dict(cfg, fields_per_step=1), seed)[0]
    c = int(cfg["chunk_symbols"])
    flat = jax.lax.bitcast_convert_type(
        field.reshape(-1), _BITS[field.dtype.itemsize]
    ).astype(jnp.int32)
    if n_symbols is not None:
        flat = flat[:n_symbols]
    nc = -(-flat.size // c)
    return jnp.pad(flat, (0, nc * c - flat.size)).reshape(nc, c)


def _digest(out) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(out):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()[:16]


def _time(fn, x, reps: int):
    out = jax.block_until_ready(fn(x))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        times.append(time.perf_counter() - t0)
    return times, _digest(out)


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default="hurr-quant-u16")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        raise SystemExit(f"no TPU: found {dev.platform}; pass --rehearse")
    with open(f"bench/configs/{args.config}.json") as f:
        cfg = json.load(f)
    s, w = int(cfg["symbol_size"]), int(cfg["window"])
    g = autotune.DEFAULT_CHUNKS_PER_BLOCK
    x = _symbols(cfg, args.seed, REHEARSAL_SYMBOLS if args.rehearse else None)
    nc, c = x.shape
    nb = -(-nc // g)
    kw = dict(window=w, chunks_per_block=g, interpret=args.rehearse)
    k1 = dict(kw, min_match=encode.min_match_length(s), symbol_size=s)
    sec_flags = fmt.HEADER_BYTES + 8 * nc
    cap = fmt.max_compressed_bytes(nc * c * s, s, c)
    entries = {
        "match": jax.jit(lambda x: lz_match.lz_match_pallas(x, **kw)),
        "kernel1": jax.jit(lambda x: lz_match.lz_kernel1_pallas(x, **k1)),
        "mono": jax.jit(
            lambda x: lz_fused.lz_fused_mono_pallas(
                x, cap=cap, sec_flags=sec_flags, **k1
            )
        ),
    }
    result = dict(
        device=dict(platform=dev.platform, kind=dev.device_kind,
                    count=jax.device_count()),
        config=args.config, seed=args.seed, chunks=nc, chunk_symbols=c,
        chunks_per_block=g, blocks=nb, window=w, symbol_size=s,
    )
    medians = {}
    for name, fn in entries.items():
        times, digest = _time(fn, x, args.reps)
        medians[name] = statistics.median(times)
        result[name] = dict(times_s=times, median_s=medians[name],
                            sha256=digest)
    per_block = {
        "match": medians["match"],
        "walk_and_scan": medians["kernel1"] - medians["match"],
        "rebuild_and_writes": medians["mono"] - medians["kernel1"],
    }
    result["us_per_block"] = {k: v / nb * 1e6 for k, v in per_block.items()}
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
