"""Check ``lossless``: every byte read back equals the byte written.

Compares what the window produced with the plain reference of
``bench/reference.py`` (numpy, imports nothing of the program).  Two
numbers, each against the limit the cell's ``bench/workloads/<cell>.json``
gives under ``limits``:

- ``roundtrip_mismatch_bytes``: bytes where the timed decoder's output
  differs from the field, over the last round trip of every field;
- ``container_mismatch_bytes``: bytes where the reference's own decoder,
  reading the timed compressor's containers, differs from the field, over
  ``reference_chunks_per_field`` chunks a field drawn from the seed.

A field the window never produced counts all its bytes in both.
"""

from __future__ import annotations

import numpy as np

from bench import reference


def compare(placement, kept: dict, originals, cfg: dict, spec: dict,
            seed: int) -> dict:
    """Compare what the window produced with the reference.

    ``originals(k)`` gives field ``k``'s bytes as the benchmark made them.
    Returns ``{number: {"value", "limit", "compared"}}``.
    """
    s, c, win = cfg["symbol_size"], cfg["chunk_symbols"], cfg["window"]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0xC4EC])
    n_fields = int(cfg["fields_per_step"])
    rt = rt_n = ct = ct_n = 0
    for k in range(n_fields):
        want = originals(k)
        if k not in kept:  # never produced: every byte is missing
            rt += want.size
            rt_n += want.size
            ct += want.size
            ct_n += want.size
            continue
        out, dec = kept[k]
        rt += reference.roundtrip_mismatch(placement.decoded_bytes(dec), want)
        rt_n += want.size
        nc = -(-(want.size // s) // c)
        chunks = reference.sample_chunks(
            rng, nc, int(spec["reference_chunks_per_field"]))
        bad, n = reference.container_mismatch(
            placement.container_bytes(out), want, chunks, symbol_size=s,
            window=win, chunk_symbols=c,
        )
        ct += bad
        ct_n += n
    limits = spec["limits"]
    return {
        "roundtrip_mismatch_bytes": dict(
            value=rt, limit=limits["roundtrip_mismatch_bytes"], compared=rt_n),
        "container_mismatch_bytes": dict(
            value=ct, limit=limits["container_mismatch_bytes"], compared=ct_n),
    }
