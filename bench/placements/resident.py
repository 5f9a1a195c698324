"""The ``resident`` placement: the field is already in HBM and the output
stays there.

Compress through ``pipeline.compress_chunks`` of the field's own symbols,
decode through ``format.parse_tables_jax`` and ``pipeline.decompress_chunks``
back to the field's dtype (``bench/codec.py``).  Every call ends in
``block_until_ready``.  The controls run through this placement too, with
their own programs or fields.
"""

from __future__ import annotations

import jax
import numpy as np

from bench import codec


class Placement:
    """Fields in HBM, containers and decoded fields left in HBM."""

    name = "resident"

    def __init__(self, cfg: dict, fields: tuple, programs=None):
        from repro.core import pipeline

        self.fields = list(fields)
        self.backend = pipeline.resolve_backend(cfg["backend"])
        self.decoder = pipeline.resolve_decoder(cfg["decoder"])
        self._compress, self._decompress = programs or codec.device_programs(
            cfg, self.backend, self.decoder
        )

    def compress(self, k: int):
        return jax.block_until_ready(self._compress(self.fields[k]))

    def decompress(self, out):
        return jax.block_until_ready(self._decompress(out[0]))

    def warm_up(self) -> None:
        self.decompress(self.compress(0))

    def total_bytes(self, out) -> int:
        return int(out[1])

    def container_bytes(self, out) -> np.ndarray:
        return np.asarray(out[0])[: int(out[1])]

    def decoded_bytes(self, dec) -> np.ndarray:
        return np.asarray(dec).reshape(-1).view(np.uint8)

    def original_bytes(self, k: int) -> np.ndarray:
        return np.asarray(self.fields[k]).reshape(-1).view(np.uint8)

    def release(self) -> None:
        self.fields = []
