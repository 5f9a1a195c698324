"""The ``host`` placement: the field is a numpy array on the host.

Compress through ``lzss.compress``, decode through ``lzss.decompress``;
numpy in, numpy out.  Set-up pays one ``device_get`` of the time step.
"""

from __future__ import annotations

import jax
import numpy as np

from bench import codec


class Placement:
    """Fields as numpy arrays on the host, through the host-bytes API."""

    name = "host"

    def __init__(self, cfg: dict, fields: tuple):
        from repro.core import lzss, pipeline

        self._lzss = lzss
        self.fields = [np.asarray(f) for f in jax.device_get(fields)]
        for f in fields:
            f.delete()
        self.dtype = self.fields[0].dtype
        self.lz = codec.lz_config(cfg)
        self.backend = pipeline.resolve_backend(cfg["backend"])
        self.decoder = pipeline.resolve_decoder(cfg["decoder"])

    def compress(self, k: int):
        return self._lzss.compress(self.fields[k], self.lz)

    def decompress(self, res):
        return self._lzss.decompress(res.data, decoder=self.lz.decoder).view(
            self.dtype
        )

    def warm_up(self) -> None:
        # the host decoder's program is specialised on the container's size
        # (4 KiB buckets), so every field of the step has a shape of its own
        for k in range(len(self.fields)):
            self.decompress(self.compress(k))

    def total_bytes(self, res) -> int:
        return int(res.total_bytes)

    def container_bytes(self, res) -> np.ndarray:
        return np.asarray(res.data, np.uint8)

    def decoded_bytes(self, dec) -> np.ndarray:
        return np.asarray(dec).reshape(-1).view(np.uint8)

    def original_bytes(self, k: int) -> np.ndarray:
        return self.fields[k].reshape(-1).view(np.uint8)

    def release(self) -> None:
        self.fields = []
