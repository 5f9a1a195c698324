"""Control ``int8_fallback`` (u16 codes): the guarantee broken on purpose.

Each code is narrowed to its high byte with the low byte centred, as the
program's own int8 fallback for u16 codes does (``optim/grad_compress.py``),
then goes through the program's round trip in the ``resident`` placement.
"""

from __future__ import annotations

import jax

from bench import manifest


@jax.jit
def _int8_fallback(codes):
    return (codes & 0xFF00) | 0x80


def make(cfg: dict, fields: tuple):
    resident = manifest.module("placements", "resident")
    return resident.Placement(cfg, tuple(_int8_fallback(f) for f in fields))
