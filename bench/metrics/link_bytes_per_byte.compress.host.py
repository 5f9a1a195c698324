"""Bytes across the host link per original byte, compressing (B/B).

The program's counters ``lzss.compress.h2d_bytes`` plus ``.d2h_bytes``, over
``lzss.compress.bytes``, since the process started (warm-up included: the
ratio is a function of the fields alone). Read in the host cells; moves
``compress_GBps.host``.
"""

from bench import spans


def read(ctx):
    return spans.link_bytes_per_byte("compress")
