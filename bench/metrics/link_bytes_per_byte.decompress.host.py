"""Bytes across the host link per original byte, decompressing (B/B).

The program's counters ``lzss.decompress.h2d_bytes`` plus ``.d2h_bytes``,
over ``lzss.decompress.bytes`` (the bytes restored), since the process
started. Read in the host cells; moves ``decompress_GBps.host``.
"""

from bench import spans


def read(ctx):
    return spans.link_bytes_per_byte("decompress")
