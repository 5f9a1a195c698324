"""Distinct decode programs the host API asked for (count).

The program's counter ``lzss.decompress.programs``: one per distinct
dispatch key (capacity bucket, S, C, chunks, decoder, block geometry, method
parameters), set-up included, since each is a compile there. Read in the
host cells; moves ``setup_s``.
"""

from bench import spans


def read(ctx):
    return spans.count("lzss.decompress.programs")
