"""Share of the compress calls' time the host API spends packing (%).

The union of the program's ``lzss.compress.pack`` spans (byte view,
zero-pad to whole chunks, int32 widening) inside the harness's
``bench.compress`` spans, over those spans' length. Read in the host cells;
moves ``compress_GBps.host``.
"""

from bench import spans


def read(ctx):
    return spans.share(ctx, "compress", ("pack",))
