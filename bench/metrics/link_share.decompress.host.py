"""Share of the decompress calls' time spent crossing the host link (%).

The union of the program's ``lzss.decompress.upload`` (the staged container
and its two tables) and ``.download`` (the int32 symbols) spans inside the
harness's ``bench.decompress`` spans, over those spans' length. Read in
the host cells; moves ``decompress_GBps.host``.
"""

from bench import spans


def read(ctx):
    return spans.share(ctx, "decompress", ("upload", "download"))
