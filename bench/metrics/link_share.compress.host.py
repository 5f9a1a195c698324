"""Share of the compress calls' time spent crossing the host link (%).

The union of the program's ``lzss.compress.upload`` (the int32 symbols) and
``.download`` (the worst-case container buffer and its length) spans inside
the harness's ``bench.compress`` spans, over those spans' length. Read in
the host cells; moves ``compress_GBps.host``.
"""

from bench import spans


def read(ctx):
    return spans.share(ctx, "compress", ("upload", "download"))
