"""Share of the decompress calls' time the host API spends on CPU work (%).

The union of the program's ``lzss.decompress.validate`` (container checks),
``.stage`` (copy into the dispatch bucket) and ``.unpack`` (narrowing to the
symbol dtype and trim) spans inside the harness's ``bench.decompress``
spans, over those spans' length. Read in the host cells; moves
``decompress_GBps.host``.
"""

from bench import spans


def read(ctx):
    return spans.share(ctx, "decompress", ("validate", "stage", "unpack"))
