"""Share of the decompress calls' time in which the device ran nothing (%).

1 minus the union of device-operation intervals inside the harness's
``bench.decompress`` spans, over the spans' length. In the host cells this
is the host API's hold on the chip: padding, packing, transfers and
trimming. Read in the host cells; moves ``decompress_GBps.host``.
"""

from bench import trace


def read(ctx):
    return trace.idle_share(ctx, "decompress")
