"""Share of the compress calls' time in which the device ran nothing (%).

1 minus the union of device-operation intervals inside the harness's
``bench.compress`` spans, over the spans' length. In the host cells this is
the host API's hold on the chip: padding, packing, transfers and trimming.
Read in the host cells; moves ``compress_GBps.host``.
"""

from bench import trace


def read(ctx):
    return trace.idle_share(ctx, "compress")
