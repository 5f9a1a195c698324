"""Share of the compress calls' time in which the device ran nothing (%).

1 minus the union of device-operation intervals inside the harness's
``bench.compress`` spans, over the spans' length. In the resident cells the
calls run back to back, so this is the device's own idle time. Read in the
resident cells; moves ``compress_GBps``.
"""

from bench import trace


def read(ctx):
    return trace.idle_share(ctx, "compress")
