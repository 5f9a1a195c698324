"""The compress calls' share of the HBM roofline (%).

The algorithm's bytes (every input byte read once, every container byte
written once) over the published HBM bandwidth, divided by all device busy
time inside the ``bench.compress`` spans. No kernel name or lane layout
enters the count, so any implementation is read against the same work. Read
in the host cells; moves ``compress_GBps.host``.
"""

from bench import trace


def read(ctx):
    return trace.hbm_roofline(ctx, "compress")
