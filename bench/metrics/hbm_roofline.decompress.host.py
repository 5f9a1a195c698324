"""The decompress calls' share of the HBM roofline (%).

The algorithm's bytes (every container byte read once, every output byte
written once) over the published HBM bandwidth, divided by all device busy
time inside the ``bench.decompress`` spans. No kernel name or lane layout
enters the count, so any implementation is read against the same work. Read
in the host cells; moves ``decompress_GBps.host``.
"""

from bench import trace


def read(ctx):
    return trace.hbm_roofline(ctx, "decompress")
