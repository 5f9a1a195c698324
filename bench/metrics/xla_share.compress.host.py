"""Share of the compress calls' device busy time outside Mosaic kernels (%).

Busy time of operations that are not ``tpu_custom_call`` kernels, over all
device busy time, inside the ``bench.compress`` spans: the pipeline glue
around the kernels. Read in the host cells; moves ``compress_GBps.host``.
"""

from bench import trace


def read(ctx):
    return trace.xla_share(ctx, "compress")
