"""Control ``lossy-fz`` (f32 fields): the guarantee broken on purpose.

The program's own error-bounded lossy path, ``backend="lossy-fz"`` at the
configuration's ``error_bound``, in place of the lossless one, driven
through the ``resident`` placement.
"""

from __future__ import annotations

import numpy as np

from bench import codec, manifest


def make(cfg: dict, fields: tuple, *, error_bound: float):
    from repro.core import format as fmt
    from repro.core import pipeline

    lossy = dict(lossy_eb=float(error_bound))
    compress, _ = codec.device_programs(cfg, "lossy-fz", "lossy-fz", **lossy)
    buf, total = compress(fields[0])
    header = fmt.parse_header(np.asarray(buf)[: int(total)])
    params = pipeline.get_decoder("lossy-fz").static_params(header)
    programs = codec.device_programs(
        cfg, "lossy-fz", "lossy-fz", method_params=params, **lossy)
    resident = manifest.module("placements", "resident")
    return resident.Placement(cfg, fields, programs=programs)
