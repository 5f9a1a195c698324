"""Read the compared numbers over many seeds in one process.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 [--control]

For each seed: make the time step, drive one round trip of every field
through the cell's placement (or, with ``--control``, through the
configuration's control), and compare exactly as a run does.  One JSON
line a seed.  This is how the limits' lower readings (the program, a dozen
seeds) and upper readings (the control) are taken on the chip; it holds the
chip once, so set-up is paid once.  No timing is read here.
"""

import json
import os
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import codec, harness, manifest  # noqa: E402


def readings(cell: str, seeds, control: bool, *, require_chip=True,
             config_overrides=None):
    os.environ["REPRO_AUTOTUNE"] = "0"
    import jax

    m = manifest.load()
    files = manifest.cell_files(m, cell)
    cfg = dict(files["config"], **(config_overrides or {}))
    harness.devices(files["cell"]["chips"], require_chip)
    harness.enable_caches()
    n_fields = int(cfg["fields_per_step"])
    fbytes = codec.field_bytes(cfg)
    for seed in seeds:
        t0 = time.perf_counter()
        fields = harness.time_step(cfg, seed)
        originals = [np.asarray(f).reshape(-1).view(np.uint8)
                     for f in jax.device_get(fields)]
        if control:
            placement = harness.control(cfg, fields)
        else:
            placement = harness.place(files["traffic"], cfg, fields)
        del fields
        w = harness.drive(placement, n_fields, fbytes, 0.0)
        checks = harness.check(placement, w.kept, originals.__getitem__, cfg,
                               files["check"], seed)
        placement.release()
        w.kept.clear()
        yield dict(cell=cell, seed=seed, control=control,
                   correct=w.failed == 0 and harness.passed(checks),
                   failed=w.failed, checks=checks,
                   seconds=time.perf_counter() - t0)


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control", action="store_true")
    a = p.parse_args(argv)
    seeds = [int(s) for s in a.seeds.split(",")]
    try:
        for r in readings(a.workload, seeds, a.control):
            print(json.dumps(r), flush=True)
    except harness.NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
