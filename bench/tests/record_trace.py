"""Record the small chip trace that ``test_trace.py`` reads.

On a machine with one TPU:

    python3 bench/tests/record_trace.py <out_dir>

runs a traced device-placement run of ``quant-u16.resident`` at a small
field shape, and writes ``<out_dir>/resident.xplane.pb`` with
``<out_dir>/summary.json``: the planes, lines and first events (with their
stats) of the trace, and the run's result line.
"""

import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from bench import harness  # noqa: E402

SMALL = dict(field_shape=[8, 128, 256], fields_per_step=2)


def summary(path) -> dict:
    data = jax.profiler.ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            lines.append(dict(
                name=line.name, n=len(events),
                first=[dict(name=e.name, start_ns=e.start_ns,
                            duration_ns=e.duration_ns,
                            stats={k: str(v) for k, v in dict(e.stats).items()})
                       for e in events[:12]],
            ))
        out.append(dict(name=plane.name, lines=lines))
    return dict(planes=out)


def main(out_dir: str) -> None:
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    xplane = out / "resident.xplane.pb"
    result = harness.run_cell("quant-u16.resident", 7, 1.0, True,
                              config_overrides=SMALL, trace_copy=xplane)
    s = summary(xplane)
    s["result"] = result
    (out / "summary.json").write_text(json.dumps(s, indent=1, default=str))
    print(json.dumps(result))


if __name__ == "__main__":
    main(sys.argv[1])
