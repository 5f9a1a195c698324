"""One run of one cell: set-up, the measured window, the check, the result.

The window is a closed loop with one caller.  Each step is a round trip of
one field of the time step, compress then decompress, cycling through the
fields in order, until ``--seconds`` have passed and every field has had a
round trip.  Each direction's rate is all of its bytes over all of its
calls' time.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time

from bench import codec, manifest

DIRECTIONS = ("compress", "decompress")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def devices(chips: int, require_chip: bool):
    import jax

    devs = jax.devices()
    if require_chip and (jax.default_backend() != "tpu" or len(devs) < chips):
        raise NoChip(
            f"cell needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{jax.default_backend()} device(s)"
        )
    return devs


def enable_caches() -> None:
    """The program's compile cache (``.jax_cache/`` in the checkout, or
    ``$JAX_COMPILATION_CACHE_DIR``), for every program however short."""
    import jax
    from repro.runtime import compile_cache

    compile_cache.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileCounter:
    """Counts JAX compile and cache-load events while ``on``."""

    def __init__(self):
        import jax

        self.on, self.count = False, 0

        def listen(event, *_a, **_k):
            if self.on and event.startswith(
                ("/jax/core/compile/backend_compile",
                 "/jax/compilation_cache/cache_retrieval")
            ):
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


@dataclasses.dataclass
class Window:
    seconds: float = 0.0
    steps: int = 0
    failed: int = 0
    time: dict = dataclasses.field(default_factory=lambda: dict.fromkeys(
        DIRECTIONS, 0.0))
    bytes: dict = dataclasses.field(default_factory=lambda: dict.fromkeys(
        DIRECTIONS, 0))
    calls: list = dataclasses.field(default_factory=list)  # field per step
    per_call: list = dataclasses.field(default_factory=list)  # (t_c, t_d)
    kept: dict = dataclasses.field(default_factory=dict)  # field -> outputs


def drive(placement, n_fields: int, field_bytes: int, seconds: float,
          spans: bool = False) -> Window:
    """The measured window: round trips until ``seconds`` and a full pass."""
    import jax

    def span(name):
        if spans:
            return jax.profiler.TraceAnnotation("bench." + name)
        return contextlib.nullcontext()

    w = Window()
    k = 0
    start = time.perf_counter()
    while time.perf_counter() - start < seconds or len(w.kept) < n_fields:
        try:
            with span("compress"):
                t0 = time.perf_counter()
                out = placement.compress(k)
                t1 = time.perf_counter()
            with span("decompress"):
                dec = placement.decompress(out)
                t2 = time.perf_counter()
        except Exception as e:  # the program failed a call: stop, report
            print(f"round trip of field {k} failed: {e!r}", file=sys.stderr)
            w.failed += 1
            w.steps += 1
            break
        w.time["compress"] += t1 - t0
        w.time["decompress"] += t2 - t1
        w.bytes["compress"] += field_bytes
        w.bytes["decompress"] += field_bytes
        w.kept[k] = (out, dec)
        w.calls.append(k)
        w.per_call.append((round(t1 - t0, 6), round(t2 - t1, 6)))
        w.steps += 1
        k = (k + 1) % n_fields
    w.seconds = time.perf_counter() - start
    return w


def check(placement, kept: dict, originals, cfg: dict, spec: dict,
          seed: int, root=manifest.ROOT) -> dict:
    """Compare what the window produced with the reference, by the check the
    cell names (``bench/checks/<spec["check"]>.py``).

    ``originals(k)`` gives field ``k``'s bytes as the benchmark made them;
    ``spec`` is the cell's ``bench/workloads/<cell>.json``.
    Returns ``{number: {"value", "limit", "compared"}}``.
    """
    return manifest.module("checks", spec["check"], root).compare(
        placement, kept, originals, cfg, spec, seed)


def passed(checks: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in checks.values())


def reader(name: str, root=manifest.ROOT):
    """The ``read(ctx)`` of ``bench/metrics/<name>.py``."""
    return manifest.module("metrics", name, root).read


def time_step(cfg: dict, seed: int, root=manifest.ROOT) -> tuple:
    """The configuration's fields for ``seed``, from the generator it names
    (``bench/generators/<generator>.py``)."""
    return manifest.module("generators", cfg["generator"], root).time_step(
        cfg, seed)


def place(traffic: dict, cfg: dict, fields: tuple, root=manifest.ROOT):
    """The placement a traffic mix names (``bench/placements/<name>.py``),
    around ``fields``."""
    return manifest.module("placements", traffic["placement"], root
                           ).Placement(cfg, fields)


def control(cfg: dict, fields: tuple):
    """The configuration's control (``bench/controls/<kind>.py``): a
    placement that breaks the guarantee, around ``fields``."""
    spec = dict(cfg["control"])
    return manifest.module("controls", spec.pop("kind")).make(
        cfg, fields, **spec)


@dataclasses.dataclass
class Readings:
    """What a per-layer reader may read."""

    trace: object
    bytes: dict  # direction -> the algorithm's bytes over the traced calls
    peaks: object


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             t_start: float | None = None, require_chip: bool = True,
             config_overrides: dict | None = None, trace_copy=None,
             root=manifest.ROOT) -> dict:
    """One run of ``cell``; returns the result object (the last line).

    ``trace_copy``, a path, keeps a copy of the traced run's ``.xplane.pb``.
    ``root`` is the tree whose ``BENCHMARK.json`` and ``bench/`` files the
    run reads.
    """
    t_start = time.perf_counter() if t_start is None else t_start
    m = manifest.load(root)
    files = manifest.cell_files(m, cell, root)
    cfg = dict(files["config"], **(config_overrides or {}))
    readers = {x["name"]: reader(x["name"], root) for x in files["per_layer"]}
    os.environ["REPRO_AUTOTUNE"] = "0"  # the fixed geometry, no sweep

    import jax

    devs = devices(files["cell"]["chips"], require_chip)
    phases = dict(jax_init=time.perf_counter() - t_start)
    enable_caches()
    counter = CompileCounter()
    from bench import peaks

    n_fields = int(cfg["fields_per_step"])
    fbytes = codec.field_bytes(cfg)
    fields = time_step(cfg, seed, root)
    jax.block_until_ready(fields)
    phases["data"] = time.perf_counter() - t_start
    placement = place(files["traffic"], cfg, fields, root)
    del fields
    phases["placement"] = time.perf_counter() - t_start
    info = dict(
        cell=cell, seed=seed, platform=devs[0].platform,
        device_kind=devs[0].device_kind, device_count=len(devs),
        backend=placement.backend, decoder=placement.decoder,
        chunk_symbols=cfg["chunk_symbols"],
        chunks_per_block=cfg["chunks_per_block"] or _fallback_g(cfg),
        window=cfg["window"], symbol_size=cfg["symbol_size"],
        field_shape=cfg["field_shape"], field_bytes=fbytes,
        fields_per_step=n_fields, step_bytes=fbytes * n_fields,
        placement=placement.name,
    )
    print(json.dumps({"info": info}), flush=True)
    placement.warm_up()
    setup_s = time.perf_counter() - t_start

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    counter.on = True
    w = drive(placement, n_fields, fbytes, seconds, spans=trace)
    counter.on = False
    if trace:
        jax.profiler.stop_trace()
    stats = devs[0].memory_stats() or {}
    device = dict(
        platform=devs[0].platform, kind=devs[0].device_kind, count=len(devs),
        memory_peak_bytes=stats.get("peak_bytes_in_use"),
    )

    totals = {k: placement.total_bytes(out) for k, (out, _) in w.kept.items()}
    checks = check(placement, w.kept, placement.original_bytes, cfg,
                   files["check"], seed, root)
    placement.release()
    w.kept.clear()
    correct = w.failed == 0 and passed(checks)

    result = dict(correct=correct, attempted=w.steps, failed=w.failed)
    if not trace:
        values = dict(
            compress_GBps=_rate(w, "compress"),
            decompress_GBps=_rate(w, "decompress"),
            ratio=fbytes * len(totals) / sum(totals.values())
            if len(totals) == n_fields else None,
            setup_s=setup_s,
        )
        metrics = {
            x["name"]: dict(value=values[quantity(x["name"])], unit=x["unit"])
            for x in files["end_to_end"]
            if values.get(quantity(x["name"])) is not None
        }
    else:
        from bench import trace as tr

        xplane = tr.find_xplane(trace_dir)
        if trace_copy:
            shutil.copyfile(xplane, trace_copy)
        reduced = tr.load(xplane)
        shutil.rmtree(trace_dir, ignore_errors=True)
        alg = sum(fbytes + totals.get(k, 0) for k in w.calls)
        ctx = Readings(reduced, dict.fromkeys(DIRECTIONS, alg),
                       peaks.peaks(devs[0].device_kind) if require_chip
                       else peaks.DEVICE_PEAKS["TPU v5 lite"])
        metrics = {}
        for x in files["per_layer"]:
            v = readers[x["name"]](ctx)
            if v is not None:
                metrics[x["name"]] = dict(value=v, unit=x["unit"])
        w0, w1 = reduced.window
        device.update(busy_s=reduced.busy_seconds([(w0, w1)]),
                      window_s=w1 - w0)
        result["breakdown"] = dict(device_ops=reduced.top_ops(10),
                                   idle_gaps=reduced.idle_gaps(10))
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {k: dict(value=v["value"], limit=v["limit"])
                        for k, v in checks.items()}
    print(json.dumps(dict(setup_phases_s=phases, window_s=w.seconds,
                          steps=w.steps, compiles_in_window=counter.count,
                          seconds=w.time, per_call_s=w.per_call)), file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k}: {v['value']} (limit {v['limit']}, of "
              f"{v['compared']} bytes compared)", file=sys.stderr)
    sys.stderr.flush()
    return result


def quantity(metric: str) -> str:
    """The quantity an end-to-end metric reports: its name up to the first
    ``.``.  ``compress_GBps.host`` is ``compress_GBps`` in the cells that
    metric lists, held to a bound of its own."""
    return metric.split(".", 1)[0]


def _rate(w: Window, direction: str):
    t = w.time[direction]
    return w.bytes[direction] / t / 1e9 if t > 0 else None


def _fallback_g(cfg: dict) -> int:
    from repro.core import autotune

    key = autotune.TuneKey(
        device_kind=autotune.device_kind(),
        dtype=autotune.default_dtype(cfg["symbol_size"]),
        symbol_size=cfg["symbol_size"], window=cfg["window"],
        direction="compress", chunk_symbols=cfg["chunk_symbols"],
    )
    return autotune.fallback(key)[1]


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)
    try:
        result = run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                          t_start=t_start)
    except NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0 if result["failed"] == 0 else 1

