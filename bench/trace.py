"""Reduction of a profiler trace to the per-layer quantities.

The harness wraps every timed call in a ``jax.profiler.TraceAnnotation``
(``bench.compress`` / ``bench.decompress``); the profiler writes those spans
on the host plane and the device's operations on each TPU plane, on one
clock.  From the ``.xplane.pb`` this module keeps:

- device operations: the events of each TPU plane's ``XLA Ops`` line, with
  whether each is a Mosaic (Pallas) kernel, i.e. an XLA custom call to
  ``tpu_custom_call``; every other operation is XLA's own;
- the harness's spans by direction, and the other host events of the
  thread they are on (what the host was doing in an idle gap).

Busy time is the union of operation intervals, clipped to the spans,
averaged over the chips that ran anything.

The device's clock, as the trace gives it, runs about a millisecond ahead
of the host's (on a v5e, the recorded ``data/resident.xplane.pb``: device
programs start 0.9-1.3 ms before the span that dispatched them).  A
program cannot start before its dispatch, so ``load`` shifts the device
timeline by the least amount that puts the first program of every span
inside the span (``align``).
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import pathlib
import re

SPAN_PREFIX = "bench."
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
LOOKBACK = 3e-3  # seconds: the most a span's program may appear to lead it
HOST_PLANE = "/host:CPU"
MOSAIC_MARK = "tpu_custom_call"


def merge(intervals) -> list:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def overlap(a, b) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def gaps(busy, window) -> list:
    """Idle ``(start, end)`` pieces of ``window`` that ``busy`` leaves."""
    out, at = [], window[0]
    for s, e in busy:
        if e <= window[0] or s >= window[1]:
            continue
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < window[1]:
        out.append((at, window[1]))
    return out


def short_name(name: str) -> str:
    """``%lz_fused_mono_pallas.1 = s32[...] custom-call(...)`` ->
    ``lz_fused_mono_pallas``: the instruction's name without its number."""
    return re.sub(r"\.\d+$", "", name.split(" = ", 1)[0].lstrip("%"))


def align(program_starts, spans) -> float:
    """Seconds to add to device times so that no span's first program
    starts before the span.  ``program_starts`` are the device's program
    (module) start times, ``spans`` all the harness's spans."""
    starts = sorted(program_starts)
    leads = []
    for s, e in spans:
        i = bisect.bisect_left(starts, s - LOOKBACK)
        if i < len(starts) and starts[i] < e:
            leads.append(starts[i] - s)
    return max(0.0, -min(leads)) if leads else 0.0


@dataclasses.dataclass(frozen=True)
class Op:
    start: float  # seconds on the trace's clock
    end: float
    name: str
    mosaic: bool


@dataclasses.dataclass
class Trace:
    ops: dict  # chip -> [Op]
    spans: dict  # direction -> [(start, end)]
    host: list  # [(start, end, name)] on the spans' thread, spans excluded
    shift: float = 0.0  # seconds added to the device's times by ``align``

    @property
    def chips(self) -> list:
        return [c for c, ops in self.ops.items() if ops]

    def _busy(self, chip, mosaic=None) -> list:
        return merge(
            (o.start, o.end)
            for o in self.ops[chip]
            if mosaic is None or o.mosaic == mosaic
        )

    def span_seconds(self, direction: str) -> float:
        return sum(e - s for s, e in merge(self.spans.get(direction, ())))

    def busy_seconds(self, within, mosaic=None) -> float:
        """Device busy time inside ``within`` (intervals), chip-averaged."""
        chips = self.chips
        if not chips:
            return 0.0
        w = merge(within)
        return sum(overlap(self._busy(c, mosaic), w) for c in chips) / len(chips)

    @property
    def window(self) -> tuple:
        ends = [x for v in self.spans.values() for x in v]
        if not ends:
            return (0.0, 0.0)
        return (min(s for s, _ in ends), max(e for _, e in ends))

    def top_ops(self, n: int = 10) -> list:
        w0, w1 = self.window
        by_name = collections.Counter()
        for c in self.chips:
            for o in self.ops[c]:
                by_name[short_name(o.name)] += max(
                    0.0, min(o.end, w1) - max(o.start, w0))
        k = max(1, len(self.chips))
        return [[name, t / k] for name, t in by_name.most_common(n) if t > 0]

    def idle_gaps(self, n: int = 10) -> list:
        """The longest device gaps in the window, named by the host."""
        chips = self.chips
        if not chips:
            return []
        busy = merge(
            (o.start, o.end) for c in chips for o in self.ops[c]
        )
        out = []
        for s, e in gaps(busy, self.window):
            out.append([self.host_doing((s + e) / 2), e - s])
        out.sort(key=lambda x: -x[1])
        return out[:n]

    def host_doing(self, t: float) -> str:
        """The span at ``t`` and the innermost host event inside it."""
        where = "between calls"
        for d, spans in self.spans.items():
            if any(s <= t < e for s, e in spans):
                where = SPAN_PREFIX + d
        inner = [(e - s, name) for s, e, name in self.host if s <= t < e]
        return f"{where}: {min(inner)[1]}" if inner else where


def load(path) -> Trace:
    """Read one ``.xplane.pb`` into a ``Trace``."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    ops, spans, host = {}, collections.defaultdict(list), []
    span_lines, programs = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            chip = plane.name[len(DEVICE_PLANE_PREFIX):]
            programs += [ev.start_ns * 1e-9 for line in plane.lines
                         if line.name == MODULES_LINE for ev in line.events]
            ops[chip] = [
                Op(
                    ev.start_ns * 1e-9,
                    (ev.start_ns + ev.duration_ns) * 1e-9,
                    ev.name,
                    MOSAIC_MARK in ev.name,  # the HLO text names the target
                )
                for line in plane.lines
                if line.name == OPS_LINE
                for ev in line.events
                if ev.duration_ns > 0
            ]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                events = [
                    (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
                     ev.name)
                    for ev in line.events
                ]
                mine = [x for x in events if x[2].startswith(SPAN_PREFIX)]
                if mine:
                    span_lines.append(events)
                for s, e, name in mine:
                    spans[name[len(SPAN_PREFIX):]].append((s, e))
    for events in span_lines:
        host += [x for x in events if not x[2].startswith(SPAN_PREFIX)]
    shift = align(programs, [x for v in spans.values() for x in v])
    ops = {c: [dataclasses.replace(o, start=o.start + shift, end=o.end + shift)
               for o in v] for c, v in ops.items()}
    return Trace(ops=ops, spans=dict(spans), host=host, shift=shift)


def find_xplane(log_dir) -> pathlib.Path:
    found = sorted(pathlib.Path(log_dir).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


# The per-layer readers' arithmetic (``bench/metrics/<name>.py`` each call
# one of these for its direction).  ``ctx`` is ``harness.Readings``.  Each
# returns ``None`` where the trace holds nothing to read.


def idle_share(ctx, direction: str):
    """1 minus the union of device-op intervals in the direction's spans,
    over the spans' length (%)."""
    span = ctx.trace.span_seconds(direction)
    if span <= 0 or not ctx.trace.chips:
        return None
    busy = ctx.trace.busy_seconds(ctx.trace.spans[direction])
    return 100.0 * (1.0 - busy / span)


def xla_share(ctx, direction: str):
    """Busy time in ops that are not Mosaic kernels, over all busy time in
    the direction's spans (%)."""
    spans = ctx.trace.spans.get(direction, [])
    busy = ctx.trace.busy_seconds(spans)
    if busy <= 0:
        return None
    return 100.0 * ctx.trace.busy_seconds(spans, mosaic=False) / busy


def hbm_roofline(ctx, direction: str):
    """The algorithm's bytes over the published HBM bandwidth, divided by
    all device busy time in the direction's spans (%)."""
    busy = ctx.trace.busy_seconds(ctx.trace.spans.get(direction, []))
    if busy <= 0:
        return None
    return 100.0 * ctx.bytes[direction] / ctx.peaks.hbm_bw / busy
