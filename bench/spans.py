"""Readers' arithmetic for the program's own spans and counters.

The host API (``core/lzss.py``) wraps each step of a call in a span named
``lzss.<dir>.<step>``; they land in the trace on the calling thread, so
``trace.load`` keeps them in ``Trace.host``.  Its counters
(``repro.runtime.tracing``) are read in-process, cumulative since the
process started.  Each function returns ``None`` where the program emits
no such span or counter.
"""

from __future__ import annotations

from bench import trace

PREFIX = "lzss."


def share(ctx, direction: str, steps) -> float | None:
    """The union of the ``lzss.<direction>.<step>`` spans for ``steps``,
    clipped to the harness's ``bench.<direction>`` spans, over those
    spans' length (%)."""
    names = {f"{PREFIX}{direction}.{s}" for s in steps}
    mine = trace.merge((s, e) for s, e, name in ctx.trace.host
                       if name in names)
    outer = trace.merge(ctx.trace.spans.get(direction, ()))
    length = sum(e - s for s, e in outer)
    if not mine or length <= 0:
        return None
    return 100.0 * trace.overlap(mine, outer) / length


def counters() -> dict | None:
    """The program's counters, or ``None`` where it keeps none."""
    try:
        from repro.runtime import tracing
    except ImportError:
        return None
    return tracing.counters()


def link_bytes_per_byte(direction: str) -> float | None:
    """Bytes that crossed the host link both ways per original byte."""
    c = counters() or {}
    key = f"{PREFIX}{direction}."
    parts = [c.get(key + k) for k in ("h2d_bytes", "d2h_bytes", "bytes")]
    if None in parts or parts[2] <= 0:
        return None
    return (parts[0] + parts[1]) / parts[2]


def count(name: str) -> int | None:
    """Counter ``name``, or ``None`` where the program keeps no such one."""
    return (counters() or {}).get(name)
