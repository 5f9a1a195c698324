"""Host spans and counters of the program's own layers.

A span is a ``jax.profiler.TraceAnnotation``: it lands in the profiler's
trace on the calling thread, on the same clock as the device's operations,
and costs well under a microsecond when no profiler runs, so it is always
on.  Counters are process-global integers, cumulative since process start
(or the last ``reset``), read in-process with ``counters()``.
"""

from __future__ import annotations

import threading

import jax

_LOCK = threading.Lock()
_COUNTERS: dict = {}
_SEEN: dict = {}  # counter name -> the keys ``count_distinct`` has seen


def span(name: str):
    """A context manager that records ``name`` as a host span."""
    return jax.profiler.TraceAnnotation(name)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name``."""
    with _LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + int(n)


def count_distinct(name: str, key) -> None:
    """Count ``key`` under ``name`` the first time it is seen: the counter
    holds the number of distinct keys."""
    with _LOCK:
        seen = _SEEN.setdefault(name, set())
        if key not in seen:
            seen.add(key)
            _COUNTERS[name] = _COUNTERS.get(name, 0) + 1


def counters() -> dict:
    """A copy of every counter."""
    with _LOCK:
        return dict(_COUNTERS)


def reset() -> None:
    """Clear every counter and every distinct key seen."""
    with _LOCK:
        _COUNTERS.clear()
        _SEEN.clear()
