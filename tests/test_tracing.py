"""The host API's spans and counters (``repro.runtime.tracing``).

Spans are read back from a real profiler trace through the benchmark's own
reduction (``bench/trace.py``), so they are checked on the clock and the
thread the benchmark reads them from.
"""

import numpy as np
import pytest

import jax

from repro.core import format as fmt
from repro.core import lzss
from repro.runtime import tracing

STEPS = {
    "compress": ("pack", "upload", "dispatch", "wait", "download"),
    "decompress": ("validate", "stage", "upload", "dispatch", "wait",
                   "download", "unpack"),
}

# (field, symbol size): u16 codes with runs, and f32 noise
CASES = {
    "u16": (lambda rng: np.repeat(rng.integers(0, 16, 700), 3).astype(np.uint16), 2),
    "f32": (lambda rng: rng.standard_normal(1500).astype(np.float32), 4),
}


def _case(name):
    make, s = CASES[name]
    return make(np.random.default_rng(13)), lzss.LZSSConfig(
        symbol_size=s, window=64, chunk_symbols=256)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One profiled round trip per case, in the harness's ``bench.*`` spans."""
    from bench import trace

    lzss.decompress(lzss.compress(*_case("u16")).data)  # compile outside
    lzss.decompress(lzss.compress(*_case("f32")).data)
    out = {}
    for name in CASES:
        d = tmp_path_factory.mktemp(f"trace-{name}")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(d), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.compress"):
                res = lzss.compress(*_case(name))
            with jax.profiler.TraceAnnotation("bench.decompress"):
                lzss.decompress(res.data)
        finally:
            jax.profiler.stop_trace()
        out[name] = trace.load(trace.find_xplane(d))
    return out


@pytest.mark.parametrize("direction", sorted(STEPS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_spans_nest_in_their_call_in_order(traced, case, direction):
    t = traced[case]
    (outer,) = t.spans[direction]
    got = {}
    for s, e, name in t.host:
        if name.startswith(f"lzss.{direction}."):
            got.setdefault(name.rsplit(".", 1)[1], []).append((s, e))
    assert set(got) == set(STEPS[direction])
    for step, ivs in got.items():
        for s, e in ivs:
            assert outer[0] <= s <= e <= outer[1], step
    for a, b in zip(STEPS[direction], STEPS[direction][1:]):
        assert max(e for _, e in got[a]) <= min(s for s, _ in got[b]), (a, b)


@pytest.mark.parametrize("case", sorted(CASES))
def test_counters_count_the_bytes_that_cross(case):
    data, cfg = _case(case)
    n = data.nbytes
    s, c = cfg.symbol_size, cfg.chunk_symbols
    nsym = -(-n // s)
    nc = -(-nsym // c)
    tracing.reset()
    res = lzss.compress(data, cfg)
    got = tracing.counters()
    assert got == {
        "lzss.compress.bytes": n,
        "lzss.compress.h2d_bytes": nc * c * 4,
        "lzss.compress.d2h_bytes": fmt.max_compressed_bytes(n, s, c) + 4,
    }
    tracing.reset()
    out = lzss.decompress(res.data)
    assert out.tobytes() == data.tobytes()
    got = tracing.counters()
    assert got == {
        "lzss.decompress.bytes": n,
        "lzss.decompress.h2d_bytes": lzss._dispatch_capacity(res.total_bytes)
        + 2 * nc * 4,  # the two per-chunk int32 tables
        "lzss.decompress.d2h_bytes": nc * c * 4,
        "lzss.decompress.programs": 1,
    }


def test_decode_programs_counts_distinct_dispatch_keys():
    cfg = lzss.LZSSConfig(symbol_size=2, window=64, chunk_symbols=256)
    n = 8192  # 16 chunks either way
    small = lzss.compress(np.zeros(n, np.uint8), cfg).data
    large = lzss.compress(
        np.random.default_rng(5).integers(0, 256, n, dtype=np.uint8), cfg).data
    assert lzss._dispatch_capacity(small.size) != lzss._dispatch_capacity(
        large.size)
    tracing.reset()

    def programs():
        return tracing.counters()["lzss.decompress.programs"]

    lzss.decompress(small)
    assert programs() == 1
    lzss.decompress(small)
    assert programs() == 1  # the same size: the same program
    lzss.decompress(large)
    assert programs() == 2  # a new capacity bucket: a new program
    lzss.decompress(large)
    assert programs() == 2


def test_count_and_reset():
    tracing.reset()
    tracing.count("x")
    tracing.count("x", 4)
    tracing.count_distinct("k", ("a", 1))
    tracing.count_distinct("k", ("a", 1))
    tracing.count_distinct("k", ("b", 1))
    snap = tracing.counters()
    assert snap == {"x": 5, "k": 2}
    snap["x"] = 0  # a copy: the counters are untouched
    assert tracing.counters()["x"] == 5
    tracing.reset()
    assert tracing.counters() == {}
    tracing.count_distinct("k", ("a", 1))  # seen keys are cleared too
    assert tracing.counters() == {"k": 1}
