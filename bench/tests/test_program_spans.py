"""The readers of the program's own spans and counters (``bench/spans.py``)
on a hand-built trace and hand-set counters."""

import sys

import pytest

from bench import harness, peaks, trace

SHARES = ("host_work_share.compress.host", "host_work_share.decompress.host",
          "link_share.compress.host", "link_share.decompress.host")
COUNTS = ("link_bytes_per_byte.compress.host",
          "link_bytes_per_byte.decompress.host", "decode_programs.host")


def ctx(t):
    return harness.Readings(t, {"compress": 1, "decompress": 1},
                            peaks.peaks("TPU v5 lite"))


def synthetic():
    # compress calls 0-10 s and 20-30 s; decompress 10-14 s
    return trace.Trace(
        ops={},
        spans={"compress": [(0, 10), (20, 30)], "decompress": [(10, 14)]},
        host=[
            (0, 2, "lzss.compress.pack"),
            (1, 3, "lzss.compress.pack"),  # overlaps the first: 0-3 once
            (20, 21, "lzss.compress.pack"),
            (3, 4, "lzss.compress.upload"),
            (3.2, 3.4, "TransferToDevice"),  # a JAX event inside upload
            (8, 10.5, "lzss.compress.download"),  # runs past its call
            (10, 11, "lzss.decompress.validate"),
            (10.2, 10.4, "lzss.decompress.validate"),  # nested in the first
            (13, 14, "lzss.decompress.unpack"),
            (12, 12.5, "lzss.decompress.download"),
            (15, 16, "lzss.decompress.stage"),  # outside every call
        ],
    )


def test_span_shares_are_unions_clipped_to_the_calls():
    read = {n: harness.reader(n)(ctx(synthetic())) for n in SHARES}
    assert read["host_work_share.compress.host"] == pytest.approx(100 * 4 / 20)
    assert read["link_share.compress.host"] == pytest.approx(100 * 3 / 20)
    assert read["host_work_share.decompress.host"] == pytest.approx(
        100 * 2 / 4)
    assert read["link_share.decompress.host"] == pytest.approx(100 * 0.5 / 4)


def test_span_shares_are_none_without_the_programs_spans():
    t = trace.Trace(ops={}, spans={"compress": [(0, 1)],
                                   "decompress": [(1, 2)]},
                    host=[(0, 1, "PjitFunction(compress)")])
    for n in SHARES:
        assert harness.reader(n)(ctx(t)) is None, n
    t = trace.Trace(ops={}, spans={}, host=[(0, 1, "lzss.compress.pack")])
    assert harness.reader(SHARES[0])(ctx(t)) is None  # no call to share


@pytest.fixture
def counters():
    from repro.runtime import tracing

    tracing.reset()
    yield tracing
    tracing.reset()


def test_counter_readers(counters):
    counters.count("lzss.compress.bytes", 1000)
    counters.count("lzss.compress.h2d_bytes", 2000)
    counters.count("lzss.compress.d2h_bytes", 1065)
    counters.count("lzss.decompress.bytes", 1000)
    counters.count("lzss.decompress.h2d_bytes", 366)
    counters.count("lzss.decompress.d2h_bytes", 2000)
    for key in range(13):
        counters.count_distinct("lzss.decompress.programs", key)
    t = ctx(synthetic())
    read = {n: harness.reader(n)(t) for n in COUNTS}
    assert read == {"link_bytes_per_byte.compress.host": 3.065,
                    "link_bytes_per_byte.decompress.host": 2.366,
                    "decode_programs.host": 13}


def test_counter_readers_are_none_without_the_counters(counters, monkeypatch):
    counters.count("lzss.compress.bytes", 1000)  # a ratio needs all three
    for n in COUNTS:
        assert harness.reader(n)(ctx(synthetic())) is None, n
    # a program without the tracing module, as before it had one
    monkeypatch.setitem(sys.modules, "repro.runtime.tracing", None)
    for n in COUNTS:
        assert harness.reader(n)(ctx(synthetic())) is None, n
