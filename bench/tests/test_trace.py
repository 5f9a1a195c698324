"""The trace reduction: interval arithmetic, the readers' arithmetic, and
the reduction of a trace recorded on a v5e (``data/resident.xplane.pb``)."""

import pathlib

import pytest

from bench import harness, peaks, trace

FIXTURE = pathlib.Path(__file__).parent / "data" / "resident.xplane.pb"


def test_merge_overlap_and_gaps():
    m = trace.merge([(5, 7), (0, 2), (1, 3), (6, 9), (4, 4)])
    assert m == [(0, 3), (5, 9)]
    assert trace.overlap(m, [(2, 6)]) == 2  # 2-3 and 5-6
    assert trace.overlap(m, []) == 0
    assert trace.gaps(m, (0, 10)) == [(3, 5), (9, 10)]
    assert trace.gaps([], (1, 2)) == [(1, 2)]


def synthetic():
    # compress span 0-10 s: a 6 s Mosaic kernel and 1 s of XLA ops;
    # decompress span 10-14 s: a 2 s kernel; 2 s idle in each
    ops = [trace.Op(1, 7, "lz_fused_mono", True),
           trace.Op(7, 8, "fusion.1", False),
           trace.Op(7.5, 8, "copy.2", False),  # overlaps: counted once
           trace.Op(11, 13, "lz_decode_mono", True)]
    return trace.Trace(
        ops={"0": ops},
        spans={"compress": [(0, 10)], "decompress": [(10, 14)]},
        host=[(0.1, 0.9, "PjitFunction(compress)"),
              (13.2, 13.9, "TransferFromDevice")],
    )


def test_busy_time_is_a_union_clipped_to_the_spans():
    t = synthetic()
    assert t.busy_seconds(t.spans["compress"]) == 7
    assert t.busy_seconds(t.spans["compress"], mosaic=False) == 1
    assert t.busy_seconds(t.spans["decompress"]) == 2
    assert t.window == (0, 14)
    assert t.top_ops(2) == [["lz_fused_mono", 6], ["lz_decode_mono", 2]]
    gaps = t.idle_gaps(10)
    assert [g[1] for g in gaps] == [3, 1, 1]
    assert gaps[0][0] == "bench.compress"  # 8-11 s: nothing on the host
    assert {g[0] for g in gaps[1:]} == {
        "bench.compress: PjitFunction(compress)",
        "bench.decompress: TransferFromDevice"}


# the host cells' readers read the same quantities as the resident cells'
SUFFIXES = pytest.mark.parametrize("suffix", ["", ".host"])


@SUFFIXES
def test_the_readers_arithmetic(suffix):
    t = synthetic()
    p = peaks.peaks("TPU v5 lite")
    ctx = harness.Readings(t, {"compress": 819e9 * 0.07,
                               "decompress": 819e9 * 0.02}, p)
    read = {n: harness.reader(n + suffix)(ctx) for n in (
        "idle_share.compress", "idle_share.decompress",
        "xla_share.compress", "xla_share.decompress",
        "hbm_roofline.compress", "hbm_roofline.decompress")}
    assert read["idle_share.compress"] == pytest.approx(30)
    assert read["idle_share.decompress"] == pytest.approx(50)
    assert read["xla_share.compress"] == pytest.approx(100 / 7)
    assert read["xla_share.decompress"] == 0
    assert read["hbm_roofline.compress"] == pytest.approx(1)
    assert read["hbm_roofline.decompress"] == pytest.approx(1)


@SUFFIXES
def test_readers_return_nothing_without_device_operations(suffix):
    t = trace.Trace(ops={}, spans={"compress": [(0, 1)]}, host=[])
    ctx = harness.Readings(t, {"compress": 1, "decompress": 1},
                           peaks.peaks("TPU v5 lite"))
    for n in ("idle_share.compress", "xla_share.decompress",
              "hbm_roofline.compress"):
        assert harness.reader(n + suffix)(ctx) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("TPU v9")


@pytest.fixture(scope="module")
def recorded():
    return trace.load(FIXTURE)


def test_recorded_trace_has_spans_and_device_programs(recorded):
    # quant-u16.resident at a (8, 128, 256) field, 2 fields, 1 s window
    t = recorded
    assert t.chips == ["0"]
    assert len(t.spans["compress"]) == len(t.spans["decompress"]) > 50
    names = {trace.short_name(o.name) for o in t.ops["0"]}
    assert {"lz_fused_mono_pallas", "lz_decode_mono_pallas"} <= names
    mosaic = {trace.short_name(o.name) for o in t.ops["0"] if o.mosaic}
    assert mosaic == {"lz_fused_mono_pallas", "lz_decode_mono_pallas"}


def test_recorded_trace_is_aligned_to_the_host_clock(recorded):
    t = recorded
    # the device's clock led the host's by about a millisecond
    assert 0.5e-3 < t.shift < trace.LOOKBACK
    for d in ("compress", "decompress"):
        spans = t.spans[d]
        busy = t.busy_seconds(spans)
        assert 0 < busy <= t.span_seconds(d)
    # after the shift, almost all device time lies inside some span
    everything = t.busy_seconds([(t.window[0] - 1, t.window[1] + 1)])
    inside = t.busy_seconds([x for v in t.spans.values() for x in v])
    assert inside / everything > 0.99


def test_recorded_trace_reduces_to_plausible_shares(recorded):
    t = recorded
    ctx = harness.Readings(t, {"compress": 1e6, "decompress": 1e6},
                           peaks.peaks("TPU v5 lite"))
    for name in ("idle_share.compress", "idle_share.decompress",
                 "xla_share.compress", "xla_share.decompress",
                 "hbm_roofline.compress", "hbm_roofline.decompress"):
        v = harness.reader(name)(ctx)
        assert v is not None and 0 <= v <= 100, name
    top = t.top_ops(3)
    assert top[0][0] == "lz_fused_mono_pallas"
    assert all(len(g) == 2 and g[1] > 0 for g in t.idle_gaps(10))
