"""Whole runs at a tiny field size on the CPU, the Pallas kernels in
interpret mode: each traffic kind end to end, the controls, and the timed
path broken underneath, which the comparison must catch."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import codec, harness, manifest, readings

TINY = dict(field_shape=[2, 16, 128], fields_per_step=3,
            backend="fused-mono", decoder="fused-mono")
CELLS = ["quant-u16.resident", "field-f32.resident", "quant-u16.host",
         "field-f32.host"]
SEED = 2**33 + 5


def run(cell, trace=False, seconds=0.2):
    return harness.run_cell(cell, SEED, seconds, trace, require_chip=False,
                            config_overrides=TINY)


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_runs_end_to_end_and_is_correct(cell):
    r = run(cell)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 3
    suffix = ".host" if cell.endswith(".host") else ""
    assert set(r["metrics"]) == {"compress_GBps" + suffix,
                                 "decompress_GBps" + suffix,
                                 "ratio", "setup_s"}
    assert list(r)[-1] == "checks"
    assert all(v["value"] == 0 for v in r["checks"].values())


def test_a_traced_run_is_correct_and_reads_no_device_number_on_cpu():
    r = run("quant-u16.resident", trace=True)
    assert r["correct"]
    assert r["metrics"] == {}  # no TPU plane: every reader returns nothing
    assert r["device"]["busy_s"] == 0 and r["device"]["window_s"] > 0


@pytest.mark.parametrize("cell", ["quant-u16.resident", "field-f32.host"])
def test_the_control_is_not_correct(cell):
    (r,) = readings.readings(cell, [SEED], True, require_chip=False,
                             config_overrides=TINY)
    assert not r["correct"]
    assert any(v["value"] > 0 for v in r["checks"].values())


def test_sound_readings_are_correct():
    (r,) = readings.readings("field-f32.resident", [SEED], False,
                             require_chip=False, config_overrides=TINY)
    assert r["correct"]


def _stale(orig):
    memo = {}

    def f(self, *a):
        if "out" not in memo:
            memo["out"] = orig(self, *a)
        return memo["out"]
    return f


def _half_input(orig):
    def f(self, k):
        field = self.fields[k]
        kept = self.fields[k]
        if isinstance(field, np.ndarray):
            flat = field.reshape(-1).copy()
            flat[flat.size // 2:] = 0
            self.fields[k] = flat.reshape(field.shape)
        else:
            flat = field.reshape(-1)
            self.fields[k] = flat.at[flat.size // 2:].set(0).reshape(
                field.shape)
        try:
            return orig(self, k)
        finally:
            self.fields[k] = kept
    return f


def _flip_container(orig):
    def f(self, k):
        out = orig(self, k)
        if isinstance(out, tuple):  # device: (buffer, total)
            buf, total = out
            i = int(total) - 1
            return buf.at[i].set(buf[i] ^ 1), total
        data = out.data.copy()
        data[-1] ^= 1
        return type(out)(data=data, orig_bytes=out.orig_bytes,
                         total_bytes=out.total_bytes)
    return f


def _flip_answer(orig):
    def f(self, out):
        dec = orig(self, out)
        if isinstance(dec, np.ndarray):
            dec = dec.copy()
            dec.reshape(-1).view(np.uint8)[7] ^= 1
            return dec
        flat = jax.lax.bitcast_convert_type(dec.reshape(-1), jax.numpy.int32) \
            if dec.dtype == jax.numpy.float32 else dec.reshape(-1)
        flat = flat.at[7].set(flat[7] ^ 1)
        if dec.dtype == jax.numpy.float32:
            flat = jax.lax.bitcast_convert_type(flat, jax.numpy.float32)
        return flat.reshape(dec.shape)
    return f


# The faults a codec cell can have.  No cell exchanges data between chips,
# so that fault has no place here.
FAULTS = {
    "decoder returns its state unchanged": ("decompress", _stale),
    "compressor leaves out half the field": ("compress", _half_input),
    "a container byte altered where produced": ("compress", _flip_container),
    "a decoded byte altered where produced": ("decompress", _flip_answer),
}


@pytest.mark.parametrize("cell", ["quant-u16.resident", "field-f32.host"])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    method, make = FAULTS[fault]
    cls = manifest.module("placements", cell.split(".")[1]).Placement
    monkeypatch.setattr(cls, method, make(getattr(cls, method)))
    r = run(cell)
    assert not r["correct"], fault


def test_no_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(manifest.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(manifest.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert not pathlib.Path(tmp_path / "src").exists()


@pytest.mark.parametrize("find", [
    lambda: harness.control(dict(control={"kind": "nope"}), ()),
    lambda: harness.place(dict(placement="nope"), {}, ()),
    lambda: harness.time_step(dict(generator="nope"), 1),
    lambda: harness.reader("nope"),
    lambda: harness.check(None, {}, None, {}, dict(check="nope"), 1),
])
def test_a_name_with_no_file_is_refused(find):
    with pytest.raises(FileNotFoundError, match="nope.py"):
        find()


@pytest.mark.parametrize("dtype", ["uint8", "uint16", "bfloat16", "float32",
                                   "int32"])
def test_any_item_of_one_two_or_four_bytes_round_trips_as_symbols(dtype):
    dtype = jax.numpy.dtype(dtype)
    raw = np.random.default_rng(0).integers(0, 256, 64 * dtype.itemsize,
                                             dtype=np.uint8)
    field = jax.numpy.asarray(raw.view(dtype))
    sym = codec._to_symbols(field)
    assert sym.dtype == jax.numpy.int32
    if dtype.itemsize < 4:  # zero-extended: the codec reads S bytes
        assert 0 <= int(sym.min()) and int(sym.max()) < 1 << 8 * dtype.itemsize
    back = codec._from_symbols(sym, dtype)
    assert back.dtype == dtype
    assert np.asarray(back).tobytes() == raw.tobytes()


def test_result_line_is_json_with_the_contract_keys():
    r = run("quant-u16.resident")
    line = json.loads(json.dumps(r))
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)


PROBE_CHECK = """
import pathlib

from bench import manifest

ROOT = pathlib.Path(__file__).resolve().parents[2]


def compare(placement, kept, originals, cfg, spec, seed):
    checks = manifest.module("checks", "lossless", ROOT).compare(
        placement, kept, originals, cfg, spec, seed)
    n = int(cfg["fields_per_step"])
    checks["fields_not_produced"] = dict(
        value=n - len(kept), limit=spec["limits"]["fields_not_produced"],
        compared=n)
    return checks
"""


def _files(root):
    return {p.relative_to(root): p.read_bytes()
            for p in (root / "bench").rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_cell_with_a_check_of_its_own_is_added_as_new_files(tmp_path):
    """The route a cell with another guarantee takes: a config, a cell file
    and a check, all new, and entries appended to ``BENCHMARK.json``."""
    shutil.copy(manifest.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(manifest.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _files(manifest.ROOT)
    bench = tmp_path / "bench"
    cfg = json.loads((bench / "configs" / "hurr-field-f32.json").read_text())
    cfg["name"] = "hurr-field-f32-probe"
    (bench / "configs" / "hurr-field-f32-probe.json").write_text(
        json.dumps(cfg))
    cell = "field-f32-probe.resident"
    spec = json.loads(
        (bench / "workloads" / "field-f32.resident.json").read_text())
    spec["check"] = "probe"
    spec["limits"]["fields_not_produced"] = 0
    (bench / "workloads" / f"{cell}.json").write_text(json.dumps(spec))
    (bench / "checks" / "probe.py").write_text(PROBE_CHECK)
    m = json.loads((tmp_path / "BENCHMARK.json").read_text())
    original = json.loads(json.dumps(m))
    m["configs"].append(dict(
        next(c for c in m["configs"] if c["name"] == "hurr-field-f32"),
        name=cfg["name"], file="bench/configs/hurr-field-f32-probe.json"))
    m["workloads"].append(dict(
        next(w for w in m["workloads"] if w["name"] == "field-f32.resident"),
        name=cell, config=cfg["name"]))
    for x in m["end_to_end"] + m["per_layer"]:
        if "field-f32.resident" in x.get("workloads", []):
            x["workloads"].append(cell)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))

    r = harness.run_cell(cell, SEED, 0.2, False, require_chip=False,
                         config_overrides=TINY, root=tmp_path)
    assert r["correct"] and r["failed"] == 0
    assert r["checks"]["fields_not_produced"] == {"value": 0, "limit": 0}
    assert set(r["checks"]) == {"roundtrip_mismatch_bytes",
                                "container_mismatch_bytes",
                                "fields_not_produced"}
    assert set(r["metrics"]) == {"compress_GBps", "decompress_GBps",
                                 "ratio", "setup_s"}

    after = _files(tmp_path)
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        pathlib.Path("bench/configs/hurr-field-f32-probe.json"),
        pathlib.Path(f"bench/workloads/{cell}.json"),
        pathlib.Path("bench/checks/probe.py")}
    # BENCHMARK.json changed only by what was appended
    for section in ("configs", "workloads"):
        assert m[section][:-1] == original[section]
    for x, y in zip(m["end_to_end"] + m["per_layer"],
                    original["end_to_end"] + original["per_layer"]):
        if "workloads" in y:
            assert x["workloads"][:len(y["workloads"])] == y["workloads"]
        assert {k: v for k, v in x.items() if k != "workloads"} == \
            {k: v for k, v in y.items() if k != "workloads"}
