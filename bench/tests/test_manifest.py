"""``BENCHMARK.json`` keeps to the contract, and every file it names exists."""

import copy
import json
import shutil

import pytest

from bench import harness, manifest


@pytest.fixture(scope="module")
def m():
    return manifest.load()


def test_committed_manifest_validates_and_resolves(m):
    assert m["command"] == ["python3", "bench/run.py"]
    assert m["paths"] == ["bench"]
    for cell in (w["name"] for w in m["workloads"]):
        files = manifest.cell_files(m, cell)
        cfg = files["config"]
        assert cfg["name"] == files["cell"]["config"]
        assert {harness.quantity(x["name"]) for x in files["end_to_end"]} == {
            "compress_GBps", "decompress_GBps", "ratio", "setup_s"}
        for x in files["per_layer"]:
            assert callable(harness.reader(x["name"]))
        # every module a cell names is found by its name alone
        assert callable(manifest.module("generators", cfg["generator"])
                        .time_step)
        assert manifest.module("placements", files["traffic"]["placement"]
                               ).Placement
        assert callable(manifest.module("controls", cfg["control"]["kind"])
                        .make)
        assert callable(manifest.module("checks", files["check"]["check"])
                        .compare)
    for c in m["configs"]:
        cfg = json.loads((manifest.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]


def test_every_name_and_unit_uses_the_allowed_characters(m):
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in m[section]:
            assert manifest.NAME.fullmatch(e["name"]), e["name"]
            if "unit" in e:
                assert manifest.UNIT.fullmatch(e["unit"]), e["unit"]
    for w in m["workloads"]:
        assert manifest.NAME.fullmatch(w["config"])
        assert manifest.NAME.fullmatch(w["traffic"])


@pytest.mark.parametrize("edit, message", [
    (lambda m: m["end_to_end"][0].update(name="compress GB/s"), "valid name"),
    (lambda m: m["end_to_end"][0].update(unit="GB per s"), "unit"),
    (lambda m: m["end_to_end"][0].update(unit="µs"), "unit"),
    (lambda m: m["per_layer"][0].update(name="a,b"), "valid name"),
    (lambda m: m["workloads"][0].update(name="x/y"), "valid name"),
    (lambda m: m["end_to_end"][0].update(bound=0.3), "bound"),
    (lambda m: m["end_to_end"][0].update(bound=0.001), "bound"),
    (lambda m: m["end_to_end"].pop(), "setup_s"),
    (lambda m: m["per_layer"][0].update(moves="nope"), "moves"),
    (lambda m: m["per_layer"][0].update(moves="compress_GBps.host"),
     "unreported"),
    (lambda m: m["per_layer"][0].update(why="extra key"), "keys"),
    (lambda m: m["workloads"][0].update(chips=2), "chips"),
    (lambda m: m.update(run_seconds=52), "run_seconds"),
    (lambda m: m["workloads"][1].update(name=m["workloads"][0]["name"]),
     "duplicate"),
    (lambda m: m["workloads"][1].update(config=m["workloads"][0]["config"],
                                        traffic=m["workloads"][0]["traffic"]),
     "repeat"),
    (lambda m: m["configs"][0].update(why="a\nb"), "one line"),
    (lambda m: m.update(paths=["../bench"]), "relative"),
])
def test_manifest_refuses(m, edit, message):
    bad = copy.deepcopy(m)
    edit(bad)
    with pytest.raises(ValueError, match=message):
        manifest.validate(bad)


def test_a_module_is_found_by_its_file_name_alone(tmp_path):
    (tmp_path / "bench" / "generators").mkdir(parents=True)
    (tmp_path / "bench" / "generators" / "new-data.py").write_text(
        "def time_step(cfg, seed):\n    return (seed,)\n")
    mod = manifest.module("generators", "new-data", root=tmp_path)
    assert mod.time_step({}, 7) == (7,)
    with pytest.raises(FileNotFoundError):
        manifest.module("generators", "absent", root=tmp_path)


def test_a_cell_file_that_names_no_check_is_refused(tmp_path):
    shutil.copy(manifest.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(manifest.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = tmp_path / "bench" / "workloads" / "quant-u16.resident.json"
    spec = json.loads(path.read_text())
    del spec["check"]
    path.write_text(json.dumps(spec))
    m = manifest.load(tmp_path)
    with pytest.raises(ValueError, match="quant-u16.resident.json"):
        manifest.cell_files(m, "quant-u16.resident", root=tmp_path)
    manifest.cell_files(m, "field-f32.resident", root=tmp_path)
