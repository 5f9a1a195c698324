"""``BENCHMARK.json``: loading, validation, and the files a cell is made of."""

from __future__ import annotations

import importlib.util
import json
import pathlib
import re

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
PATH = re.compile(r"[A-Za-z0-9_.\-/]{1,200}")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
KEYS = {
    "configs": ({"name", "source", "file", "reduced", "why"}, set()),
    "workloads": ({"name", "config", "traffic", "chips", "why"}, set()),
    "end_to_end": ({"name", "unit", "better", "bound", "source"},
                   {"workloads"}),
    "per_layer": ({"name", "unit", "better", "source", "layer", "moves"},
                  {"workloads"}),
}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
E2E_SOURCES = {"device_trace", "host_clock"}


def _text(value, what) -> None:
    if not (isinstance(value, str) and 1 <= len(value) <= 200
            and "\n" not in value and "\t" not in value):
        raise ValueError(f"{what}: 1 to 200 characters on one line, no tab")


def _name(value, what) -> None:
    if not (isinstance(value, str) and NAME.fullmatch(value)):
        raise ValueError(f"{what}: {value!r} is not a valid name")


def validate(m: dict) -> None:
    """Raise ``ValueError`` where ``m`` breaks the benchmark's contract."""
    if set(m) != TOP_KEYS:
        raise ValueError(f"top-level keys {sorted(m)} != {sorted(TOP_KEYS)}")
    cmd, paths = m["command"], m["paths"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32):
        raise ValueError("command: a list of 1 to 32 strings")
    for word in cmd:
        _text(word, "command word")
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        raise ValueError("paths: 1 to 16 directories")
    for p in paths:
        if not (isinstance(p, str) and PATH.fullmatch(p)) or p.startswith("/") \
                or ".." in p.split("/"):
            raise ValueError(f"path {p!r} is not a relative path in the repo")
    rs = m["run_seconds"]
    if not (isinstance(rs, int) and not isinstance(rs, bool) and 1 <= rs <= 51):
        raise ValueError("run_seconds: a whole number from 1 to 51")
    seen = set()
    for section, (need, optional) in KEYS.items():
        entries = m[section]
        limit = {"configs": 24, "workloads": 24, "end_to_end": 16,
                 "per_layer": 128}[section]
        if not (isinstance(entries, list) and 1 <= len(entries) <= limit):
            raise ValueError(f"{section}: 1 to {limit} entries")
        for e in entries:
            if not (need <= set(e) <= need | optional):
                raise ValueError(f"{section} entry keys {sorted(e)}")
            _name(e["name"], f"{section} name")
            if (section, e["name"]) in seen or (
                section in ("end_to_end", "per_layer")
                and any(n == e["name"] for s, n in seen
                        if s in ("end_to_end", "per_layer"))
            ):
                raise ValueError(f"{section}: duplicate name {e['name']!r}")
            seen.add((section, e["name"]))
            if "unit" in e and not (isinstance(e["unit"], str)
                                    and UNIT.fullmatch(e["unit"])):
                raise ValueError(f"{e['name']}: unit {e['unit']!r}")
            if "better" in e and e["better"] not in ("lower", "higher"):
                raise ValueError(f"{e['name']}: better is lower or higher")
            for key in ("why", "layer", "source"):
                if key in e:
                    _text(e[key], f"{e['name']}.{key}")
    configs = {c["name"]: c for c in m["configs"]}
    cells = {w["name"]: w for w in m["workloads"]}
    e2e = {x["name"]: x for x in m["end_to_end"]}
    for c in configs.values():
        if not (isinstance(c["reduced"], list) and len(c["reduced"]) <= 16):
            raise ValueError(f"{c['name']}: reduced is a list of at most 16")
        for key in c["reduced"]:
            _name(key, f"{c['name']}.reduced")
        if not any(c["file"].startswith(p.rstrip("/") + "/") for p in paths):
            raise ValueError(f"{c['name']}: file outside paths")
        if not any(w["config"] == c["name"] for w in cells.values()):
            raise ValueError(f"config {c['name']!r} is used by no cell")
    if len({c["file"] for c in configs.values()}) != len(configs):
        raise ValueError("two configurations share a file")
    pairs = set()
    for w in cells.values():
        _name(w["config"], "cell config")
        _name(w["traffic"], "cell traffic")
        if w["config"] not in configs:
            raise ValueError(f"{w['name']}: unknown config {w['config']!r}")
        if w["chips"] not in (1, 4):
            raise ValueError(f"{w['name']}: chips is 1 or 4")
        if (w["config"], w["traffic"]) in pairs:
            raise ValueError(f"{w['name']}: config and traffic repeat a cell")
        pairs.add((w["config"], w["traffic"]))
    if sum(w["chips"] == 4 for w in cells.values()) > max(1, len(cells) // 2):
        raise ValueError("too many four-chip cells")
    if "setup_s" not in e2e:
        raise ValueError("end_to_end has no setup_s")
    for x in m["end_to_end"]:
        b = x["bound"]
        if not (isinstance(b, (int, float)) and 0.01 <= b <= 0.25):
            raise ValueError(f"{x['name']}: bound in [0.01, 0.25]")
        if x["source"] not in E2E_SOURCES:
            raise ValueError(f"{x['name']}: end-to-end source {x['source']!r}")
    for x in m["per_layer"]:
        if x["source"] not in SOURCES:
            raise ValueError(f"{x['name']}: source {x['source']!r}")
        if x["moves"] not in e2e:
            raise ValueError(f"{x['name']}: moves unknown {x['moves']!r}")
    for x in m["end_to_end"] + m["per_layer"]:
        for cell in x.get("workloads", []):
            if cell not in cells:
                raise ValueError(f"{x['name']}: unknown cell {cell!r}")
    for cell in cells:
        e = [x["name"] for x in end_to_end(m, cell)]
        if "setup_s" not in e or len(e) < 2:
            raise ValueError(f"{cell}: needs setup_s and another metric")
        if not per_layer(m, cell):
            raise ValueError(f"{cell}: reports no per-layer metric")
        for x in per_layer(m, cell):
            if x["moves"] not in e:
                raise ValueError(f"{cell}: {x['name']} moves an unreported "
                                 f"{x['moves']}")


def _applies(x: dict, cell: str) -> bool:
    return "workloads" not in x or cell in x["workloads"]


def end_to_end(m: dict, cell: str) -> list:
    return [x for x in m["end_to_end"] if _applies(x, cell)]


def per_layer(m: dict, cell: str) -> list:
    return [x for x in m["per_layer"] if _applies(x, cell)]


def load(root: pathlib.Path = ROOT) -> dict:
    m = json.loads((root / "BENCHMARK.json").read_text())
    validate(m)
    return m


def _json(path: pathlib.Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise FileNotFoundError(f"the benchmark has no {path}") from None


_MODULES: dict = {}


def module(kind: str, name: str, root: pathlib.Path = ROOT):
    """``bench/<kind>/<name>.py``, loaded once and found by name alone: a
    configuration's generator, a traffic mix's placement, a configuration's
    control, a cell's check or a per-layer metric's reader."""
    path = root / "bench" / kind / f"{name}.py"
    if path not in _MODULES:
        if not path.exists():
            raise FileNotFoundError(f"the benchmark has no {path}")
        spec = importlib.util.spec_from_file_location(
            f"bench:{kind}/{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def cell_files(m: dict, cell: str, root: pathlib.Path = ROOT) -> dict:
    """Everything one cell is made of, read from its files.

    The cell's ``bench/workloads/<cell>.json`` names its check
    (``bench/checks/<check>.py``) under ``check``; a file without one is
    refused, so no cell is held to a guarantee it did not name.
    """
    cells = {w["name"]: w for w in m["workloads"]}
    if cell not in cells:
        raise KeyError(f"no cell {cell!r}; cells: {sorted(cells)}")
    w = cells[cell]
    config = next(c for c in m["configs"] if c["name"] == w["config"])
    check_file = root / "bench" / "workloads" / f"{cell}.json"
    check = _json(check_file)
    if "check" not in check:
        raise ValueError(f"{check_file} names no check (bench/checks/<name>.py)")
    _name(check["check"], f"{check_file} check")
    return dict(
        cell=w,
        config=_json(root / config["file"]),
        traffic=_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        check=check,
        end_to_end=end_to_end(m, cell),
        per_layer=per_layer(m, cell),
    )
