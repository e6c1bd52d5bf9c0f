"""Load `BENCHMARK.json` and the per-name files it points to, and validate both.

A cell is resolved from names alone: `workloads/<cell>.json` names its
configuration and traffic mix, found as `configs/<config>.json` and
`traffic/<traffic>.json`; each per-layer metric is read by
`metrics/<metric>.py`. Adding a cell, a mix or a metric is adding files and
manifest entries; no code here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = Path(__file__).resolve().parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES_E2E = {"host_clock", "device_trace"}
SOURCES = SOURCES_E2E | {"program_span", "program_counter"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}


class ManifestError(ValueError):
    pass


@dataclasses.dataclass
class Cell:
    """One workload of the manifest with every file it names, loaded."""

    name: str
    chips: int
    config: dict  # configs/<config>.json
    traffic: dict  # traffic/<traffic>.json
    workload: dict  # workloads/<cell>.json
    end_to_end: List[dict]  # the manifest's end-to-end metrics this cell reports
    per_layer: List[dict]  # the manifest's per-layer metrics this cell reports
    bench_dir: Path

    def reader(self, metric: str) -> Callable:
        """`read(ctx)` of `metrics/<metric>.py`, loaded by path."""
        path = self.bench_dir / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
        if spec is None or spec.loader is None:
            raise ManifestError(f"no reader for metric {metric!r} at {path}")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise ManifestError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise ManifestError(msg)


def _one_line(text, what: str) -> None:
    _check(isinstance(text, str) and 1 <= len(text) <= 200
           and "\n" not in text and "\t" not in text,
           f"{what}: 1 to 200 characters on one line, no tab: {text!r}")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def validate(manifest: dict, bench_dir: Path = BENCH_DIR) -> None:
    """Raise ManifestError on anything outside the manifest's rules."""
    _check(set(manifest) == TOP_KEYS, f"top-level keys {sorted(manifest)} != {sorted(TOP_KEYS)}")
    cmd = manifest["command"]
    _check(isinstance(cmd, list) and 1 <= len(cmd) <= 32, "command: 1 to 32 strings")
    for word in cmd:
        _one_line(word, "command word")
        _check(not word.startswith("/") and ".." not in word.split("/"),
               f"command word leaves the repo: {word}")
    paths = manifest["paths"]
    _check(isinstance(paths, list) and 1 <= len(paths) <= 16, "paths: 1 to 16 entries")
    for p in paths:
        _check(re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) is not None
               and not p.startswith("/") and ".." not in p.split("/"), f"bad path {p!r}")
    rs = manifest["run_seconds"]
    _check(isinstance(rs, int) and 1 <= rs <= 51, "run_seconds: a whole number 1..51")

    def _name(n, what):
        _check(isinstance(n, str) and NAME_RE.fullmatch(n) is not None, f"{what}: bad name {n!r}")

    configs = {}
    _check(1 <= len(manifest["configs"]) <= 24, "configs: 1 to 24")
    for c in manifest["configs"]:
        _check(set(c) == CONFIG_KEYS, f"config keys {sorted(c)}")
        _name(c["name"], "config")
        _check(c["name"] not in configs, f"duplicate config {c['name']}")
        _one_line(c["source"], "config source")
        _one_line(c["why"], "config why")
        _check(isinstance(c["reduced"], list) and len(c["reduced"]) <= 16, "reduced: at most 16")
        for key in c["reduced"]:
            _name(key, "reduced key")
        _check(c["file"] == f"{bench_dir.name}/configs/{c['name']}.json",
               f"config file of {c['name']} must be configs/<name>.json under paths")
        configs[c["name"]] = c
    files = [c["file"] for c in manifest["configs"]]
    _check(len(set(files)) == len(files), "two configurations share a file")

    cells = set()
    pairs = set()
    _check(1 <= len(manifest["workloads"]) <= 24, "workloads: 1 to 24")
    four = 0
    for w in manifest["workloads"]:
        _check(set(w) == WORKLOAD_KEYS, f"workload keys {sorted(w)}")
        _name(w["name"], "workload")
        _name(w["traffic"], "traffic")
        _check(w["name"] not in cells, f"duplicate workload {w['name']}")
        _check(w["config"] in configs, f"workload {w['name']}: unknown config {w['config']}")
        _check(w["chips"] in (1, 4), f"workload {w['name']}: chips must be 1 or 4")
        _check((w["config"], w["traffic"]) not in pairs, f"pair repeated: {w['name']}")
        _one_line(w["why"], "workload why")
        four += w["chips"] == 4
        cells.add(w["name"])
        pairs.add((w["config"], w["traffic"]))
    _check(four <= max(1, len(cells) // 2), "too many four-chip cells")
    used = {w["config"] for w in manifest["workloads"]}
    _check(used == set(configs), f"configs used by no cell: {sorted(set(configs) - used)}")

    e2e = {}
    _check(1 <= len(manifest["end_to_end"]) <= 16, "end_to_end: 1 to 16")
    for m in manifest["end_to_end"]:
        _check(set(m) - {"workloads"} == E2E_KEYS, f"end_to_end keys {sorted(m)}")
        _name(m["name"], "metric")
        _check(UNIT_RE.fullmatch(m["unit"]) is not None, f"bad unit {m['unit']!r}")
        _check(m["better"] in ("lower", "higher"), "better: lower or higher")
        _check(m["source"] in SOURCES_E2E, f"end-to-end source {m['source']}")
        _check(isinstance(m["bound"], (int, float)) and 0.01 <= m["bound"] <= 0.25,
               f"bound of {m['name']} outside [0.01, 0.25]")
        for c in m.get("workloads", []):
            _check(c in cells, f"{m['name']}: unknown workload {c}")
        e2e[m["name"]] = m
    _check("setup_s" in e2e and "workloads" not in e2e["setup_s"], "setup_s in every cell")

    _check(1 <= len(manifest["per_layer"]) <= 128, "per_layer: 1 to 128")
    for m in manifest["per_layer"]:
        _check(set(m) - {"workloads"} == LAYER_KEYS, f"per_layer keys {sorted(m)}")
        _name(m["name"], "metric")
        _check(UNIT_RE.fullmatch(m["unit"]) is not None, f"bad unit {m['unit']!r}")
        _check(m["better"] in ("lower", "higher"), "better: lower or higher")
        _check(m["source"] in SOURCES, f"per-layer source {m['source']}")
        _one_line(m["layer"], "layer")
        _check(m["moves"] in e2e, f"{m['name']} moves unknown metric {m['moves']}")
        for c in m.get("workloads", sorted(cells)):
            _check(c in cells, f"{m['name']}: unknown workload {c}")
            _check(_applies(e2e[m["moves"]], c),
                   f"{m['name']}: cell {c} does not report {m['moves']}")
    metric_names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    _check(len(set(metric_names)) == len(metric_names), "duplicate metric names")

    for cell in cells:
        reported = [m for m in manifest["end_to_end"] if _applies(m, cell)]
        _check(len(reported) >= 2, f"{cell}: needs setup_s and another end-to-end metric")
        _check(any(_applies(m, cell) for m in manifest["per_layer"]),
               f"{cell}: reports no per-layer metric")


def load(root: Path = ROOT, bench_dir: Optional[Path] = None) -> dict:
    """The validated manifest at `root/BENCHMARK.json`."""
    manifest = _load_json(root / "BENCHMARK.json")
    validate(manifest, bench_dir or root / "bench")
    return manifest


def cell(manifest: dict, name: str, bench_dir: Path = BENCH_DIR) -> Cell:
    """Resolve one workload of the manifest to its files."""
    by_name: Dict[str, dict] = {w["name"]: w for w in manifest["workloads"]}
    if name not in by_name:
        raise ManifestError(f"unknown workload {name!r} (have {sorted(by_name)})")
    w = by_name[name]
    workload = _load_json(bench_dir / "workloads" / f"{name}.json")
    _check(workload.get("config") == w["config"] and workload.get("traffic") == w["traffic"],
           f"workloads/{name}.json disagrees with the manifest on config or traffic")
    per_layer = [m for m in manifest["per_layer"] if _applies(m, name)]
    for m in per_layer:
        _check((bench_dir / "metrics" / f"{m['name']}.py").is_file(),
               f"no reader metrics/{m['name']}.py")
    return Cell(
        name=name,
        chips=w["chips"],
        config=_load_json(bench_dir / "configs" / f"{w['config']}.json"),
        traffic=_load_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        workload=workload,
        end_to_end=[m for m in manifest["end_to_end"] if _applies(m, name)],
        per_layer=per_layer,
        bench_dir=bench_dir,
    )
