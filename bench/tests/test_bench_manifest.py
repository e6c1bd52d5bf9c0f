"""The manifest and the per-name files it points to."""
import copy
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import manifest  # noqa: E402
from bench.cell import Context  # noqa: E402


@pytest.fixture(scope="module")
def doc():
    return manifest.load(ROOT)


def test_manifest_keys_names_and_units(doc):
    assert set(doc) == manifest.TOP_KEYS
    assert doc["command"] == ["python3", "bench/run.py"] and doc["paths"] == ["bench"]
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert manifest.NAME_RE.fullmatch(m["name"]) and manifest.UNIT_RE.fullmatch(m["unit"])
    for w in doc["workloads"]:
        assert manifest.NAME_RE.fullmatch(w["name"]) and w["chips"] == 1
    assert len(json.dumps(doc)) < 64 * 1024


def test_every_cell_finds_its_files_by_name(doc):
    for w in doc["workloads"]:
        c = manifest.cell(doc, w["name"])
        assert c.config["name"] == w["config"]
        assert c.workload["offered_rate_per_s"] > 0 and c.workload["max_batch"] == 64
        for m in c.per_layer:
            assert callable(c.reader(m["name"]))
        assert any(m["name"] == "setup_s" for m in c.end_to_end)
        assert len(c.end_to_end) >= 2 and c.per_layer


def test_config_files_state_what_the_manifest_says(doc):
    for c in doc["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert cfg["embedding_dim"] == 384  # the paper's width, never reduced
        assert (ROOT / "bench" / "references" / f"{cfg['reference']}.py").is_file()
        assert set(cfg["limits"]) == {"topk_err", "table_off_pct"}
        assert (ROOT / "bench" / "references" / f"{cfg['table_reference']}.py").is_file()
        assert "builder_args" not in cfg  # the benchmark is the builder's own, at its own size


@pytest.mark.parametrize("edit,field", [
    (lambda d: d["workloads"][0].update(name="has space"), "name"),
    (lambda d: d["end_to_end"][0].update(unit="ms per route"), "unit"),
    (lambda d: d["end_to_end"][0].update(bound=0.3), "bound"),
    (lambda d: d["per_layer"][0].update(why="x"), "keys"),
    (lambda d: d["per_layer"][0].update(moves="nope"), "moves"),
    (lambda d: d["workloads"][0].update(chips=2), "chips"),
    (lambda d: d.update(run_seconds=60), "run_seconds"),
    (lambda d: d["per_layer"][0].update(workloads=["toolbench-16464.overload"]), "reports"),
])
def test_validation_refuses(doc, edit, field):
    bad = copy.deepcopy(doc)
    edit(bad)
    with pytest.raises(manifest.ManifestError):
        manifest.validate(bad)


def test_a_cell_a_mix_and_a_metric_added_as_files_alone(doc, tmp_path):
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "workloads", "metrics"):
        shutil.copytree(ROOT / "bench" / sub, bench / sub)
    added = copy.deepcopy(doc)
    (bench / "traffic" / "idle.json").write_text(json.dumps(
        {"arrivals": "poisson", "queries": "uniform"}))
    (bench / "workloads" / "toolbench-2413-static.idle.json").write_text(json.dumps(
        {"config": "toolbench-2413-static", "traffic": "idle", "offered_rate_per_s": 100,
         "max_batch": 8}))
    (bench / "metrics" / "embed_share.idle.py").write_text(
        "def read(ctx):\n    return 100.0 * ctx.phase_ms_per_batch('embed') / 4.0\n")
    added["workloads"].append({"name": "toolbench-2413-static.idle",
                               "config": "toolbench-2413-static",
                               "traffic": "idle", "chips": 1, "why": "a test cell"})
    added["end_to_end"][0]["workloads"].append("toolbench-2413-static.idle")
    added["per_layer"].append({"name": "embed_share.idle", "unit": "%", "better": "lower",
                               "source": "program_span", "layer": "embed",
                               "moves": "route_p50_ms", "workloads": ["toolbench-2413-static.idle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(added))
    loaded = manifest.load(tmp_path)
    c = manifest.cell(loaded, "toolbench-2413-static.idle", bench_dir=bench)
    assert c.traffic["queries"] == "uniform" and c.workload["max_batch"] == 8
    assert [m["name"] for m in c.per_layer] == ["embed_share.idle"]
    ctx = Context(c, {"embed": (4, 8.0)}, None, [], None)
    assert c.reader("embed_share.idle")(ctx) == 50.0
    # the cells already there resolve as before
    assert manifest.cell(loaded, "toolbench-2413-static.steady", bench_dir=bench).workload == \
        manifest.cell(doc, "toolbench-2413-static.steady").workload


def test_a_cell_whose_file_is_missing_is_refused(doc, tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench" / "configs", bench / "configs")
    with pytest.raises(manifest.ManifestError):
        manifest.cell(doc, "toolbench-2413-static.steady", bench_dir=bench)
