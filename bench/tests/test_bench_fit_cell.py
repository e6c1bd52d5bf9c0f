"""The OATS-S1 cell at 16,464 tools: its manifest entries, its configuration as
the program and the harness read it, and the readers of its fit spans."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import manifest  # noqa: E402
from bench.cell import Context  # noqa: E402

CELL = "toolbench-16464.overload"
READERS = {  # metric -> the fit_phase_ms phases it sums
    "fit_refine_ms.toolbench-16464": ("refine", "gate"),
    "fit_grow_ms.toolbench-16464": ("grow",),
}


@pytest.fixture(scope="module")
def cell():
    return manifest.cell(manifest.load(ROOT), CELL)


def test_the_cell_validates_and_reports_capacity_setup_and_the_fit(cell):
    assert cell.chips == 1 and cell.config["name"] == "toolbench-16464"
    assert cell.traffic == json.loads((ROOT / "bench/traffic/overload.json").read_text())
    assert cell.workload["max_batch"] == 64 and cell.workload["offered_rate_per_s"] > 0
    assert sorted(m["name"] for m in cell.end_to_end) == ["routes_per_s", "setup_s"]
    assert sorted(m["name"] for m in cell.per_layer) == sorted(READERS)
    for m in cell.per_layer:
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "ms", "lower", "program_span", "setup_s")


def test_the_configuration_states_the_programs_own_fit_and_growth(cell):
    """What `bench/cell.py` reads, and the table spec the reference rebuilds,
    are the program's defaults for stage oats-s1: no option steers the fit."""
    import inspect

    from repro.core.pipeline import STAGE_PRESETS, PipelineConfig
    from repro.core.refine import RefineConfig
    from repro.data.benchmarks import scale_tool_corpus

    c = cell.config
    assert (c["builder"], c["stage"], c["k"]) == ("make_toolbench_like", "oats-s1", 5)
    assert c["num_tools"] == c["tools"] == 16464 and c["embedding_dim"] == 384
    assert c["reduced"] == [] and c["fit_precision"] == "highest"
    assert "refine" in STAGE_PRESETS[c["stage"]]
    refine, pipe = RefineConfig(), PipelineConfig()
    noise = inspect.signature(scale_tool_corpus).parameters["noise"].default
    assert c["table"] == {
        "alpha": refine.alpha, "beta": refine.beta, "iterations": refine.iterations,
        "momentum": refine.momentum, "gate_val_frac": pipe.gate_val_frac,
        "split_seed": pipe.seed, "registry_noise": noise,
    }
    assert refine.k == c["k"] and refine.positives == "ground_truth"


@pytest.mark.parametrize("metric", sorted(READERS))
def test_fit_readers_sum_their_phases_and_read_nothing_from_an_empty_registry(
        cell, metric, monkeypatch):
    from repro.obs import metrics

    read = cell.reader(metric)
    ctx = Context(cell, {}, None, [], None)
    monkeypatch.setattr(metrics, "_DEFAULT", metrics.MetricsRegistry())
    assert read(ctx) is None
    reg = metrics.get_registry()
    reg.histogram("fit_phase_ms", phase="other").record(1000.0)  # another phase: not read
    assert read(ctx) is None
    want = 0.0
    for i, phase in enumerate(READERS[metric]):
        for ms in (12.5 + i, 3.25):
            reg.histogram("fit_phase_ms", phase=phase).record(ms)
            want += ms
    assert read(ctx) == pytest.approx(want, rel=1e-12)
