"""A configuration, a traffic mix and a metric are found from files alone."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from bench import catalog

REPO = Path(__file__).resolve().parents[2]


def _write(path: Path, text: str) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text)


@pytest.fixture()
def root(tmp_path):
    """A checkout whose benchmark has one new cell: a configuration, a
    traffic mix and two metric readers that no code names."""
    _write(tmp_path / "bench" / "configs" / "tiny.json", json.dumps({
        "name": "tiny", "corpus": {"n_docs": 64}, "fit_sample": 8,
        "index": {"method": "pca_int8", "dim": 16, "post": False},
        "reference": {"recipe": "pca_affine", "dim": 16,
                      "levels": 255},
        "check": {"lost": 0, "score_err": 0.5}}))
    _write(tmp_path / "bench" / "traffic" / "burst.8.json", json.dumps({
        "loop": "open", "rows": 8, "k": 5, "pool": 32, "rate": 7.0}))
    _write(tmp_path / "bench" / "metrics" / "rows_seen.py",
           "def read(run):\n    return sum(len(r) for r in run.requests)\n")
    _write(tmp_path / "bench" / "metrics" / "never.here.py",
           "def read(run):\n    return None\n")
    _write(tmp_path / "BENCHMARK.json", json.dumps({
        "configs": [{"name": "tiny", "file": "bench/configs/tiny.json"}],
        "workloads": [{"name": "tiny.burst", "config": "tiny",
                       "traffic": "burst.8", "chips": 1}],
        "end_to_end": [{"name": "rows_seen", "unit": "rows"}],
        "per_layer": [{"name": "never.here", "unit": "%",
                       "workloads": ["tiny.burst"]},
                      {"name": "elsewhere", "unit": "ms",
                       "workloads": ["other.cell"]}]}))
    return tmp_path


def test_a_new_cell_is_found_by_its_names(root):
    cell = catalog.load_cell("tiny.burst", root)
    assert cell.config["index"]["dim"] == 16
    assert cell.traffic.rows == 8 and cell.traffic.rate == 7.0
    assert [m["name"] for m in cell.end_to_end] == ["rows_seen"]
    assert [m["name"] for m in cell.per_layer] == ["never.here"]


def test_metric_readers_are_loaded_by_name_and_silent_ones_left_out(root):
    class FakeRun:
        requests = [[1, 2], [3]]
    cell = catalog.load_cell("tiny.burst", root)
    assert catalog.read_metrics(cell.end_to_end, FakeRun(), root) == {
        "rows_seen": {"value": 3.0, "unit": "rows"}}
    assert catalog.read_metrics(cell.per_layer, FakeRun(), root) == {}


def test_an_unknown_cell_and_a_missing_reader_are_errors(root):
    with pytest.raises(KeyError):
        catalog.load_cell("nope", root)
    with pytest.raises(FileNotFoundError):
        catalog.metric_reader("elsewhere", root)


def test_every_metric_of_the_benchmark_has_a_reader_and_every_cell_loads():
    bench = catalog.load_benchmark(REPO)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(catalog.metric_reader(m["name"], REPO))
    for w in bench["workloads"]:
        cell = catalog.load_cell(w["name"], REPO)
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in [m["name"] for m in cell.end_to_end]


def test_without_an_accelerator_a_run_exits_nonzero_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench" / "run.py"), "--workload",
         "dpr2m-int8.poisson", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, cwd=REPO)
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert "{" not in proc.stdout
