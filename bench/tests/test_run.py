"""A run end to end on the CPU at a small size, through the same code as on
the chip, and with the timed path broken underneath it.

``run.run(require_tpu=False)`` skips the harness's look for a chip; the
rest is the run as it goes on the chip: draw, upsert, build, warm, serve
over HTTP to client processes, window, reference, comparison.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import run, spec
from bench.tests import faults

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]
# one cell per configuration, and the filter path
REHEARSED = ["flat1m-search", "hnsw100k-search", "hnsw100k-f99p"]


def run_cell(cell, capsys, trace=0, seconds=2):
    rc = run.run(["--workload", cell, "--seed", str(2**33 + 17),
                  "--seconds", str(seconds), "--trace", str(trace)],
                 require_tpu=False)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("cell", REHEARSED)
def test_rehearsal(cell, small, capsys):
    result = run_cell(cell, capsys)
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    names = {m["name"] for m in spec.metrics_for(
        spec.benchmark(), cell, "end_to_end")}
    assert set(result["metrics"]) == names
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_traced_rehearsal_reports_per_layer_metrics(small, capsys):
    result = run_cell("flat1m-search", capsys, trace=1, seconds=3)
    assert result["correct"] is True
    assert result["metrics"]["batcher.occupancy"]["value"] >= 1
    assert set(result["device"]) >= {"busy_s", "window_s"}
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


TRAVERSAL_CELLS = [c for c in CELLS
                   if "recall_short" in spec.load_json("limits", c)["limits"]]


@pytest.mark.parametrize("fault", faults.ANY_CELL)
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault, small, capsys,
                                          monkeypatch):
    faults.plant(monkeypatch, fault)
    result = run_cell(cell, capsys)
    assert result["correct"] is False, result["checks"]


@pytest.mark.parametrize("fault", faults.TRAVERSAL)
@pytest.mark.parametrize("cell", TRAVERSAL_CELLS)
def test_broken_traversal_is_not_correct(cell, fault, small, capsys,
                                         monkeypatch):
    """A traversal that ends early returns valid rows with exact scores,
    which the recall floor fails (where it also returns too few rows,
    ``bad_rows`` fails it too)."""
    faults.plant(monkeypatch, fault)
    result = run_cell(cell, capsys)
    checks = result["checks"]
    assert result["correct"] is False, checks
    assert checks["recall_short"]["value"] > checks["recall_short"]["limit"]


def test_traversal_cells_exist():
    assert "hnsw100k-search" in TRAVERSAL_CELLS


def test_no_tpu_no_result():
    """The measuring command refuses to run without a TPU: non-zero exit,
    nothing on standard output."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(spec.BENCH / "run.py"), "--workload",
         "flat1m-search", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_benchmark_files_alone_give_no_result(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files (no
    program) exits non-zero with no result."""
    import shutil

    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "flat1m-search",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(os.environ), capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
