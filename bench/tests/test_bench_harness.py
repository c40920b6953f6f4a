"""The harness finds a cell made only of new files and runs it end to end.

The cell's configuration, traffic mix and per-layer metric are new files in
a temporary checkout (``tiny_checkout``), none of which the harness has
heard of; no file of ``bench/`` is edited.
"""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

from tiny_checkout import REPO, make_checkout, run_cell, run_child


def test_new_cell_of_new_files_runs_end_to_end(tmp_path):
    cell = "tiny-cfg.tiny-mix"
    root = make_checkout(tmp_path, cell, {"loop": "closed", "clients": 2,
                                          "batch": 8, "pool": 1024})
    out, err = run_cell(root, cell, trace=0)
    assert out["correct"] is True, err[-3000:]
    assert set(out["metrics"]) == {"qps", "recall_at_10", "setup_s"}
    assert out["metrics"]["qps"]["value"] > 0
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
    assert out["checks"]["failed"] == {"value": 0, "limit": 0}
    assert [x.split(":")[0] for x in err.strip().splitlines()[-4:]] == [
        "check score_err_rms", "check recall_miss", "check malformed",
        "check failed"]

    out, err = run_cell(root, cell, trace=1)
    assert out["correct"] is True, err[-3000:]
    assert out["metrics"]["served_batches.tiny"]["value"] == 8.0
    assert out["metrics"]["build_s"]["value"] > 0
    assert "qps" not in out["metrics"]


def test_open_loop_cell_reports_latency(tmp_path):
    cell = "tiny-cfg.tiny-open"
    root = make_checkout(tmp_path, cell, {"loop": "open", "batch": 1,
                                          "rate": 8.0})
    out, err = run_cell(root, cell, trace=0, seconds="2")
    assert out["correct"] is True, err[-3000:]
    assert set(out["metrics"]) == {"latency_p95_ms", "recall_at_10",
                                   "setup_s"}
    assert out["attempted"] == 16
    assert "generator lateness" in err and "p95" in err


# the harness's own claim of a TPU, on a host whose TPU library cannot load
# (so that the test never takes the library's lock from another test)
NO_TPU = """
import sys
sys.modules["libtpu"] = None
sys.path.insert(0, "bench")
import run
sys.exit(run.main(sys.argv[1:]))
"""


def test_no_accelerator_exits_without_result():
    """Without the test's substitute the harness insists on a TPU."""
    proc = subprocess.run(
        [sys.executable, "-c", NO_TPU, "--workload", "querysim-1m.bulk32",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert proc.stdout.strip() == ""


def test_fails_without_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ gives no result."""
    root = tmp_path / "bare"
    shutil.copytree(os.path.join(REPO, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    proc = run_child(root, ["--workload", "querysim-1m.bulk32", "--seed",
                            "1", "--seconds", "1", "--trace", "0"],
                     check=False)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_every_metric_of_the_benchmark_has_a_reader():
    """Each per-layer metric resolves to its own reader file or to the one
    named by its name up to the first ``.``; a reader finds nothing to
    read in a run without a trace or a queue."""
    sys.path.insert(0, os.path.join(REPO, "bench"))
    import json
    import run
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    empty = run.RunData(trace=None, config={}, traffic={}, device_kind="",
                        rows=0, batch=0, build_s=1.0, queue_wait_s=None)
    for m in bench["per_layer"]:
        value = run.load_reader(REPO, m["name"])(empty)
        assert value is None or m["name"] == "build_s", m["name"]


def test_unknown_arrivals_are_refused():
    sys.path.insert(0, os.path.join(REPO, "bench"))
    import pytest
    import run
    assert run.arrival_schedule({"arrivals": "poisson"}) is \
        run.drivers.poisson_schedule
    with pytest.raises(SystemExit, match="bursty"):
        run.arrival_schedule({"arrivals": "bursty"})
