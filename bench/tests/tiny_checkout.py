"""A temporary checkout of the benchmark with a tiny cell, for the tests.

The checkout holds a copy of ``bench/`` and a link to the program's ``src/``
beside a ``BENCHMARK.json`` of one cell, whose configuration, traffic mix
and per-layer metric are new files.  Runs happen in a child process on the
CPU, where the test, and only the test, replaces the harness's claim of a
TPU (``CHILD``).
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY = {
    "num_rows": 2048, "d_sparse": 3000, "alpha": 1.5, "nnz_per_row": 20,
    "d_dense": 64, "dense_rank": 16, "dense_weight": 2.0,
    "index": {"keep_top": 64, "head_dims": 16, "kmeans_iters": 6,
              "backend": "pallas-packed"},
    "search": {"h": 10, "alpha": 4, "beta": 2},
    "buckets": [1, 8], "sample": 64,
    # on the CPU over 4 seeds: score_err_rms, program 4.70e-4..4.86e-4,
    # the control (int4 dense side) 1.69e-2..1.76e-2; recall_miss, program
    # 0.014..0.025, pass 1 over half the rows 0.48..0.51, another query's
    # candidates 0.90..0.95
    "limits": {"score_err_rms": 2e-3, "recall_miss": 0.15, "malformed": 0,
               "failed": 0},
}

# the child process: the TPU requirement lifted, then ``PLANT`` (code that
# breaks the timed path, or nothing), then the script named by ``SCRIPT``
CHILD = """
import os, sys, jax
sys.path[:0] = ["bench", "src"]
import run
run.claim_devices = lambda chips: jax.devices("cpu")[:chips]
exec(os.environ.get("PLANT", ""))
import importlib
script = importlib.import_module(os.environ.get("SCRIPT", "run"))
sys.exit(script.main(sys.argv[1:]))
"""

METRIC = '''
def read(run):
    return float(run.batch)
'''


def make_checkout(tmp_path, cell: str, traffic: dict,
                  metric_src: str = METRIC) -> "os.PathLike":
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(REPO, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(REPO, "src"), root / "src")
    (root / "bench" / "configs" / "tiny-cfg.json").write_text(
        json.dumps(TINY))
    (root / "bench" / "traffic" / "tiny-mix.json").write_text(
        json.dumps(traffic))
    (root / "bench" / "metrics" / "served_batches.tiny.py").write_text(
        metric_src)
    bench = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": "tiny-cfg", "source": "test",
                     "file": "bench/configs/tiny-cfg.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": cell, "config": "tiny-cfg",
                       "traffic": "tiny-mix", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "qps", "unit": "queries/s", "better": "higher",
             "bound": 0.05, "source": "host_clock",
             "workloads": [cell] if traffic["loop"] == "closed" else []},
            {"name": "latency_p95_ms", "unit": "ms", "better": "lower",
             "bound": 0.05, "source": "host_clock",
             "workloads": [cell] if traffic["loop"] == "open" else []},
            {"name": "recall_at_10", "unit": "ratio", "better": "higher",
             "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "served_batches.tiny", "unit": "count",
             "better": "higher", "source": "host_clock", "layer": "test",
             "moves": "qps", "workloads": [cell]},
            {"name": "build_s", "unit": "s", "better": "lower",
             "source": "host_clock", "layer": "build", "moves": "setup_s"}],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run_child(root, args, *, plant: str = "", script: str = "run",
              check: bool = True):
    """Run ``script``'s main with ``args`` in the checkout, on the CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PLANT=plant, SCRIPT=script,
               JAX_COMPILATION_CACHE_DIR=str(root / ".jax_cache"))
    proc = subprocess.run([sys.executable, "-c", CHILD, *args], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    if check:
        assert proc.returncode == 0, proc.stderr[-4000:]
    return proc


def run_cell(root, cell: str, trace: int = 0, seconds: str = "1",
             plant: str = ""):
    """One run of the cell; (its result line, its standard error)."""
    proc = run_child(root, ["--workload", cell, "--seed", "3000000007",
                            "--seconds", seconds, "--trace", str(trace)],
                     plant=plant)
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr
