"""The command refuses to run off the chip, and without the program."""
import json
import os
import shutil
import subprocess
import sys

from bench import run

CELL = "paper-fmnist.sync-h50"


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed",
         "3000000000", "--seconds", "10", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_exits_nonzero_with_no_result_on_the_cpu():
    p = _run(run.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_every_workload_has_its_files():
    spec = run.load_json(run.ROOT, "BENCHMARK.json")
    for w in spec["workloads"]:
        _, _, cell, _ = run.cell_spec(w["name"])
        assert os.path.exists(os.path.join(
            run.BENCH, "drivers", f"{cell['driver']}.py"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert os.path.exists(os.path.join(run.BENCH, "metrics",
                                           f"{m['name']}.py"))
    json.dumps(spec)
