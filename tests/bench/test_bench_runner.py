"""The runner: cells, metrics and readers found by name from
``BENCHMARK.json``; no run without a TPU; no run from the benchmark's
files alone."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run  # noqa: E402

BENCH = run.load_benchmark()


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_is_found_from_its_files(cell):
    found = run.find_cell(BENCH, cell)
    assert found["cell"]["name"] == cell
    assert found["config"]["name"] == found["cell"]["config"]
    assert os.path.exists(os.path.join(
        ROOT, "bench", "drivers", f"{found['config']['driver']}.py"))
    assert found["mix"]["kind"] in ("open", "closed", "train")
    for trace in (False, True):
        names = [m["name"] for m in run.metrics_for(BENCH, cell, trace)]
        assert names, (cell, trace)
        for name in names:
            assert callable(run.load_reader(name))
    e2e = [m["name"] for m in run.metrics_for(BENCH, cell, False)]
    assert "setup_s" in e2e and len(e2e) >= 2


def test_per_layer_metrics_move_metrics_their_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert "workloads" not in moved or cell in moved["workloads"]


def test_a_new_cell_needs_only_new_files_and_entries(tmp_path):
    """A mix added as a data file, and a cell naming it, are found without
    a change to any file the runner has."""
    mix = json.load(open(os.path.join(ROOT, "bench", "traffic",
                                      "chat-prefix.json")))
    mix["rate_per_s"] = 1.0
    path = os.path.join(ROOT, "bench", "traffic", "_test-mix.json")
    with open(path, "w") as f:
        json.dump(mix, f)
    try:
        bench = json.loads(json.dumps(BENCH))
        bench["workloads"].append({"name": "x.test", "config":
                                   BENCH["configs"][0]["name"],
                                   "traffic": "_test-mix", "chips": 1,
                                   "why": "test"})
        found = run.find_cell(bench, "x.test")
        assert found["mix"]["rate_per_s"] == 1.0
    finally:
        os.remove(path)
    with pytest.raises(KeyError):
        run.find_cell(BENCH, "no.such-cell")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    cell = BENCH["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed",
         "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_to_run_without_a_tpu():
    proc = _run(ROOT)
    assert proc.returncode == 1, proc.stderr[-2000:]
    assert "no TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_fails_with_the_benchmark_files_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(str(tmp_path))
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
