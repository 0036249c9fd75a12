"""The harness refuses to run where it cannot measure, on the CPU."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench import run as bench_run

ROOT = pathlib.Path(__file__).resolve().parents[2]


def run_py(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "celeba-bulk",
         "--seed", str(2**40 + 1), "--seconds", "1", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_tpu_exits_nonzero_without_a_result():
    p = run_py(ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "needs 1 TPU chip" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_py(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""


def test_every_cell_finds_its_files():
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    for cell in bm["workloads"]:
        spec = bench_run.Spec(cell["name"])
        assert spec.end_to_end and spec.per_layer
        assert "setup_s" in {m["name"] for m in spec.end_to_end}
        for m in spec.end_to_end + spec.per_layer:
            assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        for kind, key in (("systems", "system"),
                          ("references", "reference")):
            assert (ROOT / "bench" / kind / f"{spec.cfg[key]}.py").is_file()


def test_unknown_workload_is_refused():
    with pytest.raises(bench_run.BenchError, match="unknown workload"):
        bench_run.Spec("no-such-cell")
