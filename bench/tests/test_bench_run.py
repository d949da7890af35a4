"""``bench/run.py`` refuses to run where it cannot measure the chip."""
import os
import shutil
import subprocess
import sys

from bench.tests.conftest import ROOT


def _run(cwd_root, *args, platform="cpu"):
    env = dict(os.environ, JAX_PLATFORMS=platform)
    return subprocess.run(
        [sys.executable, os.path.join(cwd_root, "bench", "run.py"), *args],
        cwd=cwd_root, env=env, capture_output=True, text=True, timeout=120)


def _no_result(proc):
    return not any(line.lstrip().startswith("{")
                   for line in proc.stdout.splitlines())


def test_exits_nonzero_with_no_result_off_a_tpu():
    proc = _run(ROOT, "--workload", "graph500-s20.solve", "--seed",
                str(2**31 + 5), "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0 and _no_result(proc)
    assert "TPU" in proc.stderr


def test_exits_nonzero_for_an_unknown_workload():
    proc = _run(ROOT, "--workload", "no-such.cell", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert proc.returncode == 2 and _no_result(proc)


def test_exits_nonzero_without_the_program(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's own
    files has nothing to measure."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(str(tmp_path), "--workload", "graph500-s20.solve", "--seed",
                "1", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0 and _no_result(proc)
