"""The command refuses to report without a TPU, and without the program."""
import os
import shutil
import subprocess
import sys

from chipbench import harness

ARGS = ["-m", "chipbench.run", "--workload", None, "--seed", "2147483999",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    bench = harness.load_benchmark()
    args = list(ARGS)
    args[3] = bench["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    out = _run(harness.REPO)
    assert out.returncode == 2, out.stderr
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_benchmark_files_alone_are_not_enough(tmp_path):
    shutil.copy(harness.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
