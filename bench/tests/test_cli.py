"""The command refuses to run without a TPU, and without the program."""
import shutil
import subprocess
import sys

from cells import BENCH, ROOT


def test_no_tpu_exits_2_without_a_result():
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "stablelm-1.6b.chat-shared", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, cwd=ROOT,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=300)
    assert p.returncode == 2, p.stderr
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_bench_alone_fails_without_a_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "stablelm-1.6b.chat-shared", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, cwd=tmp_path,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
