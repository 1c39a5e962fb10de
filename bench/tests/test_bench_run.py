"""`bench/run.py` refuses to run without a TPU or without the program, and
then prints no result."""
import os
import subprocess
import sys

import pytest

import bench_testkit

ARGS = ["--workload", "road-solve", "--seed", str(2**31 + 11), "--seconds", "1",
        "--trace", "0"]


def _run(cwd, args=ARGS):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=120)


def test_off_tpu_it_exits_nonzero_with_no_result():
    p = _run(bench_testkit.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_without_the_program_it_exits_nonzero_with_no_result(tmp_path):
    root = bench_testkit.toy_copy(tmp_path, toy=False)
    p = _run(root)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("args", [
    ["--workload", "no-such-cell", "--seed", "1", "--seconds", "1"],
    ["--workload", "road-solve", "--seed", "1", "--seconds", "1", "--trace", "2"],
])
def test_bad_arguments_exit_nonzero(args):
    p = _run(bench_testkit.ROOT, args)
    assert p.returncode != 0 and p.stdout.strip() == ""
