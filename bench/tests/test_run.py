"""The command's refusals: no TPU, an unknown cell."""
import os
import subprocess
import sys

from bench.run import ROOT


def run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_without_a_tpu_it_fails_and_prints_no_result():
    p = run("--workload", "school.train", "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_an_unknown_cell_fails_and_prints_no_result():
    p = run("--workload", "nope", "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout == ""
