"""bench/run.py refuses what it cannot measure: a CPU, an unknown cell, a
checkout without the program. It prints no result line then."""

import json
import os
import shutil
import subprocess
import sys

import bench_testlib as T

RUN = ["--seed", "2147483649", "--seconds", "1", "--trace", "0"]


def run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def cell():
    return json.loads((T.REPO / "BENCHMARK.json").read_text())["workloads"][0]["name"]


def test_refuses_a_cpu():
    p = run(T.REPO, "--workload", cell(), *RUN)
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_an_unknown_cell():
    p = run(T.REPO, "--workload", "no.such_cell", *RUN)
    assert p.returncode == 2 and p.stdout.strip() == ""


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(T.REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for d in json.loads((T.REPO / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(T.REPO / d, tmp_path / d, ignore=shutil.ignore_patterns("__pycache__"))
    p = run(tmp_path, "--workload", cell(), *RUN)
    assert p.returncode != 0 and p.stdout.strip() == ""
