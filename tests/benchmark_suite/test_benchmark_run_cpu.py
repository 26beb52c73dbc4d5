"""``benchmark/run.py`` end to end at toy size on the CPU: it refuses to
measure without a chip, the rehearsal never prints a metric, and a
directory that holds only the benchmark cannot run."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]
    if w["chips"] == 1]


def run(args, cwd=ROOT, env=None):
    env = dict(env or os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py")] + args,
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


def json_lines(text):
    out = []
    for line in text.splitlines():
        if line.startswith("{"):
            out.append(json.loads(line))
    return out


def test_no_chip_no_result():
    p = run(["--workload", CELLS[0], "--seed", "5", "--seconds", "0.2",
             "--trace", "0"])
    assert p.returncode != 0
    assert json_lines(p.stdout) == []
    assert "no accelerator" in p.stderr


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_prints_no_device_metric(cell, trace):
    # past 32 signed bits; its own for each case, which writes a file
    seed = 2147483659 + int(trace)
    p = run(["--workload", cell, "--seed", str(seed), "--seconds",
             "0.3", "--trace", trace, "--rehearse-cpu"])
    assert p.returncode == 0, p.stderr[-2000:]
    lines = json_lines(p.stdout)
    result = lines[-1]
    assert p.stdout.rstrip().splitlines()[-1].startswith("{")
    assert "metrics" not in result and "breakdown" not in result
    assert result["device"]["platform"] == "cpu"
    assert "rehearsal" in result and list(result)[-1] == "compared"
    assert result["attempted"] >= 2 and result["failed"] == 0
    # the per-dispatch intervals: an earlier line and a file
    earlier = lines[-2]
    assert len(earlier["dispatch_intervals_s"]) == result["attempted"] - 1
    path = os.path.join(ROOT, "benchmark", "out",
                        "%s.%d.dispatches.json" % (cell, seed))
    assert json.load(open(path))["dispatch_intervals_s"] \
        == earlier["dispatch_intervals_s"]
    # what was compared stands beside its limit at the end of stderr
    for name in result["compared"]:
        assert "compared %s = " % name in p.stderr


def test_only_the_benchmark_cannot_run(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = run(["--workload", CELLS[0], "--seed", "5", "--seconds", "0.2",
             "--trace", "0", "--rehearse-cpu"], cwd=str(tmp_path),
            env=env)
    assert p.returncode != 0
    assert json_lines(p.stdout) == []
