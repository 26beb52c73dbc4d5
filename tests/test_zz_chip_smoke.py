"""chip_smoke.py and the no-hidden-device rules, as far as a CPU can
check them: the script refuses to pass without a TPU, its explicit
rehearsal runs every phase green at toy size and still prints no pass
line, and ``Executor(TPUPlace(n))`` notices a missing chip.

Named to sort last: tier-1 runs past its wall-clock cap (ROADMAP D8),
and what a cap cuts off should be these subprocess runs, not another
plane's tests."""

import os
import subprocess
import sys

import pytest

import paddle_tpu as fluid

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PASS_LINE = '{"ok": true'


def _smoke(*argv, env=None):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *argv],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


def test_executor_tpu_place_raises_without_a_tpu():
    with pytest.raises(fluid.core.InvalidArgumentError,
                       match="device 0 is not a TPU"):
        fluid.Executor(fluid.TPUPlace(0))
    fluid.Executor(fluid.CPUPlace())     # a request for the host: fine
    fluid.Executor()


def test_chip_smoke_fails_without_a_chip():
    p = _smoke()
    assert p.returncode != 0
    assert "not 'tpu'" in p.stderr
    assert PASS_LINE not in p.stdout


def test_chip_smoke_rehearsal_runs_green_and_never_passes():
    """Toy size, kernels in interpret mode, the dp4 phase over four of
    conftest's eight virtual devices."""
    p = _smoke("--rehearse-cpu")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert "finite and falling" in p.stdout
    assert "dp4 loss trace matches one chip" in p.stdout
    assert "CPU REHEARSAL complete" in p.stdout
    assert PASS_LINE not in p.stdout
