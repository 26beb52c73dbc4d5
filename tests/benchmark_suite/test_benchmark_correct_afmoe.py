"""What decides ``correct`` in the Trinity-Mini cell, at the toy size
its configuration file gives, on the CPU (test_benchmark_correct.py
names its cells; this is the same drive for ``trinity_mini_s8k_scan``).

The block has no dropout, so nothing is switched off for the control:
the reference IS the program's mathematics up to bf16 rounding and the
few tokens whose last expert flips. Beside the faults every cell can
have (``fault_driver.py``), the two only this model can have
(``fault_driver_afmoe.py``): the window left out of the sliding
layers, and the experts after the held ones computed in their place.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SEED = 2147483693
CELL = "trinity_mini_s8k_scan"


def drive(fault, driver="fault_driver.py"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, driver), fault, "--",
         "--workload", CELL, "--seed", str(SEED), "--seconds", "0.2",
         "--trace", "0", "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.rstrip().splitlines()[-1])


def over(r):
    return [n for n, c in r["compared"].items()
            if not c["value"] <= c["limit"]]


def test_sound_program_is_correct():
    r = drive("none")
    assert r["correct"] is True, r["compared"]
    assert r["compared"], "nothing was compared"
    sound = r["all_readings"]
    # bf16 rounding alone
    assert sound["loss_gap"] < 2e-3
    assert sound["m1_all"] < 0.02 and sound["delta_all"] < 0.02


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "control_int8"])
def test_broken_timed_path_is_not_correct(fault):
    r = drive(fault)
    assert r["correct"] is False, r["all_readings"]
    assert over(r), r["compared"]
    if fault == "state_unchanged":
        assert r["all_readings"]["delta_all"] == pytest.approx(1.0)
        assert r["all_readings"]["moved_worst"] == 1.0
    elif fault == "half_batch":
        # one of the rehearsal's two rows left out: its embedding rows
        # never move
        assert r["all_readings"]["moved_worst"] > 0.2
    else:
        # 127 levels a tensor flush the head's cotangent over 1,021
        # words: the columns of words that are nobody's label stay
        assert "moved_worst" in over(r)


@pytest.mark.parametrize("fault", ["window_left_out", "next_experts"])
def test_the_models_own_faults_are_not_correct(fault):
    r = drive(fault, "fault_driver_afmoe.py")
    assert r["correct"] is False, r["all_readings"]
    assert over(r), r["compared"]
    # each is a sound training run of ANOTHER model: the loss falls
    # as it should, the state moved everywhere
    assert r["all_readings"]["delta_all"] < 0.05
