"""What decides ``correct`` in the Kanana-2 cell, at the toy size its
configuration file gives, on the CPU (test_benchmark_correct.py names
its cells; this is the same drive for ``kanana2_s8k_scan``).

The block has no dropout: the reference IS the program's mathematics
up to bf16 rounding and the few tokens whose last expert flips. Beside
the faults every cell can have (``fault_driver.py``), the ones only
this model can have (``fault_driver_kanana2.py``): the rotary part
left out of latent attention, the rotated key lanes every head shares
dropped, the experts after the held ones computed in their place. At
the toy size each is seen; at the cell's own a fault that keeps a
random model's statistics may read ``correct`` (PERF.md section 7),
and then tests/test_deepseek_v3_model.py's element-wise comparison is
what guards it.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SEED = 2147483693
CELL = "kanana2_s8k_scan"
OWN = "fault_driver_kanana2.py"


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
    assert sound["loss_gap"] < 2e-4
    assert sound["m1_all"] < 0.005 and sound["delta_all"] < 1.5e-3


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
        assert r["all_readings"]["moved_worst"] > 0.2
    else:
        assert "moved_worst" in over(r) and "delta_all" in over(r)


@pytest.mark.parametrize("fault", [
    "rotary_left_out", "shared_key_lanes_dropped", "next_experts"])
def test_the_models_own_faults_are_not_correct(fault):
    r = drive(fault, OWN)
    assert r["correct"] is False, r["all_readings"]
    assert over(r), r["compared"]
    got = r["all_readings"]
    if fault == "rotary_left_out":
        # a sound run of a model without positions: every leaf moves,
        # the first gradients differ by a few percent
        assert "m1_median" in over(r) and got["delta_all"] < 0.02
    elif fault == "shared_key_lanes_dropped":
        # the 4 shared lanes' columns of kv_a never move
        assert "moved_worst" in over(r) and got["m1_all"] < 0.015
    else:
        assert got["delta_all"] < 0.05      # a sound run of another model
