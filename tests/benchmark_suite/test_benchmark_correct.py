"""What decides ``correct``, at a size a test run can hold.

Each case drives ``benchmark/run.py``'s own main path in a process of
its own (``fault_driver.py``), at the toy sizes the configuration files
give, on the CPU, and reads the result line:

  - the sound program is correct;
  - the timed path broken underneath comes out as not correct, once for
    each fault the cell can have;
  - with dropout off (at toy size the masks' noise would bury a change
    of precision) the reference agrees with the program to bf16
    rounding, and the control -- the reference in int8 in the program's
    place -- comes out as not correct through the same comparison.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SEED = 2147483693


def drive(fault, workload, *flags, devices=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    if devices:
        env["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=%d" % devices
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "fault_driver.py"), fault,
         *flags, "--", "--workload", workload, "--seed", str(SEED),
         "--seconds", "0.2", "--trace", "0", "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.rstrip().splitlines()[-1])


SCAN_CELLS = ["tfm_base_scan", "bert_base_s512_scan"]


@pytest.mark.parametrize("cell", SCAN_CELLS)
def test_sound_program_is_correct(cell):
    r = drive("none", cell)
    assert r["correct"] is True, r["compared"]
    assert r["compared"], "nothing was compared"


@pytest.mark.parametrize("cell", SCAN_CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_broken_timed_path_is_not_correct(cell, fault):
    r = drive(fault, cell)
    assert r["correct"] is False, r["all_readings"]
    over = [n for n, c in r["compared"].items()
            if c["value"] > c["limit"]]
    assert over, r["compared"]
    if fault == "state_unchanged":
        # nothing moved: the change's gap reads 1 by its measure on
        # every leaf at least as large as the median leaf
        assert r["all_readings"]["delta_worst"] == pytest.approx(1.0)
        assert r["all_readings"]["delta_median"] > 0.9
        assert r["all_readings"]["delta_all"] == pytest.approx(1.0)
        assert r["all_readings"]["moved_worst"] == 1.0
    else:
        # rows left out: their embedding rows never move
        assert r["all_readings"]["moved_worst"] > 0.2


def test_dp4_sound_and_exchange_left_out():
    sound = drive("none", "tfm_base_dp4", devices=4)
    assert sound["correct"] is True, sound["compared"]
    assert sound["device"]["count"] == 4
    broken = drive("no_exchange", "tfm_base_dp4", devices=4)
    assert broken["correct"] is False, broken["all_readings"]
    frozen = drive("state_unchanged", "tfm_base_dp4", devices=4)
    assert frozen["correct"] is False, frozen["all_readings"]


@pytest.mark.parametrize("cell", SCAN_CELLS)
def test_reference_agrees_without_masks(cell):
    r = drive("none", cell, "--no-dropout")
    assert r["correct"] is True, r["compared"]
    sound = r["all_readings"]
    # bf16 rounding alone: the reference IS the program's mathematics
    assert sound["loss_gap"] < 2e-3
    assert sound["m1_worst"] < 0.03 and sound["delta_worst"] < 0.03
    assert sound["moved_worst"] < 1e-3


@pytest.mark.parametrize("cell", SCAN_CELLS)
def test_control_in_the_programs_place_is_not_correct(cell):
    r = drive("control_int8", cell, "--no-dropout")
    assert r["correct"] is False, r["all_readings"]
    over = [n for n, c in r["compared"].items()
            if c["value"] > c["limit"]]
    assert over, r["compared"]
