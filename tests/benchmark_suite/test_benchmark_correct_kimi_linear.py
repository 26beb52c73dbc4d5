"""What decides ``correct`` in the Kimi Linear cell, at the toy size its
configuration file gives, on the CPU (test_benchmark_correct.py names
its cells; this is the same drive for ``kimi_linear_s8k_scan``).

The block has no dropout: the reference IS the program's mathematics
up to bf16 rounding and the few tokens whose last expert flips. Beside
the faults every cell can have (``fault_driver.py``), the ones only
this model can have (``fault_driver_kimi_linear.py``): the KDA decay or
its beta left out, latent attention's shared key lanes dropped, the
experts after the held ones computed in their place -- and the planted
lower precision that is this model's own, the KDA state carried from
chunk to chunk in bfloat16. The comparison's numbers do not see that
one yet: the last two tests hold its readings, and the verdict ISSUE 32
wants as an expected failure until ``compare.py`` reads a number that
sees it.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SEED = 2147483693
CELL = "kimi_linear_s8k_scan"
OWN = "fault_driver_kimi_linear.py"


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
    assert sound["m1_all"] < 0.01 and sound["delta_all"] < 1e-3


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
    "decay_left_out", "beta_left_out", "shared_key_lanes_dropped",
    "next_experts"])
def test_the_models_own_faults_are_not_correct(fault):
    r = drive(fault, OWN)
    assert r["correct"] is False, r["all_readings"]
    assert over(r), r["compared"]
    got = r["all_readings"]
    if fault in ("decay_left_out", "beta_left_out"):
        # the gate's (or beta's) own weights get no gradient at all
        assert got["moved_worst"] == 1.0 and got["m1_all"] > 0.03
    elif fault == "shared_key_lanes_dropped":
        # the 4 shared lanes' columns of kv_a never move; the rest is a
        # sound run of another model
        assert "moved_worst" in over(r) and got["m1_all"] < 0.015
    else:
        assert got["delta_all"] < 0.05      # a sound run of another model


@pytest.fixture(scope="module")
def bf16_carry():
    return drive("kda_carry_bf16", OWN)


def test_bf16_carry_reads_as_rounding(bf16_carry):
    """The readings of the KDA state carried in bfloat16, no verdict:
    the program's products already read the state in bf16 (AMP), the
    carry's rounding is unbiased, and every number ``compare.py`` reads
    is a norm or a mean, which a random relative error e moves by
    e^2 / 2. At the same chunk (8 tokens, four chunks a row) the
    float32 carry reads loss_gap 1.8e-4, m1_all 0.0038, delta_all
    1.5e-4; the bfloat16 carry 1.4e-4, 0.0049, 6.3e-4; the sound
    program at its own chunk of 64 reads 5.5e-5, 0.0022, 4.2e-4."""
    got = bf16_carry["all_readings"]
    assert got["loss_gap"] < 5e-4 and got["m1_all"] < 0.01
    assert got["delta_all"] < 1.2e-3 and got["moved_worst"] == 0.0


@pytest.mark.xfail(strict=True, reason="no number compare.py reads "
                   "sees a bf16 carry: an element-wise m1 difference "
                   "would (PERF.md section 7, first for the next "
                   "benchmark issue)")
def test_bf16_carry_is_not_correct(bf16_carry):
    """What ISSUE 32 asked of the committed limits."""
    assert bf16_carry["correct"] is False, bf16_carry["compared"]
