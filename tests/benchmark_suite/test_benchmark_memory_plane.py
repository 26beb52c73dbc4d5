"""The readers of the program's memory account
(``Executor.telemetry()["memory"]``) on a hand-made ``ctx``: the
window's step is the executable whose ``dispatches`` grew most over the
window, and a program without the account (the parent of the PR that
brought it) gives no reading and no error."""

import importlib
import json
import os

import pytest

from benchmark import run as bench

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GIB = 2 ** 30


def _exe(entry, shape_key, dispatches, temp, params, moments, other,
         feed):
    return {"entry": entry, "program_uid": 5, "shape_key": shape_key,
            "from_cache": True, "dispatches": dispatches,
            "memory": {"argument_bytes": params + moments + other + feed,
                       "output_bytes": params + moments + other,
                       "alias_bytes": params + moments + other,
                       "temp_bytes": temp, "generated_code_bytes": 1,
                       "peak_bytes": None},
            "state": {"parameters": {"bytes": params, "leaves": 3},
                      "optimizer_state": {"bytes": moments, "leaves": 12},
                      "other": {"bytes": other, "leaves": 2},
                      "feed_bytes": feed}}


def _tel(startup, step, norms, devices):
    return {"steps": 8 * step, "dispatches": startup + step + norms,
            "memory": {"executables": [
                _exe("run", "(no feed)", startup, 3 * GIB, 0, 0, 0, 0),
                _exe("run_repeated", "ids=int32[1,8192]", step, 5 * GIB,
                     2 * GIB, 4 * GIB, GIB // 1024, GIB // 4),
                _exe("run", "ids=int32[1,8192]", norms, 7 * GIB,
                     2 * GIB, 4 * GIB, GIB // 1024, GIB // 4)],
                "devices": devices}}


# two devices: the fuller one by peak_bytes_in_use + nothing reserved,
# the other by what is in use now plus the most that was reserved
DEVICES = [{"id": 0, "bytes_in_use": 6 * GIB, "peak_bytes_in_use": 7 * GIB,
            "peak_bytes_reserved": 6 * GIB, "bytes_limit": 16 * GIB},
           {"id": 1, "bytes_in_use": GIB, "peak_bytes_in_use": 11 * GIB}]
BEFORE = _tel(1, 1, 2, DEVICES)
AFTER = _tel(1, 9, 2, DEVICES)
STATE = 2 + 4 + 1 / 1024
WANT = {"hbm_state_gib": STATE, "hbm_step_temp_gib": 5.0,
        "hbm_unaccounted_gib": 12 - STATE - 5 - 0.25}


def read(name, before, after):
    return importlib.import_module("benchmark.metrics." + name).read(
        {"telemetry_before": before, "telemetry_after": after})


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_a_hand_made_ctx(name):
    assert read(name, BEFORE, AFTER) == pytest.approx(WANT[name])
    meta = bench.load_json(ROOT, "benchmark", "metrics", name + ".json")
    entry, = [m for m in json.load(open(os.path.join(
        ROOT, "BENCHMARK.json")))["per_layer"] if m["name"] == name]
    assert {k: meta[k] for k in entry} == entry
    assert entry == {"name": name, "unit": "GiB", "better": "lower",
                     "source": "program_counter", "layer": "memory",
                     "moves": "peak_hbm_gib"}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_is_silent_without_the_account(name):
    # the parent's telemetry has no such key: no reading, no error
    old = {k: v for k, v in AFTER.items() if k != "memory"}
    assert read(name, dict(old, steps=8), old) is None
    # nothing dispatched over the window: no step to name
    assert read(name, AFTER, AFTER) is None
    assert bench.read_metrics([name], {"telemetry_before": old,
                                       "telemetry_after": old}) == {}


@pytest.mark.parametrize("name", sorted(WANT))
def test_the_windows_executable_is_chosen_by_dispatches(name):
    """The state-norm call outgrows the step here, and the readers
    follow it: 7 GiB of temporaries in place of 5."""
    after = _tel(1, 9, 2 + 20, DEVICES)
    want = {"hbm_state_gib": STATE, "hbm_step_temp_gib": 7.0,
            "hbm_unaccounted_gib": 12 - STATE - 7 - 0.25}
    assert read(name, BEFORE, after) == pytest.approx(want[name])
    # an executable first built inside the window counts from nought
    fresh = dict(BEFORE, memory=dict(
        BEFORE["memory"],
        executables=BEFORE["memory"]["executables"][:1]))
    assert read(name, fresh, AFTER) == pytest.approx(WANT[name])


def test_unaccounted_needs_the_devices_statistics():
    cpu = [{"id": 0, "bytes_in_use": None, "peak_bytes_in_use": None}]
    assert read("hbm_unaccounted_gib", BEFORE,
                _tel(1, 9, 2, cpu)) is None
    assert read("hbm_state_gib", BEFORE, _tel(1, 9, 2, cpu)) \
        == pytest.approx(STATE)
    # an interpreted executable carries neither record
    bare = _tel(1, 9, 2, DEVICES)
    bare["memory"]["executables"][1].update(memory=None, state=None)
    for name in WANT:
        assert read(name, BEFORE, bare) is None
