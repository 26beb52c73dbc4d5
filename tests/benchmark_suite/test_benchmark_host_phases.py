"""The readers of the program's host-phase counters, on a hand-made
``ctx``: window metrics are a difference over the window's steps, build
metrics the whole run's, and a program without the counter (the parent
of the PR that brought them) gives no reading and no error."""

import importlib
import json
import os

import pytest

from benchmark import run as bench

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

BEFORE = {"steps": 24, "dispatches": 3, "entry_seconds_total": 60.0,
          "prepare_seconds_total": 59.0, "dispatch_seconds_total": 0.5,
          "settle_seconds_total": 0.25,
          "build_phases": {"trace_lower_seconds": 7.5,
                           "key_seconds": 1.0,
                           "store_load_seconds": 11.0,
                           "xla_compile_seconds": 0.0,
                           "store_put_seconds": 0.0}}
AFTER = dict(BEFORE, steps=24 + 160, dispatches=23,
             entry_seconds_total=60.8, prepare_seconds_total=59.4,
             dispatch_seconds_total=0.58, settle_seconds_total=0.49)
WANT = {"host_entry_ms_per_step": 0.8 / 160 * 1e3,
        "host_prepare_ms_per_step": 0.4 / 160 * 1e3,
        "host_settle_ms_per_step": 0.24 / 160 * 1e3,
        "trace_lower_s": 7.5, "store_load_s": 11.0}


def read(name, before, after):
    return importlib.import_module("benchmark.metrics." + name).read(
        {"telemetry_before": before, "telemetry_after": after})


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_a_hand_made_ctx(name):
    assert read(name, BEFORE, AFTER) == pytest.approx(WANT[name])
    # the parent's telemetry has no such counter: no reading, no error
    old = {k: v for k, v in AFTER.items()
           if k in ("steps", "dispatches", "dispatch_seconds_total")}
    assert read(name, dict(old, steps=24), old) is None
    meta = bench.load_json(ROOT, "benchmark", "metrics", name + ".json")
    entry, = [m for m in json.load(open(os.path.join(
        ROOT, "BENCHMARK.json")))["per_layer"] if m["name"] == name]
    assert {k: meta[k] for k in entry} == entry
    assert entry["source"] == "program_counter" \
        and "workloads" not in entry


def test_entry_holds_its_phases_and_the_line_reports_them():
    got = bench.read_metrics(
        sorted(WANT) + ["host_dispatch_ms_per_step"],
        {"telemetry_before": BEFORE, "telemetry_after": AFTER})
    assert set(got) == set(WANT) | {"host_dispatch_ms_per_step"}
    v = {k: m["value"] for k, m in got.items()}
    assert v["host_entry_ms_per_step"] >= (
        v["host_prepare_ms_per_step"] + v["host_dispatch_ms_per_step"]
        + v["host_settle_ms_per_step"])
    assert got["store_load_s"]["unit"] == "s"
    # a cold run loads nothing: 0.0, never absent
    cold = dict(AFTER, build_phases=dict(BEFORE["build_phases"],
                                         store_load_seconds=0.0))
    assert bench.read_metrics(["store_load_s"], {
        "telemetry_before": BEFORE,
        "telemetry_after": cold})["store_load_s"]["value"] == 0.0
