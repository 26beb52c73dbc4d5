"""Profiler tests (reference: test_profiler.py, tools/timeline.py)."""

import json
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, profiler


def _small_train(n=3):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4, 8], append_batch_size=False)
        loss = layers.reduce_sum(layers.fc(x, size=2))
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor()
    exe.run(startup)
    xv = np.random.RandomState(0).rand(4, 8).astype(np.float32)
    for _ in range(n):
        exe.run(main, feed={"x": xv}, fetch_list=[loss])


def test_record_event_and_table(capsys):
    profiler.reset_profiler()
    profiler.start_profiler("CPU")
    with profiler.RecordEvent("outer"):
        with profiler.RecordEvent("inner"):
            pass
    _small_train()
    profiler.stop_profiler(sorted_key="total")
    out = capsys.readouterr().out
    assert "Profiling Report" in out
    assert "outer" in out and "inner" in out
    assert "executor_run" in out
    assert "executor_trace_compile" in out
    assert "feed_h2d" in out


def test_chrome_trace_export(tmp_path):
    profiler.reset_profiler()
    path = str(tmp_path / "trace.json")
    with profiler.profiler("CPU", sorted_key="total",
                           profile_path=path):
        _small_train()
    data = json.load(open(path))
    evs = data["traceEvents"]
    assert len(evs) >= 4
    names = {e["name"] for e in evs}
    assert "executor_run" in names
    for e in evs:
        if e["ph"] in ("M", "C"):  # metadata / counter samples
            continue
        assert e["ph"] == "X" and e["dur"] >= 0
    # cross-process merge anchor (tools/trace_merge.py)
    sync = [e for e in evs if e["name"] == "clock_sync"]
    assert sync and sync[0]["args"]["wall_time_s"] > 0


def test_chrome_trace_no_device_events(tmp_path):
    """Host-only capture (no jax.profiler trace): export must emit a
    valid single-process trace with only host-pid spans."""
    profiler.reset_profiler()
    profiler.start_profiler("CPU")
    with profiler.RecordEvent("solo"):
        pass
    profiler._enabled = False  # silent stop: no table print
    path = str(tmp_path / "t.json")
    profiler.export_chrome_tracing(path)
    evs = json.load(open(path))["traceEvents"]
    spans = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in spans} == {"solo"}
    assert all(e["pid"] == 0 for e in spans)
    assert not [e for e in evs if e.get("cat") == "device"]


def test_chrome_trace_nested_same_name_spans(tmp_path):
    profiler.reset_profiler()
    profiler.start_profiler("CPU")
    with profiler.RecordEvent("dup"):
        with profiler.RecordEvent("dup"):
            with profiler.RecordEvent("dup"):
                pass
    profiler._enabled = False
    path = str(tmp_path / "t.json")
    profiler.export_chrome_tracing(path)
    dups = [e for e in json.load(open(path))["traceEvents"]
            if e["name"] == "dup"]
    assert len(dups) == 3
    assert sorted(e["args"]["depth"] for e in dups) == [0, 1, 2]
    # nesting: each deeper span starts no earlier and ends no later
    dups.sort(key=lambda e: e["args"]["depth"])
    for outer, inner in zip(dups, dups[1:]):
        assert inner["ts"] >= outer["ts"]
        assert inner["ts"] + inner["dur"] <= \
            outer["ts"] + outer["dur"] + 1e-6


def test_chrome_trace_counters_only(tmp_path):
    """A run that never recorded a span (counters only) still exports
    valid JSON, with the counters as chrome counter samples."""
    profiler.reset_profiler()
    profiler.reset_counters()
    profiler.bump_counter("test_export_counter", 3.5)
    path = str(tmp_path / "t.json")
    profiler.export_chrome_tracing(path)
    evs = json.load(open(path))["traceEvents"]
    assert not [e for e in evs if e["ph"] == "X"]
    cs = [e for e in evs if e["ph"] == "C"
          and e["name"] == "test_export_counter"]
    assert cs and cs[0]["args"]["test_export_counter"] == 3.5


def test_chrome_trace_args_json_roundtrip(tmp_path):
    profiler.reset_profiler()
    profiler.start_profiler("CPU")
    args = {"bucket": 8, "rows": 5, "label": "q1",
            "nested": {"a": [1, 2]}}
    with profiler.RecordEvent("argspan", args=args):
        pass
    profiler._enabled = False
    path = str(tmp_path / "t.json")
    profiler.export_chrome_tracing(path)
    ev = next(e for e in json.load(open(path))["traceEvents"]
              if e["name"] == "argspan")
    for k, v in args.items():
        assert ev["args"][k] == v
    assert ev["args"]["depth"] == 0


def test_disabled_profiler_records_nothing():
    profiler.reset_profiler()
    with profiler.RecordEvent("should_not_appear"):
        pass
    table = profiler.summary_table()
    assert "should_not_appear" not in table


# tier-1 wall-time headroom (ISSUE 15): ~10 s spent to reach this
# platform's quarantine skip (jax emits no device events here) — the
# slow tier keeps it for platforms where the capture works
@pytest.mark.slow
def test_device_trace_merged_into_timeline(tmp_path):
    """Host RecordEvents and XLA device-op events land in ONE chrome
    trace (separate pid tracks) and the per-op device table reports
    real op names (reference: device_tracer.cc + tools/timeline.py
    merged timeline).

    Quarantine: some CPU-backend/jax.profiler combinations emit NO
    device events at all (the xprof capture comes back host-only) —
    an environment limitation, not a merge bug. The skip condition is
    deliberately NARROW: the capture must have succeeded, produced a
    valid merged trace with the host span present, and contain zero
    device-category events; any other failure still fails loudly."""
    import json

    import jax.numpy as jnp

    trace_dir = str(tmp_path / "xprof")
    out = str(tmp_path / "merged.json")
    profiler.reset_profiler()
    profiler.start_profiler("All", trace_path=trace_dir)
    with profiler.RecordEvent("host_span"):
        x = jnp.ones((128, 128))
        for _ in range(3):
            x = (x @ x) / 128.0
        x.block_until_ready()
    profiler.stop_profiler(profile_path=out)

    data = json.load(open(out))
    cats = {e.get("cat") for e in data["traceEvents"]}
    assert "host" in cats
    if "device" not in cats:
        # narrow skip: the merge worked (valid JSON, host track with
        # our span present) and the platform simply handed the
        # profiler no device trace — nothing for the merge to merge
        host_names = {e["name"] for e in data["traceEvents"]
                      if e.get("cat") == "host"}
        assert "host_span" in host_names, (
            "no device events AND the host span is missing — that is "
            "a real export bug, not the known env limitation")
        profiler.reset_profiler()
        import pytest
        pytest.skip("platform emitted no device trace events "
                    "(host-only xprof capture); device-merge "
                    "assertions have nothing to check")
    assert "device" in cats
    names = [e["name"] for e in data["traceEvents"]
             if e.get("cat") == "device"]
    assert any("dot" in n or "fusion" in n or "jit" in n
               for n in names), names[:20]
    # the device table groups by the program's scopes, not by raw HLO
    # names; plain jnp work outside any Executor carries none
    table = profiler.device_summary_table()
    assert "Device (XLA) Report" in table
    assert "unscoped" in table and "Phase" in table
    assert "carries no scope" in table.splitlines()[1]
    profiler.reset_profiler()
    assert profiler.device_summary_table().count("\n") <= 3
