"""``Executor.telemetry()`` counts the whole entry-point call and the
executable build in phases, always on: entry >= prepare + dispatch +
settle for every entry point, and a build's phases for a compiled and
for a store-loaded executable."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import compile_cache as cc
from paddle_tpu import layers
from paddle_tpu import observability as obs

PHASES = ("entry_seconds_total", "prepare_seconds_total",
          "dispatch_seconds_total", "settle_seconds_total")
BUILD = ("trace_lower_seconds", "key_seconds", "store_load_seconds",
         "xla_compile_seconds", "store_put_seconds")


@pytest.fixture(autouse=True)
def _no_cache_leak():
    yield
    cc.configure(None)


def _net():
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4, 8], append_batch_size=False)
        loss = layers.reduce_sum(layers.fc(x, size=2))
        fluid.optimizer.Adam(0.1).minimize(loss)
    return main, startup, loss


def _call(exe, entry, main, loss, x):
    if entry == "run":
        exe.run(main, feed={"x": x}, fetch_list=[loss])
    elif entry == "run_repeated":
        exe.run_repeated(main, feed={"x": x}, fetch_list=[loss],
                         iters=3)
    else:
        exe.run_pipelined(main, feed_chunk={"x": np.stack([x] * 3)},
                          fetch_list=[loss])


@pytest.mark.parametrize("entry", ["run", "run_repeated",
                                   "run_pipelined"])
def test_entry_holds_its_phases_and_all_grow_by_call(entry):
    main, startup, loss = _net()
    exe = fluid.Executor()
    x = np.ones((4, 8), np.float32)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        seen = [exe.telemetry()]
        for _ in range(4):
            _call(exe, entry, main, loss, x)
            seen.append(exe.telemetry())
    for before, after in zip(seen, seen[1:]):
        grew = {k: after[k] - before[k] for k in PHASES}
        assert all(v > 0 for v in grew.values()), grew
        # telemetry rounds each total to a microsecond
        assert grew["entry_seconds_total"] >= (
            grew["prepare_seconds_total"]
            + grew["dispatch_seconds_total"]
            + grew["settle_seconds_total"]) - 4e-6, grew
    t = seen[-1]
    assert "steps_per_s" not in t and "step_time_ms" not in t
    # the first call's build lies in its prepare
    first = {k: seen[1][k] - seen[0][k] for k in PHASES}
    built = [r for r in exe.aot_artifacts() if r["entry"] == entry][-1]
    assert first["prepare_seconds_total"] >= built["build_seconds"]


def test_a_loop_over_run_books_each_entry_once():
    """run_repeated on a CompiledProgram loops over run(): the loop
    itself books nothing, so seconds are not counted twice."""
    main, startup, loss = _net()
    exe = fluid.Executor()
    compiled = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name)
    x = np.ones((4, 8), np.float32)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(compiled, feed={"x": x}, fetch_list=[loss])
        before = exe.telemetry()
        import time
        t0 = time.perf_counter()
        exe.run_repeated(compiled, feed={"x": x}, fetch_list=[loss],
                         iters=3)
        wall = time.perf_counter() - t0
        after = exe.telemetry()
    assert after["dispatches"] - before["dispatches"] == 3
    assert 0 < after["entry_seconds_total"] \
        - before["entry_seconds_total"] <= wall


def test_build_phases_of_a_compiled_and_a_loaded_executable(tmp_path):
    cc.configure(str(tmp_path / "store"))
    main, startup, loss = _net()
    x = np.ones((4, 8), np.float32)
    scope = fluid.Scope()
    mark = obs.journal_events()[-1]["seq"] if obs.journal_events() \
        else 0

    def build():
        exe = fluid.Executor()
        with fluid.scope_guard(scope):
            exe.run(main, feed={"x": x}, fetch_list=[loss])
        rec, = exe.aot_artifacts()
        return exe.telemetry()["build_phases"], rec

    exe0 = fluid.Executor()
    with fluid.scope_guard(scope):
        exe0.run(startup)
    cold, cold_rec = build()
    warm, warm_rec = build()
    assert not cold_rec["from_cache"] and warm_rec["from_cache"]
    for phases, rec in ((cold, cold_rec), (warm, warm_rec)):
        assert set(phases) == set(BUILD) == set(rec["build_phases"])
        assert all(v >= 0 for v in phases.values())
        assert phases["trace_lower_seconds"] > 0
        assert phases["key_seconds"] > 0
        assert sum(rec["build_phases"].values()) <= rec["build_seconds"]
    assert cold["xla_compile_seconds"] > 0 and \
        cold["store_put_seconds"] > 0
    assert warm["xla_compile_seconds"] == 0 == warm["store_put_seconds"]
    assert warm["store_load_seconds"] > 0
    # the journal's events carry the same phases
    compiled = obs.journal_events(kind="executor_compile",
                                  since_seq=mark)[-1]
    hit = obs.journal_events(kind="compile_cache_hit",
                             since_seq=mark)[-1]
    assert compiled["build_phases"]["xla_compile_seconds"] > 0
    assert hit["build_phases"]["store_load_seconds"] > 0
    assert hit["build_phases"]["xla_compile_seconds"] == 0
