"""The memory plane (PR 36): what each executable and the carried
state hold in device memory, counted where the executable is built
(``Executor.telemetry()["memory"]``, the artifact record, the journal
events), and ``profiler.memory_table``: the scheduled HLO's fullest
moment by the program's scopes."""

import json

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import compile_cache as cc
from paddle_tpu import observability as obs
from paddle_tpu import profiler
from paddle_tpu.contrib import mixed_precision as amp
from paddle_tpu.core import device_info

FIELDS = ("argument_bytes", "output_bytes", "alias_bytes", "temp_bytes",
          "generated_code_bytes", "peak_bytes")


@pytest.fixture(autouse=True)
def _no_cache_or_journal_leak():
    yield
    cc.configure(None)
    obs.clear_journal()


def _adam_amp_net(seed=11):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[16], dtype="float32")
            y = fluid.layers.data("y", shape=[1], dtype="float32")
            h = fluid.layers.fc(x, 32, act="relu")
            pred = fluid.layers.fc(h, 1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))
            amp.decorate(fluid.optimizer.AdamOptimizer(1e-3)).minimize(
                loss)
    return main, startup, loss


FEED = {"x": np.ones((8, 16), np.float32),
        "y": np.ones((8, 1), np.float32)}


def _trained(runs=1, repeated=0):
    """(executor, scope, main program) after the startup program,
    ``runs`` x ``run`` and ``repeated`` x ``run_repeated(iters=3)``."""
    main, startup, loss = _adam_amp_net()
    exe, scope = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(runs):
            exe.run(main, feed=FEED, fetch_list=[loss])
        for _ in range(repeated):
            exe.run_repeated(main, feed=FEED, fetch_list=[loss], iters=3)
    return exe, scope, main


def _step_record(exe, entry="run"):
    rec, = [r for r in exe.telemetry()["memory"]["executables"]
            if r["entry"] == entry and r["shape_key"] != "(no feed)"]
    return rec


# -- the record, compiled and loaded from the store ---------------------

@pytest.fixture(scope="module")
def cold_and_warm(tmp_path_factory):
    """The step's record from an Executor that compiled it into a fresh
    store and from a second one that loaded it from there, with the
    journal events both builds emitted."""
    cc.configure(str(tmp_path_factory.mktemp("store")))
    obs.clear_journal()
    try:
        cold, _, _ = _trained()
        warm, _, _ = _trained()
        events = obs.journal_events()
    finally:
        cc.configure(None)
        obs.clear_journal()
    return _step_record(cold), _step_record(warm), events


@pytest.mark.parametrize("field", FIELDS)
def test_store_loaded_executable_reports_what_the_compiled_did(
        cold_and_warm, field):
    cold, warm, _ = cold_and_warm
    assert not cold["from_cache"] and warm["from_cache"]
    assert set(cold["memory"]) == set(FIELDS)
    assert warm["memory"][field] == cold["memory"][field]
    if field in ("argument_bytes", "output_bytes", "temp_bytes"):
        assert cold["memory"][field] > 0


def test_journal_events_carry_the_record(cold_and_warm):
    cold, _, events = cold_and_warm
    compiled = [e for e in events if e["kind"] == "executor_compile"
                and e["shape_key"] == cold["shape_key"]]
    hits = [e for e in events if e["kind"] == "compile_cache_hit"
            and e["shape_key"] == cold["shape_key"]]
    assert len(compiled) == 1 and len(hits) == 1
    assert compiled[0]["memory"] == cold["memory"]
    assert hits[0]["memory"] == cold["memory"]


def test_put_stamps_the_record_for_a_load_that_gives_none(
        tmp_path, monkeypatch):
    """Where the loaded executable gives no analysis, the hit path
    reports what the compiling process put into the entry's meta."""
    cc.configure(str(tmp_path))
    cold, _, _ = _trained()
    want = _step_record(cold)["memory"]
    metas = [json.load(open(p)) for p in tmp_path.glob("*.json")]
    assert want in [m["memory"] for m in metas]
    monkeypatch.setattr(cc, "memory_record", lambda compiled: None)
    warm, _, _ = _trained()
    rec = _step_record(warm)
    assert rec["from_cache"] and rec["memory"] == want


# -- the carried state by kind ------------------------------------------

def test_state_by_kind_sums_to_the_persistables_the_step_takes():
    exe, scope, main = _trained()
    state = _step_record(exe)["state"]
    block = main.global_block()
    held = {n: scope.find_var(n) for n, v in block.vars.items()
            if v.persistable and scope.has_var(n)}
    nbytes = lambda names: sum(
        int(np.prod(held[n].shape)) * held[n].dtype.itemsize
        for n in names)
    params = [p.name for p in main.all_parameters()]
    moments = [n for n in held if "_moment" in n or "_pow_acc" in n]
    assert state["parameters"] == {"bytes": nbytes(params),
                                   "leaves": len(params)}
    # Adam keeps two moments and two powers a parameter
    assert state["optimizer_state"] == {"bytes": nbytes(moments),
                                        "leaves": 4 * len(params)}
    assert "loss_scaling_0" in held      # AMP's scale is state too
    assert sum(state[k]["bytes"] for k in (
        "parameters", "optimizer_state", "other")) == nbytes(held)
    assert sum(state[k]["leaves"] for k in (
        "parameters", "optimizer_state", "other")) == len(held)
    assert state["feed_bytes"] == sum(v.nbytes for v in FEED.values())


def test_a_sharded_leaf_counts_its_shard():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from paddle_tpu.executor import _state_by_kind
    main, _, _ = _adam_amp_net()
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    x = jax.device_put(np.zeros((8, 16), np.float32),
                       NamedSharding(mesh, PartitionSpec("dp")))
    state = _state_by_kind(main.global_block(), {}, {"x": x})
    assert state["feed_bytes"] == x.nbytes // 4


# -- which executable the window ran ------------------------------------

def test_dispatches_count_per_executable():
    exe, _, _ = _trained(runs=3, repeated=2)
    assert _step_record(exe, "run")["dispatches"] == 3
    assert _step_record(exe, "run_repeated")["dispatches"] == 2
    assert sum(r["dispatches"] for r in
               exe.telemetry()["memory"]["executables"]) \
        == exe.telemetry()["dispatches"] == 6


def test_a_dispatch_of_a_built_executable_reads_nothing(monkeypatch):
    """No ``memory_analysis()``, no HLO text and no allocator statistics
    on the dispatch path: a built executable's dispatch makes none;
    ``telemetry()``, read at phase boundaries, asks the devices."""
    main, startup, loss = _adam_amp_net()
    exe, scope = fluid.Executor(), fluid.Scope()
    calls = []

    def counted(name, fn):
        def wrapper(*a, **k):
            calls.append(name)
            return fn(*a, **k)
        return wrapper

    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=FEED, fetch_list=[loss])
        exe.run_repeated(main, feed=FEED, fetch_list=[loss], iters=2)
        for owner, name in ((jax.stages.Compiled, "memory_analysis"),
                            (jax.stages.Compiled, "as_text"),
                            (cc, "memory_record"),
                            (device_info, "device_properties")):
            monkeypatch.setattr(owner, name,
                                counted(name, getattr(owner, name)))
        exe.run(main, feed=FEED, fetch_list=[loss])
        exe.run_repeated(main, feed=FEED, fetch_list=[loss], iters=2)
    assert calls == []
    exe.telemetry(scope=scope)
    assert calls == ["device_properties"]


# -- telemetry()["memory"] ----------------------------------------------

def test_memory_is_there_before_any_run_and_is_json():
    exe = fluid.Executor()
    mem = exe.telemetry(scope=fluid.Scope())["memory"]
    assert mem["executables"] == []
    assert [d["id"] for d in mem["devices"]] == [jax.devices()[0].id]
    trained, scope, _ = _trained(repeated=1)
    mem = trained.telemetry(scope=scope)["memory"]
    assert json.loads(json.dumps(mem)) == mem
    assert {tuple(sorted(r)) for r in mem["executables"]} == {tuple(sorted((
        "entry", "program_uid", "shape_key", "from_cache", "dispatches",
        "memory", "state")))}


class _Device:
    device_kind, platform, id, process_index = "fake", "tpu", 0, 0

    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("stats,absent", [
    ({"bytes_limit": 16, "bytes_in_use": 3, "peak_bytes_in_use": 5,
      "bytes_reserved": 7, "peak_bytes_reserved": 9,
      "largest_free_block_bytes": 2}, ()),
    ({"bytes_limit": 16, "bytes_in_use": 3, "peak_bytes_in_use": 5,
      "peak_bytes_reserved": 9},
     ("bytes_reserved", "largest_free_block_bytes")),
])
def test_device_properties_reads_the_reserved_side(stats, absent):
    props = device_info.device_properties(_Device(stats))
    assert {k: props[k] for k in stats} == stats
    assert not set(absent) & set(props)


# -- memory_table ---------------------------------------------------------

@pytest.mark.parametrize("shape,want", [
    ("f32[1024,256]{1,0:T(8,128)}", 1024 * 256 * 4),
    ("bf16[1001,200]{1,0:T(8,128)(2,1)}", 1008 * 256 * 2),
    ("f32[4]{0:T(128)}", 128 * 4),
    ("f32[]{:T(128)}", 128 * 4),
    ("pred[8]{0:T(512)(128)(4,1)}", 512),
    ("f32[3,5]{0,1:T(8,128)}", 8 * 128 * 4),     # 5 is the major one
    ("f32[1024,256]{1,0:T(8,128)S(1)}", 0),      # not in HBM
    ("f32[7,9]{1,0}", 7 * 9 * 4),                # a CPU layout: no tile
    ("f32[7,9]", 7 * 9 * 4),
    ("s4[256,256]{1,0:T(8,128)(4,1)E(4)}", 256 * 256 // 2),
])
def test_hbm_bytes_of_a_shape(shape, want):
    assert [b for b, _ in profiler._hbm_bytes(shape)] == [want]


HAND = """HloModule hand, is_scheduled=true

%cond (q: (s32[], f32[1024,256])) -> pred[] {
  %q = (s32[]{:T(128)}, f32[1024,256]{1,0:T(8,128)}) parameter(0)
  %k = s32[]{:T(128)} get-tuple-element(%q), index=0
  %eight = s32[]{:T(128)} constant(8)
  ROOT %lt = pred[]{:T(512)} compare(%k, %eight), direction=LT
}

%body (p: (s32[], f32[1024,256])) -> (s32[], f32[1024,256]) {
  %p = (s32[]{:T(128)}, f32[1024,256]{1,0:T(8,128)}) parameter(0)
  %w = f32[1024,256]{1,0:T(8,128)} get-tuple-element(%p), index=1
  %i = s32[]{:T(128)} get-tuple-element(%p), index=0
  %a = f32[1024,256]{1,0:T(8,128)} exponential(%w), metadata={op_name="jit(f)/while/body/fwd/ffn/exp"}
  %b = bf16[1001,200]{1,0:T(8,128)(2,1)} convert(%a), metadata={op_name="jit(f)/while/body/fwd/attention/cast"}
  %c = f32[1024,256]{1,0:T(8,128)S(1)} negate(%a), metadata={op_name="jit(f)/while/body/bwd/ffn/neg"}
  %d = f32[4]{0:T(128)} reduce(%a, %i), dimensions={0}, metadata={op_name="jit(f)/while/body/bwd/loss/reduce_sum"}
  %e = f32[1024,256]{1,0:T(8,128)} add(%c, %b), metadata={op_name="jit(f)/while/body/bwd/ffn/add"}
  %n = f32[1024,256]{1,0:T(8,128)} multiply(%e, %w), metadata={op_name="jit(f)/while/body/opt/optimizer/adam"}
  ROOT %t = (s32[]{:T(128)}, f32[1024,256]{1,0:T(8,128)}) tuple(%i, %n)
}

ENTRY %main (x: f32[1024,256]) -> f32[1024,256] {
  %x = f32[1024,256]{1,0:T(8,128)} parameter(0)
  %z = s32[]{:T(128)} constant(0)
  %g = f32[2048]{0:T(1024)} iota(), iota_dimension=0
  %tup = (s32[]{:T(128)}, f32[1024,256]{1,0:T(8,128)}) tuple(%z, %x)
  %wh = (s32[]{:T(128)}, f32[1024,256]{1,0:T(8,128)}) while(%tup), condition=%cond, body=%body
  %r = f32[1024,256]{1,0:T(8,128)} get-tuple-element(%wh), index=1
  ROOT %o = f32[1024,256]{1,0:T(8,128)} add(%r, %g)
}
"""
A, B, D, G = 1024 * 256 * 4, 1008 * 256 * 2, 512, 8192


def test_memory_table_of_a_hand_written_schedule():
    """Five instructions of a ``while`` body own bytes (``c`` lies
    outside HBM, ``n`` is what the body returns): ``a`` lives until
    ``d`` reads it, so the fullest moment is at ``d`` with ``a``, ``b``
    and ``d`` itself, on top of the one buffer the entry holds across
    the loop."""
    t = profiler.memory_table(HAND, temp_bytes=2 * (A + B + D + G))
    assert t["peak_bytes"] == A + B + D + G
    assert (t["computation"], t["instruction"], t["scope"]) \
        == ("body", "d", "bwd/loss/reduce_sum")
    assert t["by_layer"] == {"ffn": A, "attention": B, "loss": D,
                             "unscoped": G}
    assert t["by_phase"] == {"fwd": A + B, "bwd": D, "unscoped": G}
    assert t["by_layer_op"] == {"ffn exp": A, "attention cast": B,
                                "loss reduce_sum": D, "unscoped iota": G}
    assert [(r["instruction"], r["bytes"]) for r in t["largest"]] \
        == [("a", A), ("b", B), ("g", G), ("d", D)]
    assert t["buffers"] == 4 and t["coverage"] == 0.5
    text = profiler.format_memory_table(t)
    assert "a model" in text.splitlines()[1] \
        and "coverage 0.500" in text.splitlines()[1]
    assert profiler.memory_table(HAND)["coverage"] is None


@pytest.fixture(scope="module")
def real_step_table():
    exe, _, _ = _trained(runs=0, repeated=1)
    rec, = [r for r in exe.aot_artifacts()
            if r["entry"] == "run_repeated"]
    return profiler.memory_table(rec["optimized_hlo"],
                                 rec["memory"]["temp_bytes"]), rec


@pytest.mark.parametrize("table", ["by_phase", "by_layer", "by_layer_op"])
def test_every_table_of_a_real_step_sums_to_the_modelled_peak(
        real_step_table, table):
    t, _ = real_step_table
    assert t["peak_bytes"] > 0 and t["instruction"]
    assert sum(t[table].values()) == t["peak_bytes"]
    assert sum(r["bytes"] for r in t["largest"]) <= t["peak_bytes"]


def test_device_memory_table_is_the_most_dispatched_executables(
        real_step_table, monkeypatch):
    t, rec = real_step_table
    assert 0.0 < t["coverage"] < float("inf")
    exe, _, _ = _trained(runs=1, repeated=2)
    # the live Executors of this process alone: other tests' may linger
    monkeypatch.setattr(profiler, "_executors", {exe})
    live = profiler.device_memory_table()
    assert live["entry"] == "run_repeated" and live["dispatches"] == 2
    assert live["state"] == rec["state"]
    assert live["peak_bytes"] == t["peak_bytes"]
    # of equals, the one built last: the step, not the startup program
    once, _, _ = _trained(runs=1)
    monkeypatch.setattr(profiler, "_executors", {once})
    assert profiler.device_memory_table()["shape_key"] != "(no feed)"
