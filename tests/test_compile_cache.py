"""Compile-plane observability tests (PR 11): provenance ledger,
persistent AOT compile cache, miss-reason classification, doctor
culprit citation, and the journal-rotation interplay.

Acceptance anchors:
  - warm restart of the same program/shape performs ZERO XLA compiles
    (all persistent-cache hits), verified by a subprocess pair reading
    the provenance ledger;
  - every compile in a 2-process fleet run is attributable (one
    ``executor_compile`` record with a non-null miss reason per
    compile), and ``doctor --expect recompile_storm`` cites the
    offending (entry, shape-bucket) pair;
  - clone-race regression: two threads racing one Executor's first
    compile of a shape book exactly ONE provenance record.
"""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import compile_cache as cc
from paddle_tpu import observability as obs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

pytestmark = pytest.mark.compile


@pytest.fixture(autouse=True)
def _no_cache_or_journal_leak():
    """The active compile cache and journal sink are process-wide;
    tests here configure both and must not leak them into the rest of
    the suite."""
    yield
    cc.configure(None)
    obs.configure_journal(None)
    obs.clear_journal()


def _build_net(seed=13, in_dim=8, hidden=16, classes=4):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[in_dim])
        label = fluid.layers.data("label", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, size=hidden, act="relu")
        pred = fluid.layers.fc(h, size=classes, act="softmax")
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(pred, label))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _batch(batch=8, in_dim=8, classes=4, seed=0):
    rng = np.random.RandomState(seed)
    return {"x": rng.rand(batch, in_dim).astype(np.float32),
            "label": rng.randint(0, classes, (batch, 1)).astype(
                np.int64)}


# ---------------------------------------------------------------------------
# CompileCache store unit tests
# ---------------------------------------------------------------------------

def _tiny_compiled(n=4):
    return jax.jit(lambda a: a * 2 + 1).lower(
        jnp.ones((n,), jnp.float32)).compile()


class TestCompileCacheStore:
    def test_put_get_roundtrip_executes(self, tmp_path):
        c = cc.CompileCache(str(tmp_path))
        nbytes = c.put("k1", _tiny_compiled(), {"entry": "run",
                                                "compile_seconds": 0.5})
        assert nbytes and nbytes > 0
        hit = c.get("k1")
        assert hit is not None
        out = hit.loaded(jnp.ones((4,), jnp.float32))
        out = out[0] if isinstance(out, tuple) else out
        np.testing.assert_array_equal(np.asarray(out),
                                      np.full((4,), 3.0, np.float32))
        assert hit.meta["origin_pid"] == os.getpid()
        assert hit.meta["compile_seconds"] == 0.5
        assert hit.nbytes == nbytes

    def test_missing_and_corrupt_are_misses(self, tmp_path):
        c = cc.CompileCache(str(tmp_path))
        assert c.get("nope") is None
        with open(str(tmp_path / "bad.bin"), "wb") as f:
            f.write(b"torn garbage not a pickle")
        assert c.get("bad") is None
        # the corrupt entry was dropped so a recompile can overwrite
        assert not os.path.exists(str(tmp_path / "bad.bin"))

    def test_lru_eviction_remembers_keys(self, tmp_path):
        c = cc.CompileCache(str(tmp_path), max_bytes=1)
        c.put("k_old", _tiny_compiled(4), {"entry": "run"})
        # over budget already: the store itself triggers eviction
        assert c.disk_entries() == 0
        assert c.was_evicted("k_old")
        assert not c.was_evicted("never_seen")
        assert c.get("k_old") is None


# ---------------------------------------------------------------------------
# provenance ledger: miss reasons, metrics, telemetry
# ---------------------------------------------------------------------------

class TestResolver:
    """``enable()`` is the one place persistent compile state is
    placed: JAX's cache and the executable store under one root that
    can be set from outside."""

    @pytest.fixture
    def jax_updates(self, monkeypatch):
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: calls.append((k, v)))
        return calls

    def test_env_places_both_and_jax_is_not_overridden(
            self, tmp_path, monkeypatch, jax_updates):
        monkeypatch.setenv(cc.JAX_ENV_DIR, str(tmp_path))
        assert cc.enable() == str(tmp_path)
        assert jax_updates == []
        assert cc.active().dir == str(tmp_path / cc.STORE_SUBDIR)

    def test_unset_is_the_fixed_path_in_the_checkout(
            self, monkeypatch, jax_updates):
        monkeypatch.delenv(cc.JAX_ENV_DIR, raising=False)
        root = os.path.join(ROOT, ".jax_cache")
        assert cc.enable() == root
        assert [v for _k, v in jax_updates] == [root]
        assert cc.active().dir == os.path.join(root, cc.STORE_SUBDIR)

    def test_explicit_dir_wins_over_env(self, tmp_path, monkeypatch,
                                        jax_updates):
        monkeypatch.setenv(cc.JAX_ENV_DIR, str(tmp_path / "env"))
        want = str(tmp_path / "explicit")
        assert cc.enable(want) == want
        assert [v for _k, v in jax_updates] == [want]
        assert cc.active().dir == os.path.join(want, cc.STORE_SUBDIR)

    def test_one_call_site_sets_jax_cache_dir(self):
        """No entry point, tool or library module places JAX's cache
        on its own."""
        name = "jax_compilation_" + "cache_dir"
        hits = []
        for base, dirs, files in os.walk(ROOT):
            dirs[:] = [d for d in dirs if not d.startswith(".")
                       and d != "chiprun_out"]
            for f in files:
                if f.endswith(".py"):
                    path = os.path.join(base, f)
                    with open(path) as fh:
                        if name in fh.read():
                            hits.append(os.path.relpath(path, ROOT))
        assert hits == [os.path.join("paddle_tpu", "compile_cache.py")]


class TestProvenanceLedger:
    def _events(self, mark):
        return obs.journal_events(kind="executor_compile",
                                  since_seq=mark)

    def _mark(self):
        evs = obs.journal_events()
        return evs[-1]["seq"] if evs else 0

    def test_new_program_then_new_shape(self):
        main, startup, loss = _build_net()
        exe = fluid.Executor()
        scope = fluid.Scope()
        mark = self._mark()
        h = obs.registry().histogram("executor_compile_seconds")
        h0 = h.count
        with fluid.scope_guard(scope):
            exe.run(startup)
            exe.run(main, feed=_batch(8), fetch_list=[loss])
            exe.run(main, feed=_batch(8), fetch_list=[loss])  # cached
            exe.run(main, feed=_batch(16), fetch_list=[loss])
        evs = self._events(mark)
        assert [e["miss_reason"] for e in evs] == \
            ["new_program", "new_program", "new_shape"]
        assert all(e["fingerprint"] for e in evs)
        assert all(e["mode"] == "xla" for e in evs)
        assert evs[-1]["shape_key"].startswith("label=")
        assert "x=float32[16,8]" in evs[-1]["shape_key"]
        assert exe.xla_compile_count == 3
        assert exe.compile_count == 3
        assert h.count - h0 == 3
        t = exe.telemetry()
        assert t["xla_compiles"] == 3
        assert t["compiles_by_entry"] == {"run": 3}
        assert t["compile_seconds_total"] > 0

    def test_cache_cold_then_hit_then_evicted(self, tmp_path):
        cc.configure(str(tmp_path / "cc"))
        mark = self._mark()
        main, startup, loss = _build_net()
        exe = fluid.Executor()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            exe.run(main, feed=_batch(8), fetch_list=[loss])
        evs = self._events(mark)
        assert {e["miss_reason"] for e in evs} == {"cache_cold"}
        stores = obs.journal_events(kind="compile_cache_store",
                                    since_seq=mark)
        assert len(stores) == len(evs)

        # a fresh Executor, same cache: close() drops the in-memory
        # executables, the disk cache serves the reload
        mark2 = self._mark()
        exe.close()
        with fluid.scope_guard(scope):
            exe.run(main, feed=_batch(8), fetch_list=[loss])
        hits = obs.journal_events(kind="compile_cache_hit",
                                  since_seq=mark2)
        assert len(hits) == 1
        assert hits[0]["origin_pid"] == os.getpid()
        assert not self._events(mark2)  # no compile happened

        # LRU-evict everything, then the SAME program again: the
        # recompile is attributed to the eviction
        c = cc.active()
        c.max_bytes = 1
        c._evict_lru()
        mark3 = self._mark()
        exe.close()
        with fluid.scope_guard(scope):
            exe.run(main, feed=_batch(8), fetch_list=[loss])
        evs3 = self._events(mark3)
        assert evs3 and {e["miss_reason"] for e in evs3} == {"evicted"}

    def test_new_mesh_reason(self):
        from paddle_tpu.parallel import mesh as mesh_lib
        main, startup, loss = _build_net()
        exe = fluid.Executor()
        scope = fluid.Scope()
        mark = self._mark()
        with fluid.scope_guard(scope):
            exe.run(startup)
            for n in (2, 4):
                prog = fluid.CompiledProgram(main).with_data_parallel(
                    build_strategy=fluid.BuildStrategy(),
                    mesh=mesh_lib.data_parallel_mesh(n))
                exe.run(prog, feed=_batch(8), fetch_list=[loss])
        evs = [e for e in self._events(mark)
               if e["shapes"]]  # the two distributed steps
        assert [e["miss_reason"] for e in evs] == \
            ["new_program", "new_mesh"]
        assert evs[0]["mesh"] != evs[1]["mesh"]

    def test_clone_race_books_one_provenance_record(self):
        """Satellite: two threads racing one shared Executor's first
        compile of a shape must produce exactly one ledger record and
        one compile_count increment (the per-key gate; PR 3's clone()
        shares one Executor across predictor clones)."""
        main, startup, loss = _build_net()
        exe = fluid.Executor()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
        mark = self._mark()
        base = exe.compile_count
        feed = _batch(8)
        barrier = threading.Barrier(2)
        errors = []

        def work():
            try:
                barrier.wait(timeout=10)
                # donate=False: concurrent runs share the scope
                exe.run(main, feed=feed, fetch_list=[loss],
                        scope=scope, donate=False)
            except Exception as e:  # pragma: no cover
                errors.append(e)

        ts = [threading.Thread(target=work) for _ in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not errors
        assert exe.compile_count - base == 1
        evs = self._events(mark)
        assert len(evs) == 1, [e["shape_key"] for e in evs]

    def test_aot_build_counts_as_inflight_for_hang_watch(self):
        """The wedged-dispatch hang watch reads dispatch_inflight();
        pre-AOT the first-step compile happened inside the dispatch
        in-flight window, so a wedged compile tripped it. The AOT
        build runs BEFORE the dispatch counters — it must still be
        visible, or a stuck compile hangs silently."""
        import contextlib

        import jax
        import jax.numpy as jnp
        exe = fluid.Executor()
        prog = fluid.Program()
        seen = []

        @contextlib.contextmanager
        def probe_ctx():
            # runs inside the lower+compile window
            seen.append(exe.dispatch_inflight())
            yield

        fn = exe._executable_for(
            ("probe-key",), (), "run", prog,
            lambda: jax.jit(lambda: jnp.zeros(())), lambda: (),
            compile_ctx=probe_ctx)
        assert fn is not None
        assert seen == [True], "build window invisible to hang watch"
        assert exe.dispatch_inflight() is False

    def test_persist_aval_drift_rebuilds_executable(self):
        """A persistable whose aval changed between calls (jit used to
        absorb this with a silent retrace) must rebuild the AOT
        executable instead of failing the dispatch."""
        main, startup, loss = _build_net()
        exe = fluid.Executor()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            exe.run(main, feed=_batch(8), fetch_list=[loss])
            n0 = exe.xla_compile_count
            wname = next(n for n in scope.local_var_names()
                         if n.endswith(".w_0"))
            w = scope.find_var(wname)
            scope.set_var(wname,
                          jnp.asarray(w).astype(jnp.bfloat16))
            out = exe.run(main, feed=_batch(8), fetch_list=[loss])
        assert np.isfinite(float(out[0]))
        assert exe.xla_compile_count == n0 + 1


# ---------------------------------------------------------------------------
# warm restart across processes (acceptance)
# ---------------------------------------------------------------------------

_WORKER = """
import json, os, sys
sys.path.insert(0, %(root)r)
import numpy as np
import paddle_tpu as fluid

main, startup = fluid.Program(), fluid.Program()
main.random_seed = 13
startup.random_seed = 13
with fluid.program_guard(main, startup):
    x = fluid.layers.data("x", shape=[8])
    label = fluid.layers.data("label", shape=[1], dtype="int64")
    h = fluid.layers.fc(x, size=16, act="relu")
    pred = fluid.layers.fc(h, size=4, act="softmax")
    loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
exe = fluid.Executor()
exe.run(startup)
rng = np.random.RandomState(0)
feed = {"x": rng.rand(8, 8).astype(np.float32),
        "label": rng.randint(0, 4, (8, 1)).astype(np.int64)}
out = None
for _ in range(3):
    out = exe.run(main, feed=feed, fetch_list=[loss])
t = exe.telemetry()
print("RESULT " + json.dumps({
    "loss": float(out[0]), "pid": os.getpid(),
    "xla_compiles": exe.xla_compile_count,
    "compiles": exe.compile_count,
    "cache": t["compile_cache"]}), flush=True)
"""


def _run_worker(tmp_path, role, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PADDLE_TPU_COMPILE_CACHE_DIR=str(tmp_path / "cc"),
               PADDLE_TPU_EVENT_JOURNAL=str(
                   tmp_path / ("events.%s.jsonl" % role)),
               PADDLE_TPU_ROLE=role)
    env.update(extra_env or {})
    out = subprocess.run(
        [sys.executable, "-c", _WORKER % {"root": ROOT}],
        capture_output=True, text=True, timeout=180, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    line = next(l for l in out.stdout.splitlines()
                if l.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


class TestWarmRestartAcceptance:
    def test_warm_restart_is_all_hits_zero_compiles(self, tmp_path):
        """Run the SAME program/shape in two processes sharing one
        cache dir: the restart must perform ZERO XLA compiles — every
        executable loads from the cache, the journal shows hits
        attributing the compile to the first process, and the result
        is bit-identical (seeds pinned)."""
        r1 = _run_worker(tmp_path, "replica-0")
        assert r1["xla_compiles"] == r1["compiles"] == 2
        assert r1["cache"]["stores"] == 2

        r2 = _run_worker(tmp_path, "replica-1")
        assert r2["xla_compiles"] == 0, r2
        assert r2["compiles"] == 2  # same per-shape accounting
        assert r2["cache"]["hits"] == 2
        assert r2["loss"] == r1["loss"]

        j1 = obs.read_journal(str(tmp_path / "events.replica-0.jsonl"))
        j2 = obs.read_journal(str(tmp_path / "events.replica-1.jsonl"))
        compiles1 = [e for e in j1 if e["kind"] == "executor_compile"]
        compiles2 = [e for e in j2 if e["kind"] == "executor_compile"]
        hits2 = [e for e in j2 if e["kind"] == "compile_cache_hit"]
        assert len(compiles1) == 2 and not compiles2
        assert len(hits2) == 2
        for h in hits2:
            assert h["origin_pid"] == r1["pid"]
            assert h["origin_role"] == "replica-0"
        # the hit and its origin compile share the canonical
        # fingerprint — the cross-process attribution key
        assert {h["fingerprint"] for h in hits2} == \
            {e["fingerprint"] for e in compiles1}

    def test_fleet_compiles_all_attributable(self, tmp_path):
        """2-replica fleet acceptance: every compile in either journal
        is one provenance record with a non-null miss reason, and
        compiles + hits account for every executable either process
        used."""
        import concurrent.futures as cf
        with cf.ThreadPoolExecutor(2) as pool:
            futs = [pool.submit(_run_worker, tmp_path,
                                "replica-%d" % i) for i in range(2)]
            results = [f.result() for f in futs]
        events = []
        for i in range(2):
            events += obs.read_journal(
                str(tmp_path / ("events.replica-%d.jsonl" % i)))
        compiles = [e for e in events
                    if e["kind"] == "executor_compile"]
        hits = [e for e in events if e["kind"] == "compile_cache_hit"]
        total_xla = sum(r["xla_compiles"] for r in results)
        assert len(compiles) == total_xla
        from paddle_tpu.executor import MISS_REASONS
        assert all(e.get("miss_reason") in MISS_REASONS
                   for e in compiles)
        assert all(e.get("fingerprint") for e in compiles)
        # every executable either compiled here or loaded from a
        # sibling's store
        assert len(compiles) + len(hits) == \
            sum(r["compiles"] for r in results)
        assert results[0]["loss"] == results[1]["loss"]


# ---------------------------------------------------------------------------
# doctor: recompile-storm culprit citation (satellite)
# ---------------------------------------------------------------------------

class TestDoctorCulprit:
    def _storm_events(self, n=12):
        evs = []
        for i in range(n):
            entry = "run" if i % 4 else "run_pipelined"
            shape = "x=float32[%d,8]" % (8 + i)
            if i % 4:
                shape = "x=float32[8,8]"
            evs.append(dict(kind="executor_compile", seq=i + 1,
                            role="trainer-0", t_wall=100.0 + i * 1.5,
                            entry=entry, shape_key=shape,
                            miss_reason="new_shape", nth=i))
        return evs

    def test_verdict_names_entry_and_shape_bucket(self):
        import doctor
        rep = doctor.diagnose(self._storm_events())
        assert rep["top"] == "recompile_storm"
        d = rep["diagnoses"][0]
        assert d["culprit"]["entry"] == "run"
        assert d["culprit"]["shape_key"] == "x=float32[8,8]"
        assert d["culprit"]["miss_reasons"] == {"new_shape": 12}
        assert "'run'" in d["summary"]
        assert "x=float32[8,8]" in d["summary"]
        assert "new_shape" in d["summary"]
        # evidence rows carry the provenance fields
        assert all("miss_reason" in c for c in d["evidence"])

    def test_culprit_counted_within_storm_window_only(self):
        """Historical compiles spread over hours must not outvote the
        burst actually driving the storm window."""
        import doctor
        old = [dict(kind="executor_compile", seq=i + 1, role="t",
                    t_wall=i * 300.0, entry="run_pipelined",
                    shape_key="old", miss_reason="new_shape", nth=i)
               for i in range(12)]  # 1 per 5 min: never a storm
        burst = [dict(kind="executor_compile", seq=100 + i, role="t",
                      t_wall=100000.0 + i, entry="run",
                      shape_key="hot", miss_reason="cache_cold",
                      nth=100 + i)
                 for i in range(10)]
        rep = doctor.diagnose(old + burst)
        d = next(x for x in rep["diagnoses"]
                 if x["name"] == "recompile_storm")
        assert d["culprit"]["entry"] == "run"
        assert d["culprit"]["shape_key"] == "hot"
        assert d["culprit"]["miss_reasons"] == {"cache_cold": 10}

    def test_expect_gate_via_cli(self, tmp_path):
        import doctor
        jpath = tmp_path / "events.jsonl"
        with open(str(jpath), "w") as f:
            for e in self._storm_events():
                f.write(json.dumps(e) + "\n")
        rc = doctor.main(["--journal", str(jpath),
                          "--expect", "recompile_storm"])
        assert rc == 0
        rc = doctor.main(["--journal", str(jpath),
                          "--expect", "overload"])
        assert rc == 1

    def test_pre_provenance_events_still_diagnose(self):
        """Events from a pre-PR11 journal (no shape_key/miss_reason)
        must still storm-detect, just without the shape citation."""
        import doctor
        evs = [dict(kind="executor_compile", seq=i + 1, role="t",
                    t_wall=100.0 + i, entry="run", nth=i)
               for i in range(12)]
        rep = doctor.diagnose(evs)
        assert rep["top"] == "recompile_storm"
        assert "compiles/min" in rep["diagnoses"][0]["summary"]


# ---------------------------------------------------------------------------
# journal interplay: ledger survives rotation (satellite)
# ---------------------------------------------------------------------------

class TestLedgerRotationInterplay:
    def test_compile_events_survive_keep_one_rotation(self, tmp_path):
        path = str(tmp_path / "events.jsonl")
        obs.configure_journal(path, max_bytes=20000)
        main, startup, loss = _build_net()
        exe = fluid.Executor()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            for b in (4, 8, 16):
                exe.run(main, feed=_batch(b), fetch_list=[loss])
        n_compiles = exe.compile_count  # startup + three shapes
        # pad filler events until exactly one rotation has happened
        for i in range(2000):
            obs.emit("filler", i=i, pad="x" * 64)
            if os.path.exists(path + ".1"):
                break
        assert os.path.exists(path + ".1"), "journal never rotated"
        obs.emit("filler_tail")
        merged = obs.read_journal(path)
        seqs = [e["seq"] for e in merged]
        assert seqs == sorted(seqs), "stitched journal not causal"
        compiles = [e for e in merged
                    if e["kind"] == "executor_compile"]
        assert len(compiles) == n_compiles == 4
        assert all(e["miss_reason"] for e in compiles)
        # the ledger's own ordering survives the stitch too
        nths = [e["nth"] for e in compiles]
        assert nths == sorted(nths)


# ---------------------------------------------------------------------------
# bench_diff: hit rate is higher-is-better (satellite)
# ---------------------------------------------------------------------------

class TestBenchDiffHitRate:
    def test_hit_rate_drop_flags_regression(self, tmp_path):
        import bench_diff
        r1, r2 = tmp_path / "B1.json", tmp_path / "B2.json"
        rows1 = [{"metric": "compile_cache_warmup", "value": 1.0,
                  "unit": "warm-restart hit rate"}]
        rows2 = [{"metric": "compile_cache_warmup", "value": 0.4,
                  "unit": "warm-restart hit rate"}]
        r1.write_text(json.dumps({"n": 1, "tail": "\n".join(
            json.dumps(r) for r in rows1)}))
        r2.write_text(json.dumps({"n": 2, "tail": "\n".join(
            json.dumps(r) for r in rows2)}))
        report = bench_diff.diff(
            bench_diff.load_rounds([str(r1), str(r2)]))
        flags = {(f["metric"], f["flag"]) for f in report["flags"]}
        assert ("compile_cache_warmup", "REGRESSION") in flags

    def test_hit_rate_rise_is_not_flagged(self, tmp_path):
        import bench_diff
        r1, r2 = tmp_path / "B1.json", tmp_path / "B2.json"
        r1.write_text(json.dumps({"n": 1, "tail": json.dumps(
            {"metric": "compile_cache_warmup", "value": 0.5,
             "unit": "warm-restart hit rate"})}))
        r2.write_text(json.dumps({"n": 2, "tail": json.dumps(
            {"metric": "compile_cache_warmup", "value": 1.0,
             "unit": "warm-restart hit rate"})}))
        report = bench_diff.diff(
            bench_diff.load_rounds([str(r1), str(r2)]))
        assert not report["flags"]


# ---------------------------------------------------------------------------
# serving warmup telemetry (satellite)
# ---------------------------------------------------------------------------

class TestServingWarmupTelemetry:
    def test_warmup_event_reports_compiles(self, tmp_path):
        from paddle_tpu import layers
        from paddle_tpu.serving import ServingConfig, ServingEngine
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 7
        with fluid.program_guard(main, startup):
            x = layers.data(name="x", shape=[16], dtype="float32")
            h = layers.fc(x, size=32, act="relu")
            pred = layers.fc(h, size=4, act="softmax")
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            mdir = str(tmp_path / "model")
            fluid.io.save_inference_model(mdir, ["x"], [pred], exe,
                                          main_program=main,
                                          scope=scope)
        evs0 = obs.journal_events(kind="serving_warmup")
        mark = evs0[-1]["seq"] if evs0 else 0
        eng = ServingEngine(mdir, ServingConfig(max_batch_size=8,
                                                max_queue_wait_us=2000))
        try:
            evs = obs.journal_events(kind="serving_warmup",
                                     since_seq=mark)
            assert len(evs) == 1
            ev = evs[0]
            assert ev["buckets"], ev
            assert ev["xla_compiles"] == len(ev["buckets"])
            assert ev["wall_seconds"] > 0
        finally:
            eng.shutdown()
