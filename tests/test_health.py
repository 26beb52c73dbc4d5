"""Fleet health plane tests: beacon/watchdog stall detection (no
false positives on a healthy run), declarative HealthRules over
registry deltas, flight-recorder blackbox dumps (incl. on SIGTERM),
the machine-readable /healthz verdict, the wedge acceptance scenarios
(stalled serving batcher, parked PS barrier), journal rotation,
tools/doctor.py auto-diagnosis, and tools/bench_diff.py."""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu import observability as obs
from paddle_tpu.observability import health
from paddle_tpu.observability.registry import MetricsRegistry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(ROOT, "tools")
sys.path.insert(0, TOOLS)

pytestmark = pytest.mark.health


def _wait_for(fn, timeout=8.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = fn()
        if v:
            return v
        time.sleep(interval)
    return None


@pytest.fixture
def clean_role():
    """Tests that stamp a role / blackbox dir must not leak them into
    the rest of the suite."""
    yield
    obs.set_role(None)
    health.set_blackbox_dir(None)


@pytest.fixture(autouse=True)
def _isolate_journal_ring():
    """The in-memory journal ring is process-wide, and the chaos
    scenarios this module runs emit kinds (replica_evicted, health,
    rpc_reconnect, ...) that LATER test modules wait on — e.g.
    test_serving_fleet's kill test polls journal_events(
    kind="replica_evicted") and must not break early on this
    module's stale events. Drop the ring after every test (seq
    counters are never rewound, so watermark-based consumers are
    unaffected)."""
    yield
    obs.clear_journal()


# ---------------------------------------------------------------------------
# beacon + watchdog core
# ---------------------------------------------------------------------------

class TestWatchdog:
    def test_stall_fires_within_deadline_and_clears(self):
        wd = health.Watchdog(role="t", interval_s=0.05)
        b = health.Beacon("probe")
        wd.watch("probe", beacon=b, deadline_s=0.2)
        try:
            v = _wait_for(lambda: (lambda x: x if x["state"] ==
                                   "unhealthy" else None)(
                                       wd.check_now()), timeout=3.0)
            assert v, "stall never fired"
            (p,) = v["problems"]
            assert p["reason"] == "stall:probe"
            assert p["kind"] == "stall"
            assert p["severity"] == "unhealthy"
            # verdict surfaced as a journal `health` event...
            evs = [e for e in obs.journal_events(kind="health")
                   if e.get("reason") == "stall:probe"
                   and e.get("action") == "raise"]
            assert evs and evs[-1]["severity"] == "unhealthy"
            # ...and as the health_state{role,reason} gauge
            reg = obs.registry()
            assert reg.gauge("health_state", role="t",
                             reason="stall:probe").value == 2.0
            assert reg.gauge("health_state", role="t",
                             reason="overall").value == 2.0
            # progress clears it (journal clear event + gauge reset)
            b.bump()
            v = wd.check_now()
            assert v["state"] == "healthy" and not v["problems"]
            assert any(e.get("action") == "clear" for e in
                       obs.journal_events(kind="health")
                       if e.get("reason") == "stall:probe")
            assert reg.gauge("health_state", role="t",
                             reason="stall:probe").value == 0.0
        finally:
            wd.stop()

    def test_no_false_positive_while_progressing(self):
        """A healthy loop that keeps bumping inside the deadline must
        never trip the watchdog, however long it runs."""
        wd = health.Watchdog(role="t", interval_s=0.03)
        b = health.Beacon("busy")
        wd.watch("busy", beacon=b, deadline_s=0.3)
        try:
            t_end = time.monotonic() + 1.0
            while time.monotonic() < t_end:
                b.bump()
                time.sleep(0.02)
                assert wd.check_now()["state"] == "healthy"
        finally:
            wd.stop()

    def test_pending_gate(self):
        """No work pending -> an idle beacon is healthy; pending work
        starts the stall clock."""
        wd = health.Watchdog(role="t", interval_s=0.05)
        b = health.Beacon("gated")
        pending = [False]
        wd.watch("gated", beacon=b, deadline_s=0.15,
                 pending_fn=lambda: pending[0])
        try:
            time.sleep(0.4)
            assert wd.check_now()["state"] == "healthy"
            pending[0] = True
            v = _wait_for(lambda: (lambda x: x if x["problems"]
                                   else None)(wd.check_now()),
                          timeout=3.0)
            assert v and v["problems"][0]["reason"] == "stall:gated"
            # the stall clock started when pending went TRUE, not at
            # the (much older) last bump
            assert v["problems"][0]["stalled_s"] < 2.0
        finally:
            wd.stop()

    def test_unwatch_removes(self):
        wd = health.Watchdog(role="t", interval_s=0.05)
        h = wd.watch("gone", beacon=health.Beacon("gone"),
                     deadline_s=0.05)
        time.sleep(0.15)
        assert wd.check_now()["problems"]
        wd.unwatch(h)
        assert not wd.check_now()["problems"]
        wd.stop()


class TestHealthRules:
    def test_recompile_storm_rate_above(self):
        reg = MetricsRegistry()
        wd = health.Watchdog(role="t", interval_s=999, registry_=reg)
        wd.add_rule(health.HealthRule.rate_above(
            "recompile_storm", "executor_compiles_total", per_s=2.0,
            window_s=5.0))
        c = reg.counter("executor_compiles_total")
        wd.check_now()
        assert wd.check_now()["state"] == "healthy"
        for _ in range(4):
            c.inc(5)
            time.sleep(0.05)
            v = wd.check_now()
        assert v["problems"] and \
            v["problems"][0]["reason"] == "recompile_storm"
        assert v["problems"][0]["severity"] == "degraded"
        wd.stop()

    def test_queue_saturation_gauge(self):
        reg = MetricsRegistry()
        wd = health.Watchdog(role="t", interval_s=999, registry_=reg)
        wd.add_rule(health.HealthRule.gauge_above(
            "queue_saturation", "serving_queue_depth", threshold=10))
        g = reg.gauge("serving_queue_depth", model="m")
        g.set(3)
        assert wd.check_now()["state"] == "healthy"
        g.set(12)
        v = wd.check_now()
        assert v["problems"][0]["reason"] == "queue_saturation"
        g.set(0)
        assert wd.check_now()["state"] == "healthy"
        wd.stop()

    def test_throughput_collapse_vs_rolling_baseline(self):
        reg = MetricsRegistry()
        wd = health.Watchdog(role="t", interval_s=999, registry_=reg)
        wd.add_rule(health.HealthRule.rate_collapse(
            "throughput_collapse", "executor_steps_total",
            frac=0.25, window_s=0.4, min_rate=10.0))
        c = reg.counter("executor_steps_total")
        # establish the baseline: steady fast progress
        for _ in range(10):
            c.inc(20)
            time.sleep(0.05)
            wd.check_now()
        assert wd.check_now()["state"] == "healthy"
        # collapse: counter freezes; windowed rate decays to ~0 while
        # the EWMA baseline remembers the established pace
        v = _wait_for(lambda: (lambda x: x if x["problems"]
                               else None)(wd.check_now()),
                      timeout=5.0, interval=0.1)
        assert v, "collapse never detected"
        assert v["problems"][0]["reason"] == "throughput_collapse"
        assert v["problems"][0]["baseline"] > 0
        wd.stop()


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_dump_contents(self, tmp_path):
        rec = health.FlightRecorder(role="boxtest",
                                    dir=str(tmp_path))
        obs.registry().counter("box_probe_total").inc(3)
        rec.sample()
        obs.emit("box_probe_event", x=1)
        parked = threading.Event()
        release = threading.Event()

        def park():
            parked.set()
            release.wait(10)

        t = threading.Thread(target=park, name="park-me",
                             daemon=True)
        t.start()
        parked.wait(5)
        try:
            path = rec.dump("unit-test", extra={"k": "v"})
            assert os.path.basename(path) == "blackbox.boxtest.json"
            box = json.load(open(path))
            assert box["reason"] == "unit-test"
            assert box["extra"] == {"k": "v"}
            # all-thread stacks include the parked thread at its park
            names = {s["name"]: "".join(s["frames"])
                     for s in box["stacks"]}
            assert "park-me" in names
            assert "release.wait" in names["park-me"]
            # journal tail + metric samples + beacon ages ride along
            assert any(e["kind"] == "box_probe_event"
                       for e in box["journal_tail"])
            assert len(box["metric_samples"]) == 1
            assert "box_probe_total" in box["metrics"]["counters"]
            assert isinstance(box["beacons"], dict)
        finally:
            release.set()

    def test_dump_without_dir_is_noop(self):
        rec = health.FlightRecorder(role="nodir", dir=None)
        assert rec.dump_path() is None
        assert rec.dump("whatever") is None

    def test_blackbox_dump_on_sigterm(self, tmp_path):
        """A SIGTERMed process leaves blackbox.<role>.json with its
        thread stacks and journal tail — the black-box contract for a
        killed replica/worker."""
        code = (
            "import sys, time, threading\n"
            "sys.path.insert(0, %r)\n"
            "from paddle_tpu.observability import health, journal\n"
            "journal.set_role('victim')\n"
            "rec = health.get_recorder()\n"
            "rec.set_dir(%r)\n"
            "assert rec.install_signal_handlers()\n"
            "journal.emit('victim_alive', pid=1)\n"
            "ev = threading.Event()\n"
            "threading.Thread(target=ev.wait, args=(60,),\n"
            "                 name='parked-worker',\n"
            "                 daemon=True).start()\n"
            "print('READY', flush=True)\n"
            "time.sleep(60)\n" % (ROOT, str(tmp_path)))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen([sys.executable, "-c", code],
                                stdout=subprocess.PIPE, env=env,
                                text=True)
        try:
            line = proc.stdout.readline()
            assert "READY" in line, line
            proc.send_signal(signal.SIGTERM)
            proc.wait(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()
        box_path = tmp_path / "blackbox.victim.json"
        assert box_path.exists(), list(tmp_path.iterdir())
        box = json.load(open(str(box_path)))
        assert box["reason"] == "SIGTERM"
        assert box["role"] == "victim"
        # all-thread capture: the main thread is there (its top
        # frames are the signal handler that took the dump — the
        # park site sits underneath), and the parked worker thread's
        # stack shows exactly where it waited
        stacks = {s["name"]: "".join(s["frames"])
                  for s in box["stacks"]}
        assert "MainThread" in stacks
        assert "parked-worker" in stacks
        assert "wait" in stacks["parked-worker"]
        assert any(e["kind"] == "victim_alive"
                   for e in box["journal_tail"])
        # the faulthandler C-level twin exists too (fires even when
        # no Python handler can run)
        assert (tmp_path / "blackbox.victim.stacks.txt").exists()


# ---------------------------------------------------------------------------
# /healthz verdict
# ---------------------------------------------------------------------------

class TestHealthz:
    def test_unknown_without_watchdog(self, monkeypatch):
        monkeypatch.setattr(health, "_WATCHDOG", None)
        code, v = health.healthz()
        assert code == 200 and v["state"] == "unknown"

    def test_healthz_scrape_healthy_and_503_on_stall(self,
                                                     monkeypatch):
        wd = health.Watchdog(role="hz", interval_s=999)
        monkeypatch.setattr(health, "_WATCHDOG", wd)
        b = health.Beacon("hz_probe")
        wd.watch("hz_probe", beacon=b, deadline_s=0.1)
        with obs.start_metrics_server() as srv:
            b.bump()
            r = urllib.request.urlopen(srv.url + "/healthz")
            assert r.status == 200
            v = json.loads(r.read().decode())
            assert v["state"] == "healthy"
            assert "hz_probe" in v["watches"]
            time.sleep(0.3)  # now stalled past the deadline
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(srv.url + "/healthz")
            assert ei.value.code == 503
            v = json.loads(ei.value.read().decode())
            assert v["state"] == "unhealthy"
            assert v["problems"][0]["reason"] == "stall:hz_probe"
        wd.stop()


# ---------------------------------------------------------------------------
# wedge acceptance: stalled serving batcher + parked PS barrier
# ---------------------------------------------------------------------------

def _save_mlp_model(tmp_path, in_dim=16, out_dim=4):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[in_dim], dtype="float32")
        h = layers.fc(x, size=8, act="relu")
        pred = layers.fc(h, size=out_dim, act="softmax")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        d = str(tmp_path / "model")
        fluid.io.save_inference_model(d, ["x"], [pred], exe,
                                      main_program=main, scope=scope)
    return d


@pytest.mark.chaos
class TestWedgeDetection:
    def test_stalled_batcher_verdict_and_blackbox(self, tmp_path,
                                                  clean_role):
        """The acceptance wedge: a batcher thread that neither dies
        nor dispatches while a request is queued must produce an
        unhealthy stall verdict within its deadline AND a
        blackbox.<role>.json holding all-thread stacks + journal
        tail."""
        from paddle_tpu.serving import ServingConfig, ServingEngine
        obs.set_role("serving-wedge")
        health.set_blackbox_dir(str(tmp_path))
        model_dir = _save_mlp_model(tmp_path)
        engine = ServingEngine(model_dir, ServingConfig(
            max_batch_size=8, max_queue_wait_us=500,
            hang_deadline_s=0.4))
        worker = engine._workers["default"]
        hold = threading.Event()

        def wedge(w, batch):
            hold.wait(20)

        worker._dispatch_hook = wedge
        t0 = time.monotonic()
        fut = engine.infer({"x": np.zeros((1, 16), np.float32)})
        reason = "stall:serving_batcher/default"
        wd = health.get_watchdog()
        v = _wait_for(lambda: (lambda x: x if any(
            p["reason"] == reason for p in x["problems"]) else None)(
                wd.check_now()), timeout=10.0)
        detected_after = time.monotonic() - t0
        try:
            assert v, "stalled batcher never detected"
            # detected within deadline + a couple of watchdog ticks
            assert detected_after < 5.0
            box_path = tmp_path / "blackbox.serving-wedge.json"
            assert box_path.exists(), \
                "stall verdict did not dump the black box"
            box = json.load(open(str(box_path)))
            assert box["reason"] == "watchdog:%s" % reason
            joined = "".join("".join(s["frames"])
                             for s in box["stacks"])
            assert "hold.wait" in joined  # the wedged frame is cited
            assert box["journal_tail"], "journal tail missing"
        finally:
            hold.set()
        fut.result(timeout=20)
        # progress clears the verdict
        assert _wait_for(lambda: not any(
            p["reason"] == reason
            for p in wd.check_now()["problems"])), \
            "verdict did not clear after the batcher resumed"
        engine.shutdown(drain=True, timeout=10)

    def test_parked_ps_barrier_verdict(self, clean_role):
        """A barrier parked past its stall deadline (quorum can never
        form: 1 of 2 trainers arrived, no leases armed) must raise an
        unhealthy verdict, and the shutdown release must clear the
        beacon's pending state."""
        from paddle_tpu.distributed.ps import ListenAndServ
        from paddle_tpu.distributed.rpc import RPCClient
        s = ListenAndServ(
            "127.0.0.1:0", {"w": np.zeros(2, np.float32)},
            lambda name, grad: None, n_trainers=2, sync_mode=True,
            barrier_stall_s=0.4)
        s.start()
        client = RPCClient(s.endpoint, deadline_s=15.0, trainer_id=0)
        errors = []

        def barrier_call():
            try:
                client.barrier("send")
            except Exception as e:
                errors.append(e)

        th = threading.Thread(target=barrier_call, daemon=True)
        th.start()
        reason = "stall:ps_barrier@%s" % s.endpoint
        wd = health.get_watchdog()
        v = _wait_for(lambda: (lambda x: x if any(
            p["reason"] == reason for p in x["problems"]) else None)(
                wd.check_now()), timeout=10.0)
        assert v, "parked barrier never detected"
        p = next(p for p in v["problems"] if p["reason"] == reason)
        assert p["severity"] == "unhealthy"
        s.shutdown()  # answers the waiter with BarrierAborted
        th.join(timeout=10)
        assert errors, "parked waiter was not released"
        client.close()
        # watch unregistered at shutdown: the verdict no longer
        # carries the barrier problem
        assert _wait_for(lambda: not any(
            p["reason"] == reason
            for p in wd.check_now()["problems"]))


# ---------------------------------------------------------------------------
# journal rotation (satellite)
# ---------------------------------------------------------------------------

class TestJournalRotation:
    def test_rotation_keeps_one_and_read_stitches(self, tmp_path):
        path = str(tmp_path / "events.jsonl")
        obs.configure_journal(path, max_bytes=4096)
        try:
            # emit until exactly one rotation fires, then a few more
            # into the fresh live file — with a single rotation the
            # stitched read must be lossless
            n = 0
            while not os.path.exists(path + ".1") and n < 80:
                obs.emit("rotation_probe", i=n, pad="x" * 80)
                n += 1
            assert os.path.exists(path + ".1"), \
                "rotation never fired"
            for _ in range(5):
                obs.emit("rotation_probe", i=n, pad="x" * 80)
                n += 1
        finally:
            obs.configure_journal(None)
        # keep-one: neither file grows much past the bound
        assert os.path.getsize(path) <= 4096 + 512
        assert os.path.getsize(path + ".1") <= 4096 + 512
        # read_journal stitches rotated + live into one contiguous,
        # seq-ordered stream covering every event emitted
        evs = [e for e in obs.read_journal(path)
               if e["kind"] == "rotation_probe"]
        assert len(evs) == n  # one rotation: nothing lost
        assert [e["i"] for e in evs] == list(range(n))
        seqs = [e["seq"] for e in evs]
        assert seqs == sorted(seqs)
        # include_rotated=False sees only the live tail
        live = [e for e in obs.read_journal(path,
                                            include_rotated=False)
                if e["kind"] == "rotation_probe"]
        assert 0 < len(live) < n


# ---------------------------------------------------------------------------
# doctor (offline auto-diagnosis)
# ---------------------------------------------------------------------------

class TestDoctor:
    def _ev(self, kind, seq, **kw):
        kw.setdefault("role", "tester")
        kw.setdefault("t_wall", float(seq))
        return dict(kind=kind, seq=seq, **kw)

    def test_trainer_eviction_named_with_seq_evidence(self):
        import doctor
        rep = doctor.diagnose([
            self._ev("trainer_evicted", 412, tid=1,
                     endpoint="h:7000", lease_timeout_s=0.6,
                     role="pserver-1"),
            self._ev("barrier_aborted", 413, tids=[1],
                     role="pserver-1"),
        ])
        assert rep["top"] == "trainer_eviction"
        d = rep["diagnoses"][0]
        assert "lease expired" in d["summary"]
        assert "BarrierAborted" in d["summary"]
        cited = {c["seq"] for c in d["evidence"]}
        assert 412 in cited and 413 in cited

    def test_pserver_restart_beats_network_flaky(self):
        import doctor
        evs = [self._ev("snapshot", 10, boundary=3,
                        endpoint="h:1", role="pserver-0")]
        evs += [self._ev("rpc_reconnect", 20 + i, endpoint="h:1",
                         reconnects=i + 1, role="trainer-0")
                for i in range(4)]
        evs.append(self._ev("phase_replay", 30, what="step",
                            role="trainer-0"))
        rep = doctor.diagnose(evs)
        assert rep["top"] == "pserver_restart"
        names = [d["name"] for d in rep["diagnoses"]]
        assert "network_flaky" in names  # present, ranked below
        assert "snapshot at seq 10" in rep["diagnoses"][0]["summary"]

    def test_reconnects_without_snapshot_is_network_flaky(self):
        import doctor
        evs = [self._ev("rpc_reconnect", i + 1, endpoint="h:%d" % i,
                        role="trainer-0") for i in range(5)]
        rep = doctor.diagnose(evs)
        assert rep["top"] == "network_flaky"

    def test_recompile_storm_rate(self):
        import doctor
        evs = [self._ev("executor_compile", i + 1, entry="run",
                        nth=i, t_wall=100.0 + i * 1.5)
               for i in range(12)]
        rep = doctor.diagnose(evs)
        assert rep["top"] == "recompile_storm"
        assert "compiles/min" in rep["diagnoses"][0]["summary"]

    def test_input_bound_from_metrics_snapshot(self):
        import doctor
        rep = doctor.diagnose(
            [], metrics=[{"gauges": {"input_stall_fraction": 0.41}}])
        assert rep["top"] == "input_bound"
        assert "0.41" in rep["diagnoses"][0]["summary"]

    def test_hang_from_health_event_and_blackbox(self):
        import doctor
        rep = doctor.diagnose(
            [self._ev("health", 9, action="raise",
                      severity="unhealthy",
                      reason="stall:serving_batcher/default",
                      detail="no progress for 1.2s",
                      role="serving-0")],
            blackboxes=[{"reason":
                         "watchdog:stall:serving_batcher/default",
                         "role": "serving-0", "_path": "bb.json",
                         "stacks": [{"name": "serving-batcher-default",
                                     "frames": ["  ...",
                                                "    hold.wait(20)"]}]
                         }])
        assert rep["top"] == "hang"
        assert rep["diagnoses"][0]["detail"]  # cites the parked frame
        assert "hold.wait" in rep["diagnoses"][0]["detail"]

    def test_cli_expect_gate(self, tmp_path):
        import doctor
        p = str(tmp_path / "j.jsonl")
        with open(p, "w") as f:
            f.write(json.dumps(self._ev(
                "trainer_evicted", 5, tid=0, endpoint="e",
                role="pserver-0")) + "\n")
        assert doctor.main(["--journal", p, "--json",
                            "--expect", "trainer_eviction"]) == 0
        assert doctor.main(["--journal", p,
                            "--expect", "pserver_restart"]) == 1


# ---------------------------------------------------------------------------
# chaos: doctor must name the injected fault for real scenarios
# ---------------------------------------------------------------------------

@pytest.mark.chaos
class TestChaosDoctor:
    def _args(self, steps, **kw):
        import argparse
        return argparse.Namespace(seed=0, steps=steps, **kw)

    @pytest.mark.slow
    def test_serving_kill_diagnosed(self):
        """Run the real serving_kill chaos scenario (3 replicas, 5%
        drop, replica 0 SIGKILLed mid-flight) and assert doctor names
        replica_failure from the journal alone, citing seq
        evidence.

        ``slow`` since PR 15 (tier-1 headroom trim, the PR 14
        discipline): the replica-SIGKILL fault class stays covered in
        tier-1 twice over — test_serving_fleet's ``-m chaos`` kill
        test (zero lost futures, eviction causality) and
        test_control's ``control_loop`` scenario, whose doctor gate is
        STRICTER than this one (replica-kill diagnosis AND the full
        remediation audit). The CLI chaos suite still runs this
        scenario with ``--verdict doctor``."""
        import chaos_run
        res = chaos_run._scenario_serving_kill(self._args(4))
        assert res["ok"], res
        doc = res["doctor"]
        assert doc["top"] == "replica_failure", doc
        assert doc["match"], doc
        assert any(c.get("seq") is not None
                   for c in doc["evidence"]), doc

    # tier-1 headroom (PR 18): full 2x2 restart chaos scenario (~53 s) -> slow;
    # doctor restart diagnosis stays via TestDoctor::
    # test_pserver_restart_beats_network_flaky and exact restart
    # trajectories via test_distributed_chaos.py::TestPServerKillRestart
    @pytest.mark.slow
    def test_restart_2x2_obs_diagnosed(self):
        """The 2x2 pserver kill+restart scenario must be diagnosed as
        pserver_restart (snapshot -> reconnect/replay evidence) —
        UNDER the 5% wire drop. This test used to run at drop_rate=0.0
        because an unlucky drop pattern could phase-lock the two
        trainers' barrier replays into a 360 s retry storm; the
        barrier replay-epoch fence (a replayed already-released
        barrier is re-acked, never re-parked into the next step's
        quorum — ``dup_barrier_ack``) plus jittered replay backoff
        eliminated that class, so the lossy-wire variant is back in
        tier-1. The scenario's own ok-verdict bounds the wall time
        (steps=3 keeps the tier-1 cost down; the CLI chaos suite runs
        the longer default)."""
        import chaos_run
        res = chaos_run._scenario_restart_2x2_obs(
            self._args(3, drop_rate=0.05))
        assert res["ok"], res
        doc = res["doctor"]
        assert doc["top"] == "pserver_restart", doc
        assert doc["match"], doc
        assert any(c.get("seq") is not None
                   for c in doc["evidence"]), doc


# ---------------------------------------------------------------------------
# bench_diff (satellite)
# ---------------------------------------------------------------------------

class TestBenchDiff:
    def test_hang_flagged_on_value_to_null(self, tmp_path):
        """A headline that measured in round 1 and came back null with
        an error in every later round — bench_diff must flag the
        value->null transition as HANG, loudly."""
        import bench_diff
        files = []
        for n in range(1, 4):
            row = {"metric": "transformer_base_train_throughput",
                   "unit": "tokens/sec/chip",
                   "value": 65804.0 if n == 1 else None}
            if n > 1:
                row["error"] = "watchdog: run exceeded its budget"
            f = tmp_path / ("BENCH_r%02d.json" % n)
            f.write_text(json.dumps({"n": n, "rc": 0, "parsed": row,
                                     "tail": json.dumps(row) + "\n"}))
            files.append(str(f))
        report = bench_diff.diff(bench_diff.load_rounds(files))
        hangs = [f for f in report["hangs"]
                 if f["metric"] == "transformer_base_train_throughput"]
        assert hangs, report["flags"]
        text = bench_diff.format_report(report)
        assert "HANG" in text
        # strict mode exits nonzero on the hang
        assert bench_diff.main(files + ["--strict", "--json"]) == 1

    def test_regression_and_recovery_flags(self, tmp_path):
        import bench_diff
        r1 = tmp_path / "BENCH_r01.json"
        r2 = tmp_path / "BENCH_r02.json"
        rows1 = [{"metric": "m_throughput", "value": 100.0,
                  "unit": "examples/sec"},
                 {"metric": "p99_latency", "value": 10.0,
                  "unit": "ms"},
                 {"metric": "dead_row", "value": None,
                  "error": "boom"}]
        rows2 = [{"metric": "m_throughput", "value": 50.0,
                  "unit": "examples/sec"},
                 {"metric": "p99_latency", "value": 30.0,
                  "unit": "ms"},
                 {"metric": "dead_row", "value": 5.0}]
        r1.write_text(json.dumps(
            {"n": 1, "tail": "\n".join(json.dumps(r)
                                       for r in rows1)}))
        r2.write_text(json.dumps(
            {"n": 2, "tail": "\n".join(json.dumps(r)
                                       for r in rows2)}))
        report = bench_diff.diff(
            bench_diff.load_rounds([str(r1), str(r2)]))
        flags = {(f["metric"], f["flag"]) for f in report["flags"]}
        assert ("m_throughput", "REGRESSION") in flags
        # lower-is-better heuristic: a latency RISE is the regression
        assert ("p99_latency", "REGRESSION") in flags
        assert ("dead_row", "RECOVERED") in flags

    def test_sparse_throughput_metrics_direction(self, tmp_path):
        """ISSUE 14 satellite: the sparse rows (rows/s throughput and
        cache hit rate) are registered HIGHER-is-better, both
        directions — a drop flags REGRESSION, a rise does not (the
        raw unit strings would otherwise trip the lower-is-better
        'rate/fraction' heuristics)."""
        import bench_diff

        def write(path, n, rps, hit):
            rows = [{"metric": "sparse_embedding_throughput",
                     "value": rps,
                     "unit": "rows/s (zipf0.9, cache+q8)"},
                    {"metric": "sparse_embedding_throughput_mix",
                     "library": "zipf0.9/cache/q8", "value": hit,
                     "unit": "cache hit rate fraction"}]
            path.write_text(json.dumps(
                {"n": n, "tail": "\n".join(json.dumps(r)
                                           for r in rows)}))

        r1 = tmp_path / "BENCH_r01.json"
        r2 = tmp_path / "BENCH_r02.json"
        # direction 1: a DROP in rows/s and hit rate is a regression
        write(r1, 1, 50000.0, 0.85)
        write(r2, 2, 20000.0, 0.40)
        report = bench_diff.diff(
            bench_diff.load_rounds([str(r1), str(r2)]))
        flags = {(f["metric"], f["flag"]) for f in report["flags"]}
        assert ("sparse_embedding_throughput", "REGRESSION") in flags
        assert ("sparse_embedding_throughput_mix[zipf0.9/cache/q8]",
                "REGRESSION") in flags
        # direction 2: a RISE reads as an improvement, no flag
        write(r1, 1, 20000.0, 0.40)
        write(r2, 2, 50000.0, 0.85)
        report = bench_diff.diff(
            bench_diff.load_rounds([str(r1), str(r2)]))
        assert not report["regressions"], report["flags"]


# ---------------------------------------------------------------------------
# singleton-lock reentrancy (PR 11 hardening)
# ---------------------------------------------------------------------------

class TestSingletonReentrancy:
    def test_accessors_safe_under_singleton_lock(self):
        """Regression for the known `_SINGLETON_MU` pitfall: the
        singleton accessors must be callable while the lock is already
        held by the same thread (a future watchdog/recorder callback
        reaching back into the accessors is exactly this shape). With
        the old non-reentrant Lock this thread parks forever — the
        deadlock that only ever surfaced in the CLI path, because
        pytest happened to create the recorder first."""
        done = []

        def inner():
            with health._SINGLETON_MU:
                health.get_recorder()
                health.get_watchdog()
            done.append(True)

        t = threading.Thread(target=inner, daemon=True)
        t.start()
        t.join(timeout=10)
        assert done, ("health singleton accessors deadlocked while "
                      "_SINGLETON_MU was held by the calling thread")

    def test_get_watchdog_still_singleton(self):
        wd1 = health.get_watchdog()
        wd2 = health.get_watchdog()
        assert wd1 is wd2
        assert health.get_recorder() in wd1._recorders
