"""Unified telemetry plane tests: MetricsRegistry, event journal,
trace correlation, /metrics export, launcher role stamping, and the
obs_dump / trace_merge tools."""

import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, profiler
from paddle_tpu import observability as obs
from paddle_tpu.observability.registry import MetricsRegistry

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")
sys.path.insert(0, TOOLS)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counter_gauge_histogram(self):
        reg = MetricsRegistry()
        c = reg.counter("reqs", model="m")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        # memoized: same labels -> same object; new labels -> new series
        assert reg.counter("reqs", model="m") is c
        assert reg.counter("reqs", model="n") is not c
        g = reg.gauge("depth")
        g.set(7)
        assert g.value == 7.0
        h = reg.histogram("lat", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        snap = h.snapshot()
        assert snap["count"] == 3 and snap["counts"] == [1, 1, 1]
        assert h.quantile(0.5) == 1.0

    def test_kind_collision_rejected(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x")

    def test_prometheus_text(self):
        reg = MetricsRegistry()
        reg.counter("a_total", role="t0").inc(3)
        reg.gauge("q").set(1.5)
        h = reg.histogram("lat_seconds", buckets=(0.1,))
        h.observe(0.05)
        h.observe(0.2)
        text = reg.prometheus_text()
        assert "# TYPE a_total counter" in text
        assert 'a_total{role="t0"} 3' in text
        assert "q 1.5" in text
        assert 'lat_seconds_bucket{le="0.1"} 1' in text
        assert 'lat_seconds_bucket{le="+Inf"} 2' in text
        assert "lat_seconds_count 2" in text

    def test_snapshot_shape(self):
        reg = MetricsRegistry()
        reg.counter("c", a="b").inc()
        snap = reg.snapshot()
        assert snap["counters"] == {'c{a="b"}': 1.0}

    def test_disabled_stubs_mutations(self):
        c = obs.registry().counter("test_disabled_probe")
        c.reset()
        with obs.disabled():
            c.inc(5)
            ev = obs.emit("should_not_exist")
        c.inc(1)
        assert c.value == 1.0
        assert ev is None
        assert not obs.journal_events(kind="should_not_exist")


class TestProfilerCounters:
    def test_bump_counter_is_registry_backed(self):
        profiler.bump_counter("test_bump_probe", 2.0)
        assert obs.registry().counter("test_bump_probe").value >= 2.0
        assert profiler.counter_values()["test_bump_probe"] >= 2.0

    def test_reset_profiler_keeps_counters(self):
        """Regression (the reset_profiler footgun): span resets must
        not clear the always-on counters stall accounting and bench
        probes accumulate into."""
        profiler.reset_counters()
        profiler.bump_counter("test_reset_probe", 1.5)
        profiler.reset_profiler()
        assert profiler.counter_values()["test_reset_probe"] == 1.5
        profiler.reset_counters()
        assert profiler.counter_values()["test_reset_probe"] == 0.0


# ---------------------------------------------------------------------------
# journal
# ---------------------------------------------------------------------------

class TestJournal:
    def test_emit_schema_and_filtering(self):
        obs.set_role("trainer-9")
        try:
            e1 = obs.emit("test_ev_a", foo=1)
            e2 = obs.emit("test_ev_b", bar="x")
            assert e1["role"] == "trainer-9" and e1["pid"] == os.getpid()
            assert e2["seq"] > e1["seq"]
            assert e1["t_wall"] > 0 and e1["t_mono"] > 0
            got = obs.journal_events(kind="test_ev_b",
                                     since_seq=e1["seq"])
            assert [e["bar"] for e in got] == ["x"]
        finally:
            obs.set_role(None)

    def test_core_keys_win_over_fields(self):
        e = obs.emit("test_ev_core", seq="forged", pid="forged")
        assert e["kind"] == "test_ev_core"
        assert isinstance(e["seq"], int)
        assert e["pid"] == os.getpid()

    def test_sink_jsonl_and_torn_tail(self, tmp_path):
        path = str(tmp_path / "j.jsonl")
        obs.configure_journal(path)
        try:
            obs.emit("test_sink", n=1)
            obs.emit("test_sink", n=2)
        finally:
            obs.configure_journal(None)
        with open(path, "a") as f:
            f.write('{"kind": "torn')  # killed-process tail
        events = obs.read_journal(path)
        assert [e["n"] for e in events] == [1, 2]

    def test_concurrent_emit_file_order_is_seq_order(self, tmp_path):
        path = str(tmp_path / "c.jsonl")
        obs.configure_journal(path)
        try:
            def pump(k):
                for i in range(50):
                    obs.emit("test_conc", worker=k, i=i)
            ths = [threading.Thread(target=pump, args=(k,))
                   for k in range(4)]
            for t in ths:
                t.start()
            for t in ths:
                t.join()
        finally:
            obs.configure_journal(None)
        seqs = [e["seq"] for e in obs.read_journal(path)
                if e["kind"] == "test_conc"]
        assert len(seqs) == 200
        assert seqs == sorted(seqs)

    def test_env_role(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_ROLE", "pserver-3")
        assert obs.get_role() == "pserver-3"


# ---------------------------------------------------------------------------
# trace correlation
# ---------------------------------------------------------------------------

class TestTrace:
    def test_span_nesting_inherits_trace(self):
        with obs.span("outer") as (tr, sp):
            assert obs.current_span() == (tr, sp)
            with obs.span("inner") as (tr2, sp2):
                assert tr2 == tr and sp2 != sp
        assert obs.current_span() == (None, None)

    def test_wire_token_roundtrip(self):
        tok = obs.wire_token("abc", "def")
        assert obs.parse_wire_token(tok) == ("abc", "def")
        assert obs.parse_wire_token(None) == (None, None)
        assert obs.wire_token(None, "x") is None

    def test_attach_crosses_threads(self):
        got = []
        with obs.span("parent") as ctx:
            def worker():
                with obs.attach(ctx):
                    got.append(obs.current_span())
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        assert got == [ctx]

    def test_rpc_client_server_spans_share_trace_id(self):
        """The wire carries the client span's ids; the server handler
        span adopts the trace and records the client span as parent —
        the cross-process correlation seam, in-process."""
        from paddle_tpu.distributed.rpc import RPCClient, RPCServer
        srv = RPCServer("127.0.0.1:0")
        srv.register("GET", lambda name, payload: b"hi")
        srv.start()
        profiler.reset_profiler()
        profiler.start_profiler("CPU")
        try:
            c = RPCClient(srv.endpoint, timeout_s=10, trainer_id=4)
            assert c.call("GET", "thing") == b"hi"
            c.close()
            time.sleep(0.1)  # the server span lands from its thread
        finally:
            profiler._enabled = False  # silent stop (no table print)
            srv.shutdown()
        evs = list(profiler._events)
        client = [e for e in evs if e.name == "rpc_client:GET"]
        server = [e for e in evs if e.name == "rpc_server:GET"]
        assert client and server
        assert client[0].args["trace"] == server[0].args["trace"]
        assert server[0].args["parent_span"] == client[0].args["span"]
        assert server[0].args["trainer_id"] == 4

    def test_wire_meta_unpack(self):
        from paddle_tpu.distributed.rpc import (pack_wire_name,
                                                unpack_wire_meta,
                                                unpack_wire_name)
        w = pack_wire_name("v", 2, 9, trace="aa-bb")
        assert unpack_wire_meta(w) == ("v", 2, 9, "aa-bb")
        # 3-tuple parser (every existing handler) ignores the token
        assert unpack_wire_name(w) == ("v", 2, 9)
        # trace without tid/seq
        w2 = pack_wire_name("v", trace="aa-bb")
        assert unpack_wire_meta(w2) == ("v", None, None, "aa-bb")


# ---------------------------------------------------------------------------
# /metrics export
# ---------------------------------------------------------------------------

class TestMetricsServer:
    def test_endpoints(self):
        obs.registry().counter("test_http_probe").inc(4)
        obs.emit("test_http_event")
        with obs.start_metrics_server() as srv:
            txt = urllib.request.urlopen(
                srv.url + "/metrics").read().decode()
            assert "test_http_probe 4" in txt
            j = json.loads(urllib.request.urlopen(
                srv.url + "/journal").read().decode())
            assert any(e["kind"] == "test_http_event" for e in j)
            # /healthz is the health plane's machine-readable verdict
            # now (observability/health.py): JSON state, 200 unless
            # an armed watchdog reports unhealthy
            hz = urllib.request.urlopen(srv.url + "/healthz")
            assert hz.status == 200
            verdict = json.loads(hz.read().decode())
            assert verdict["state"] in ("unknown", "healthy",
                                        "degraded")
            assert "role" in verdict
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(srv.url + "/nope")


# ---------------------------------------------------------------------------
# island integrations
# ---------------------------------------------------------------------------

class TestIslandIntegration:
    def test_engine_stats_mirror(self):
        from paddle_tpu.serving.metrics import EngineStats
        reg = obs.registry()
        st = EngineStats(window=16, model="test_mirror_model")
        st.record_request(0.01)
        st.record_batch(rows=3, bucket=4)
        st.count("rejected", 2)
        assert reg.counter("serving_requests_total",
                           model="test_mirror_model",
                           outcome="completed").value == 1
        assert reg.counter("serving_requests_total",
                           model="test_mirror_model",
                           outcome="rejected").value == 2
        assert reg.counter("serving_rows_total",
                           model="test_mirror_model").value == 3
        assert reg.histogram("serving_latency_seconds",
                             model="test_mirror_model").count == 1
        # the snapshot surface is unchanged
        snap = st.snapshot()
        assert snap["completed"] == 1 and snap["rejected"] == 2

    def test_executor_telemetry_and_compile_journal(self):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 1
        with fluid.program_guard(main, startup):
            x = layers.data("x", shape=[4, 8],
                            append_batch_size=False)
            loss = layers.reduce_sum(layers.fc(x, size=2))
            fluid.optimizer.SGD(0.1).minimize(loss)
        scope = fluid.core.Scope()
        exe = fluid.Executor()
        mark = obs.journal_events()[-1]["seq"] \
            if obs.journal_events() else 0
        with fluid.scope_guard(scope):
            exe.run(startup)
            xv = np.random.RandomState(0).rand(4, 8) \
                .astype(np.float32)
            for _ in range(3):
                exe.run(main, feed={"x": xv}, fetch_list=[loss])
        t = exe.telemetry(scope=scope)
        assert t["steps"] == 4 and t["dispatches"] == 4
        assert t["compiles"] == 2  # startup + main
        # host seconds of the entry-point calls, in phases; what
        # divided steps by ENQUEUE seconds is gone
        assert "steps_per_s" not in t and "step_time_ms" not in t
        assert t["entry_seconds_total"] >= (
            t["prepare_seconds_total"] + t["dispatch_seconds_total"]
            + t["settle_seconds_total"]) - 1e-5
        assert min(t["prepare_seconds_total"],
                   t["dispatch_seconds_total"],
                   t["settle_seconds_total"]) > 0
        assert t["build_phases"]["trace_lower_seconds"] > 0
        assert t["anomaly_skipped_steps"] == 0.0
        compiles = obs.journal_events(kind="executor_compile",
                                      since_seq=mark)
        assert len(compiles) == 2
        assert "x" in compiles[-1]["shapes"]


# ---------------------------------------------------------------------------
# launcher role stamping
# ---------------------------------------------------------------------------

class TestLauncherRoles:
    def test_env_stamping(self, tmp_path):
        from paddle_tpu.distributed import launch as L
        args = L._parse_args([
            "--nproc_per_node=2", "--server_num=2",
            "--journal_dir", str(tmp_path), "t.py"])
        trainers = L.get_cluster_env(args)
        servers = L.get_server_env(args)
        assert [e["PADDLE_TPU_ROLE"] for e in trainers] == \
            ["trainer-0", "trainer-1"]
        assert [e["PADDLE_TPU_ROLE"] for e in servers] == \
            ["pserver-0", "pserver-1"]
        assert servers[0]["PADDLE_TRAINING_ROLE"] == "PSERVER"
        assert trainers[0]["PADDLE_TRAINING_ROLE"] == "TRAINER"
        assert servers[1]["PADDLE_PSERVER_ID"] == "1"
        paths = {e["PADDLE_TPU_EVENT_JOURNAL"]
                 for e in trainers + servers}
        assert len(paths) == 4  # four distinct journal paths
        assert all(str(tmp_path) in p for p in paths)

    def test_2x2_launch_writes_four_distinct_journals(self, tmp_path):
        """End to end: a 2-trainer x 2-pserver launch gives each
        worker its own role + journal path; the workers' journal
        files are distinct and role-attributable. (The script writes
        one event line itself — stdlib only, so the test doesn't pay
        four heavyweight interpreter boots.)"""
        from paddle_tpu.distributed import launch as L
        script = tmp_path / "w.py"
        script.write_text(
            "import json, os\n"
            "role = os.environ['PADDLE_TPU_ROLE']\n"
            "path = os.environ['PADDLE_TPU_EVENT_JOURNAL']\n"
            "with open(path, 'a') as f:\n"
            "    f.write(json.dumps({'kind': 'hello', 'role': role,"
            " 'seq': 1}) + '\\n')\n"
            "print('worker', role, 'done')\n")
        jdir = tmp_path / "journals"
        args = L._parse_args([
            "--nproc_per_node=2", "--server_num=2",
            "--journal_dir", str(jdir),
            "--log_dir", str(tmp_path / "logs"), str(script)])
        assert L.launch(args, poll_interval_s=0.05) == 0
        journals = sorted(p.name for p in jdir.glob("events.*.jsonl"))
        assert journals == ["events.pserver-0.jsonl",
                            "events.pserver-1.jsonl",
                            "events.trainer-0.jsonl",
                            "events.trainer-1.jsonl"]
        roles = set()
        for p in jdir.glob("events.*.jsonl"):
            events = obs.read_journal(str(p))
            assert len(events) == 1
            roles.add(events[0]["role"])
        assert len(roles) == 4

    def test_prefixed_stdout_without_log_dir(self, tmp_path, capfd):
        from paddle_tpu.distributed import launch as L
        script = tmp_path / "w.py"
        script.write_text("print('hello from worker')\n")
        args = L._parse_args(["--nproc_per_node=1", str(script)])
        assert L.launch(args, poll_interval_s=0.05) == 0
        out = capfd.readouterr().out
        assert "[trainer-0] hello from worker" in out


# ---------------------------------------------------------------------------
# tools
# ---------------------------------------------------------------------------

class TestObsDump:
    def test_dump_json(self, tmp_path):
        import obs_dump
        jpath = str(tmp_path / "events.trainer-0.jsonl")
        obs.configure_journal(jpath)
        try:
            obs.set_role("trainer-0")
            obs.emit("step_done", step=1)
            obs.emit("step_done", step=2)
        finally:
            obs.set_role(None)
            obs.configure_journal(None)
        mpath = str(tmp_path / "metrics.txt")
        reg = MetricsRegistry()
        reg.counter("dump_probe", role="t").inc(9)
        with open(mpath, "w") as f:
            f.write(reg.prometheus_text())
        out = obs_dump.dump(metrics_src=mpath, journal_paths=[jpath],
                            tail=1)
        assert out["metrics"]["series"]['dump_probe{role="t"}'] == 9.0
        assert out["metrics"]["types"]["dump_probe"] == "counter"
        js = out["journals"][jpath]
        assert js["events"] == 2 and js["role"] == "trainer-0"
        assert js["kinds"] == {"step_done": 2}
        assert len(out["tail"]) == 1 and out["tail"][0]["step"] == 2
        # the whole dump is JSON-serializable (the CLI contract)
        json.dumps(out)


class TestTraceMerge:
    def _trace(self, role, wall0, spans):
        """Synthetic per-process chrome trace: wall time of ts=0 is
        ``wall0`` (clock_sync at ts=1000)."""
        evs = [{"name": "process_name", "ph": "M", "pid": 0,
                "args": {"name": "host"}},
               {"name": "clock_sync", "ph": "M", "pid": 0,
                "args": {"wall_time_s": wall0 + 0.001,
                         "trace_ts_us": 1000.0, "role": role}}]
        evs += spans
        return {"traceEvents": evs}

    def test_merge_offsets_and_flow_links(self, tmp_path):
        import trace_merge

        # server clock runs 5s AHEAD of the trainer clock
        offset = 5.0
        client = {"name": "rpc_client:SEND", "ph": "X", "cat": "host",
                  "ts": 100.0, "dur": 50.0, "pid": 0, "tid": 1,
                  "args": {"trace": "t1", "span": "c1",
                           "endpoint": "e"}}
        server = {"name": "rpc_server:SEND", "ph": "X", "cat": "host",
                  "ts": 700.0, "dur": 20.0, "pid": 0, "tid": 2,
                  "args": {"trace": "t1", "parent_span": "c1",
                           "span": "s1"}}
        t_train = self._trace("trainer-0", 1000.0, [client])
        t_serv = self._trace("pserver-0", 1000.0 + offset, [server])
        p1 = tmp_path / "trainer.json"
        p2 = tmp_path / "pserver.json"
        p1.write_text(json.dumps(t_train))
        p2.write_text(json.dumps(t_serv))

        # paired heartbeat events: trainer t0/t1 bracket the beat, the
        # server's receive timestamp carries its (shifted) clock
        j1 = tmp_path / "j_trainer.jsonl"
        j2 = tmp_path / "j_pserver.jsonl"
        j1.write_text(json.dumps({
            "kind": "heartbeat_rtt", "endpoint": "e", "tid": 0,
            "beat": 1, "t0_wall": 1000.0, "t1_wall": 1000.2,
            "role": "trainer-0", "seq": 1}) + "\n")
        j2.write_text(json.dumps({
            "kind": "heartbeat_recv", "endpoint": "e", "tid": 0,
            "beat": 1, "t_wall": 1000.1 + offset,
            "role": "pserver-0", "seq": 1}) + "\n")

        out_path = str(tmp_path / "merged.json")
        merged, report = trace_merge.merge(
            [str(p1), str(p2)], [str(j1), str(j2)], out_path)
        assert report["processes"] == 2
        assert report["links"] == 1
        assert abs(report["offsets_s"]["pserver-0"] - offset) < 1e-6
        data = json.load(open(out_path))
        evs = data["traceEvents"]
        # offset correction: both spans land on the SAME timeline —
        # the server span is NOT 5s away from the client span
        c = next(e for e in evs if e["name"] == "rpc_client:SEND")
        s = next(e for e in evs if e["name"] == "rpc_server:SEND")
        assert abs(s["ts"] - c["ts"]) < 1e4  # < 10 ms apart
        assert c["pid"] != s["pid"]  # distinct process tracks
        flows = [e for e in evs if e.get("cat") == "rpc_flow"]
        assert {e["ph"] for e in flows} == {"s", "f"}
        names = {e["args"]["name"] for e in evs
                 if e.get("name") == "process_name"}
        assert any("trainer-0" in n for n in names)
        assert any("pserver-0" in n for n in names)

    def test_merge_without_journals_trusts_wall_clock(self, tmp_path):
        import trace_merge
        sp = {"name": "x", "ph": "X", "ts": 0.0, "dur": 1.0,
              "pid": 0, "tid": 0}
        p1 = tmp_path / "a.json"
        p1.write_text(json.dumps(self._trace("r0", 50.0, [sp])))
        _, report = trace_merge.merge([str(p1)], [],
                                      str(tmp_path / "m.json"))
        assert report["processes"] == 1 and report["links"] == 0
        assert report["offsets_s"] == {}
