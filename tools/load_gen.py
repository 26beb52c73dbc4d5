#!/usr/bin/env python
"""Load generator for the serving engine: open- or closed-loop traffic
against a saved inference model (or a built-in synthetic MLP), emitting
ONE JSON latency report — the serving analog of bench.py's one-line
contract.

- ``--mode open``: arrivals at a fixed offered QPS regardless of
  completions (the SLO-honest protocol: queueing delay shows up in the
  latencies instead of throttling the arrival process — avoids
  coordinated omission).
- ``--mode closed``: ``--concurrency`` workers each keep exactly one
  request in flight (classic throughput probe; latencies flatter).
- ``--mode ramp``: stepped-concurrency closed loop — one closed-loop
  step per level in ``--ramp`` (e.g. 1,2,4,8), each ``--step-duration``
  seconds, reported per step (where does throughput saturate? where
  does p99 leave the SLO?).

``--replicas N`` drives a FLEET instead of the in-process engine: N
``serving/replica.py`` subprocesses behind a ``ServingRouter``
(``--policy least_loaded|round_robin``), with per-replica attribution
(requests, p99, sheds) in the JSON report.

Examples
--------
# synthetic model, open loop at 200 QPS for 5 s, ragged batches 1..8
python tools/load_gen.py --synthetic --mode open --qps 200 --duration 5

# a saved model dir, closed loop with 16 workers
python tools/load_gen.py --model-dir /tmp/mnist_model --mode closed \
    --concurrency 16 --duration 10

# 4-replica fleet, stepped ramp
python tools/load_gen.py --synthetic --replicas 4 --mode ramp \
    --ramp 2,4,8,16 --step-duration 3

Exit code 0 when the run completed and every non-rejected request
resolved; 1 otherwise. The last stdout line is the JSON report.
"""

import argparse
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402


def zipf_ids(rng, vocab, size, skew=0.9, perm=None):
    """Bounded Zipf key stream: P(rank r) ∝ r^-skew over ``vocab``
    ids, rank->id scrambled by ``perm`` so hot keys scatter across
    hash shards (a real CTR id space has no rank order). CANONICAL
    implementation — bench.py's sparse rows, the train-and-serve chaos
    scenario, and ``--sparse-table`` below all draw their traffic from
    this one function, so their skew profiles are comparable by
    construction."""
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -float(skew)
    p /= p.sum()
    ranks = rng.choice(vocab, size=size, p=p)
    return (perm[ranks] if perm is not None else ranks) \
        .astype(np.int64)


def sparse_feed_maker(rng, vocab, slots, batch_min, batch_max,
                      skew=0.9, perm=None):
    """Feed maker for the sparse serving plane: each call returns
    ``({"ids": int64 [b, slots]}, b)`` with ids drawn from the shared
    Zipf stream — the sparse analog of ``_feed_maker`` (same
    ``(feed, n)`` contract, so ``run_open_loop``/``run_closed_loop``/
    ``run_ramp`` drive it unchanged)."""
    def make_feed():
        b = int(rng.randint(batch_min, batch_max + 1))
        ids = zipf_ids(rng, vocab, b * slots, skew=skew,
                       perm=perm).reshape(b, slots)
        return {"ids": ids}, b
    return make_feed


def build_sparse_stack(vocab, dim, shards=2, lr=0.5, seed=9,
                       staleness_bound=8, staleness_action="repull",
                       device_rows=None, cache_bytes=None,
                       snapshot_dir=None, replica_kw=None,
                       retry=None):
    """One in-process train-AND-serve sparse stack: ``shards``
    SparsePServers hosting one LargeScaleKV table, a
    SparseServingReplica over them, and a ServingRouter in front —
    plus a trainer-side LookupServiceClient pushing into the SAME
    tables. Returns ``(router, replicas, servers, trainer_client,
    stop)``; the chaos scenario and ``--sparse-table`` both build
    their worlds through this so they cannot drift apart."""
    from paddle_tpu.distributed import (LargeScaleKV,
                                        LookupServiceClient,
                                        SparsePServer)
    from paddle_tpu.serving import (RouterConfig, SparseServingConfig,
                                    SparseServingReplica,
                                    ServingRouter)

    servers = []
    for i in range(shards):
        tables = {"emb": LargeScaleKV(dim=dim, lr=lr, seed=seed)}
        kw = {}
        if snapshot_dir is not None:
            kw = {"snapshot_dir": os.path.join(snapshot_dir,
                                               "shard%d" % i),
                  "snapshot_every": 1}
        servers.append(SparsePServer("127.0.0.1:0", tables,
                                     **kw).start())
    eps = [s.endpoint for s in servers]
    cfg = SparseServingConfig(
        max_staleness_steps=staleness_bound,
        staleness_action=staleness_action, retry=retry,
        device_rows=device_rows
        if device_rows is not None else max(64, vocab // 4),
        cache_bytes=cache_bytes
        if cache_bytes is not None else vocab * dim * 4 // 2)
    rep = SparseServingReplica("emb", eps, dim, config=cfg,
                               **(replica_kw or {})).start()
    router = ServingRouter([rep.endpoint], RouterConfig(
        lease_timeout_s=2.0, heartbeat_interval_s=0.2,
        rpc_deadline_s=5.0, connect_timeout_s=5.0, max_retries=5))
    trainer = LookupServiceClient("emb", eps, dim=dim, trainer_id=0,
                                  push_q8=True, retry=retry,
                                  write_policy="none")

    def stop():
        try:
            router.shutdown()
        finally:
            rep.shutdown()
            trainer.close()
            for s in servers:
                s.shutdown()

    return router, [rep], servers, trainer, stop


def build_synthetic_model(dirname, hidden=32, seed=3):
    """Train-free 64->hidden->8 softmax MLP saved as an inference
    model — enough to exercise batching/bucketing without a real
    checkpoint. ``hidden`` scales per-request compute (the fleet
    scaling bench uses a wider net so replica compute, not router
    overhead, is the bottleneck being scaled)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            x = layers.data(name="x", shape=[64], dtype="float32")
            h = layers.fc(x, size=hidden, act="relu")
            pred = layers.fc(h, size=8, act="softmax")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        fluid.io.save_inference_model(dirname, ["x"], [pred], exe,
                                      main_program=main, scope=scope)
    return dirname


def _feed_maker(engine, rng, batch_min, batch_max):
    """Random ragged feed built from the model signature (sidecar or
    live derivation) — batch dim in [batch_min, batch_max]."""
    worker = engine._worker(None)
    return _feed_maker_from_sig(worker.predictor.signature, rng,
                                batch_min, batch_max)


def _feed_maker_from_sig(sig, rng, batch_min, batch_max):
    """Signature-driven twin of ``_feed_maker`` for targets without a
    local predictor (the fleet router: the signature comes from the
    model dir's ``__signature__.json`` sidecar)."""

    def make():
        n = int(rng.randint(batch_min, batch_max + 1))
        feed = {}
        for inp in sig["inputs"]:
            dims = list(inp["shape"])
            if inp["dynamic_dims"]:
                dims[inp["dynamic_dims"][0]] = n
            else:
                dims = [n] + dims
            dt = np.dtype(inp["dtype"])
            if np.issubdtype(dt, np.floating):
                feed[inp["name"]] = rng.rand(*dims).astype(dt)
            else:
                feed[inp["name"]] = np.zeros(dims, dt)
        return feed, n

    return make


def run_open_loop(engine, make_feed, qps, duration_s, deadline_ms):
    """Fixed-rate arrivals; every submitted future is awaited at the
    end so queueing delay lands in the latency record, not in a
    throttled arrival process."""
    from paddle_tpu.serving import ServerOverloaded

    interval = 1.0 / qps
    t_end = time.monotonic() + duration_s
    pending, lat_ms, rejected = [], [], 0
    failed = [0]
    lock = threading.Lock()
    next_fire = time.monotonic()
    while time.monotonic() < t_end:
        now = time.monotonic()
        if now < next_fire:
            time.sleep(min(next_fire - now, 0.002))
            continue
        next_fire += interval
        feed, _n = make_feed()
        t0 = time.monotonic()
        try:
            fut = engine.infer(feed, deadline_ms=deadline_ms)
        except ServerOverloaded:
            rejected += 1
            continue

        def on_done(f, t0=t0):
            # completion time recorded IN the callback (fires on
            # set_result), not when the harvest loop gets around to
            # reading the future — the latter would overstate latency
            # by the whole remaining run
            with lock:
                if f.exception() is None:
                    lat_ms.append((time.monotonic() - t0) * 1e3)
                else:
                    failed[0] += 1

        fut.add_done_callback(on_done)
        pending.append(fut)
    for fut in pending:  # drain; outcomes already recorded above
        try:
            fut.result(timeout=60)
        except Exception:
            pass
    return {"offered_qps": qps, "submitted": len(pending),
            "client_rejected": rejected, "client_failed": failed[0],
            "client_lat_ms": lat_ms}


def _replica_cmd(model_dir, k, max_batch, wait_us, queue_size,
                 replica_args=()):
    cmd = [sys.executable, "-m", "paddle_tpu.serving.replica",
           "--model-dir", str(model_dir), "--port", "0",
           "--replica-id", str(k),
           "--max-batch", str(max_batch),
           "--wait-us", str(wait_us),
           "--queue-size", str(queue_size)]
    cmd.extend(replica_args)
    return cmd


def _stamp_replica_env(env, k, journal_dir=None):
    """Per-replica observability stamping (launch.py's posture for
    fleet workers): role + its OWN journal file + blackbox dir, so a
    spawned replica's ledger trail (compile_cache_hit origin
    attribution, serving_warmup, executor_compile) is separable from
    its siblings'."""
    env = dict(env, PADDLE_TPU_ROLE="serving-%d" % k)
    if journal_dir:
        os.makedirs(journal_dir, exist_ok=True)
        env["PADDLE_TPU_EVENT_JOURNAL"] = os.path.join(
            journal_dir, "events.serving-%d.jsonl" % k)
        env["PADDLE_TPU_BLACKBOX_DIR"] = str(journal_dir)
    return env


def _wait_ready(p, deadline):
    """Deadline-bounded wait for a replica child's ``REPLICA_READY``
    line -> endpoint. A plain ``readline()`` would block PAST the
    deadline on a silent-hung child — and this can run on the control
    plane's evaluation thread (``FleetScaler.scale_up``), where one
    wedged spawn would stall all remediation fleet-wide. A daemon
    reader thread does the blocking reads; it also keeps draining
    stdout for the child's lifetime, so a chatty replica can never
    block on a full pipe."""
    import queue as _queue

    q = _queue.Queue()
    ready = threading.Event()

    def _reader():
        try:
            for line in iter(p.stdout.readline, ""):
                # post-READY chatter is discarded, not queued: the
                # consumer is gone, and a long-lived chatty replica
                # must drain to nowhere, not into the parent's heap
                if not ready.is_set():
                    q.put(line)
        except Exception:
            pass
        q.put(None)

    threading.Thread(target=_reader, daemon=True,
                     name="replica-ready-reader").start()
    while True:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            raise RuntimeError("replica startup timed out")
        try:
            line = q.get(timeout=min(remaining, 1.0))
        except _queue.Empty:
            continue
        if line is None:
            raise RuntimeError(
                "replica died before READY (rc=%s)" % p.poll())
        if line.startswith("REPLICA_READY "):
            ready.set()
            return line.split()[1]


def _spawn_replica(cmd, env, cwd, startup_timeout_s=120.0):
    """Start one replica subprocess and wait for its REPLICA_READY
    line -> (proc, endpoint). Kills the child on timeout/death."""
    import subprocess

    p = subprocess.Popen(cmd, env=env, cwd=cwd,
                         stdin=subprocess.PIPE,
                         stdout=subprocess.PIPE, text=True)
    try:
        endpoint = _wait_ready(
            p, time.monotonic() + startup_timeout_s)
        return p, endpoint
    except Exception:
        p.kill()
        raise


def spawn_fleet(model_dir, n_replicas, max_batch=32, wait_us=2000,
                queue_size=256, policy="least_loaded",
                router_config=None, startup_timeout_s=120.0,
                replica_args=(), compile_cache_dir=None,
                group_size=1, mesh_axes=None, journal_dir=None):
    """Spawn ``n_replicas`` serving-replica SUBPROCESSES (real
    processes — the fleet's scaling claim is about escaping one
    process) for ``model_dir`` and return ``(router, stop)`` where
    ``stop()`` shuts the router down and reaps the children. Each
    child announces ``REPLICA_READY <endpoint>`` on stdout before the
    router is built, so a returned router is immediately usable.

    Replicas are CPU processes by request (``JAX_PLATFORMS=cpu`` in
    their env): a chip belongs to one process, and this parent may
    hold it — N replicas on N chips of one process is ROADMAP S9/R8.
    On a v5e host the parent built the model on the chip and two CPU
    replicas served 1,617 requests with none failed (chip run, PR 21).

    Every replica is stamped with ONE shared persistent compile-cache
    dir (PADDLE_TPU_COMPILE_CACHE_DIR; ROADMAP compile-plane
    follow-up): replica 0's warmup compiles are replicas 1..N's cache
    loads, and a respawned fleet cold-starts with zero XLA compiles.
    ``compile_cache_dir``: explicit dir, or "" to disable stamping;
    default resolves like launch.py (env var, else
    ``compile_cache.store_dir()``). ``journal_dir``: stamp each
    replica with its OWN event-journal file + blackbox dir
    (``events.serving-<k>.jsonl``) so per-replica ledger trails stay
    separable."""
    from paddle_tpu.distributed.launch import default_compile_cache_dir
    from paddle_tpu.serving import RouterConfig, ServingRouter

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if compile_cache_dir is None:
        compile_cache_dir = default_compile_cache_dir()
    # ASSIGN, never setdefault: env was seeded from os.environ, so an
    # explicit dir must beat an inherited var, and "" must blank the
    # inherited var out (compile_cache.active() reads "" as disabled)
    env["PADDLE_TPU_COMPILE_CACHE_DIR"] = compile_cache_dir or ""
    group_size = max(1, int(group_size))
    # with groups, n_replicas counts GROUPS; total = groups * size.
    # Member 0 of each group executes the pjit'd forward over
    # mesh_axes; members >0 are the group's shard/lease surface.
    n_procs = n_replicas * group_size
    mesh_json = json.dumps(mesh_axes) if mesh_axes else None
    import subprocess

    cwd = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs, endpoints = [], []
    try:
        for k in range(n_procs):
            rank = k % group_size
            cmd = _replica_cmd(model_dir, k, max_batch, wait_us,
                               queue_size)
            child_env = _stamp_replica_env(env, k,
                                           journal_dir=journal_dir)
            if group_size > 1:
                cmd.extend(["--group-rank", str(rank),
                            "--group-size", str(group_size)])
                if rank == 0 and mesh_json:
                    cmd.extend(["--mesh-axes", mesh_json])
                    import numpy as _np
                    ndev = int(_np.prod(list(mesh_axes.values())))
                    child_env = dict(
                        child_env,
                        XLA_FLAGS=(child_env.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform"
                                   "_device_count=%d"
                                   % ndev).strip())
            cmd.extend(replica_args)
            procs.append(subprocess.Popen(
                cmd, env=child_env, cwd=cwd,
                stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                text=True))
        deadline = time.monotonic() + startup_timeout_s
        for p in procs:
            endpoints.append(_wait_ready(p, deadline))
    except Exception:
        for p in procs:
            p.kill()
        raise
    cfg = router_config or RouterConfig(policy=policy,
                                        lease_timeout_s=2.0,
                                        heartbeat_interval_s=0.2,
                                        connect_timeout_s=10.0,
                                        group_size=group_size)
    router = ServingRouter(endpoints, cfg)

    def stop():
        router.shutdown()
        for p in procs:
            try:
                p.stdin.close()  # replicas exit on stdin EOF
            except Exception:
                pass
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                p.kill()

    stop.procs = procs  # chaos/bench seam: kill a REAL process
    stop.model_dir = str(model_dir)
    stop.env = env
    stop.journal_dir = journal_dir
    stop.spawn_opts = {"max_batch": max_batch, "wait_us": wait_us,
                       "queue_size": queue_size,
                       "replica_args": list(replica_args),
                       "group_size": group_size,
                       "mesh_axes": mesh_axes}
    return router, stop


class FleetScaler:
    """``spawn_fleet``'s actuator face for the control plane
    (``observability.control.ControlPlane.attach_scaler``): spawn or
    retire ONE replica subprocess per call, through the router's
    dynamic-membership API. Spawned replicas reuse the fleet's
    environment — in particular the shared
    ``PADDLE_TPU_COMPILE_CACHE_DIR`` — so a scale-up warms from the
    persistent compile cache (replica 0 paid the compiles) and serves
    its first request with zero XLA compiles, and the per-replica
    journal stamping keeps each spawned replica's ledger separable.

    On a GROUPED fleet (``spawn_fleet(..., group_size>1)``) the unit
    of scaling is a WHOLE sharded replica group: ``scale_up`` spawns
    all ``group_size`` member processes, waits for every READY line,
    and admits the group to the router atomically (``add_group``) or
    — if any member fails to come up — kills ALL of them and admits
    nothing; a partial mesh never reaches dispatch. The spawned group
    warms through the same shared compile cache as the base fleet
    (member 0's pjit compile is a cache load, not a cold compile).

    Build from a live fleet: ``FleetScaler(router, stop)`` (the pair
    ``spawn_fleet`` returns)."""

    def __init__(self, router, stop, startup_timeout_s=120.0):
        self.router = router
        self._stop = stop
        self.model_dir = stop.model_dir
        self.startup_timeout_s = float(startup_timeout_s)
        self._mu = threading.Lock()
        self._next_k = len(stop.procs)
        self._cwd = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        # rid -> proc for the replicas THIS scaler spawned (scale-down
        # retires newest-first and only ever reaps what it created)
        self._spawned = {}
        # gid -> [procs] for groups this scaler spawned (grouped fleet)
        self._spawned_groups = {}

    @property
    def _grouped(self) -> bool:
        return getattr(self.router, "_groups", None) is not None

    def replica_count(self) -> int:
        # membership, NOT the healthy subset: max_replicas bounds the
        # process budget, and an evicted-but-member replica still owns
        # its slot (it may be readmitted) — counting only healthy would
        # let repeated crashes under load scale past the cap. On a
        # grouped fleet the unit is the GROUP (max_replicas bounds
        # groups, each group_size processes).
        if self._grouped:
            return len(self.router._groups)
        return len(self.router._replicas)

    def retirable_count(self) -> int:
        # the control plane's down-bound tap: this scaler only ever
        # retires replicas/groups IT spawned, never the base fleet
        with self._mu:
            return len(self._spawned_groups) if self._grouped \
                else len(self._spawned)

    def pressure(self) -> dict:
        return self.router.pressure()

    def scale_up(self) -> dict:
        if self._grouped:
            return self._scale_up_group()
        with self._mu:
            k = self._next_k
            self._next_k += 1
        opts = self._stop.spawn_opts
        cmd = _replica_cmd(self.model_dir, k, opts["max_batch"],
                           opts["wait_us"], opts["queue_size"],
                           opts["replica_args"])
        env = _stamp_replica_env(self._stop.env, k,
                                 journal_dir=self._stop.journal_dir)
        t0 = time.monotonic()
        proc, endpoint = _spawn_replica(
            cmd, env, self._cwd,
            startup_timeout_s=self.startup_timeout_s)
        try:
            rid = self.router.add_replica(endpoint)
        except Exception:
            # admission refused (router shutting down, ...): the
            # already-READY child must not outlive the failure
            proc.kill()
            raise
        with self._mu:
            self._spawned[rid] = proc
        self._stop.procs.append(proc)  # fleet stop() reaps it too
        return {"ok": True, "op": "scale_up", "replica": rid,
                "endpoint": endpoint, "pid": proc.pid,
                "spawn_seconds": round(time.monotonic() - t0, 3),
                "replicas": self.replica_count()}

    def _scale_up_group(self) -> dict:
        """Spawn one whole sharded group and admit it atomically."""
        opts = self._stop.spawn_opts
        gs = max(1, int(opts.get("group_size") or 1))
        mesh_axes = opts.get("mesh_axes")
        mesh_json = json.dumps(mesh_axes) if mesh_axes else None
        with self._mu:
            ks = list(range(self._next_k, self._next_k + gs))
            self._next_k += gs
        t0 = time.monotonic()
        procs = []
        import subprocess
        try:
            for rank, k in enumerate(ks):
                cmd = _replica_cmd(self.model_dir, k,
                                   opts["max_batch"], opts["wait_us"],
                                   opts["queue_size"],
                                   opts["replica_args"])
                cmd.extend(["--group-rank", str(rank),
                            "--group-size", str(gs)])
                env = _stamp_replica_env(
                    self._stop.env, k,
                    journal_dir=self._stop.journal_dir)
                if rank == 0 and mesh_json:
                    cmd.extend(["--mesh-axes", mesh_json])
                    import numpy as _np
                    ndev = int(_np.prod(list(mesh_axes.values())))
                    env = dict(
                        env,
                        XLA_FLAGS=(env.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform"
                                   "_device_count=%d" % ndev).strip())
                procs.append(subprocess.Popen(
                    cmd, env=env, cwd=self._cwd,
                    stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                    text=True))
            deadline = time.monotonic() + self.startup_timeout_s
            endpoints = [_wait_ready(p, deadline) for p in procs]
            gid = self.router.add_group(endpoints)
        except Exception:
            # all-or-nothing: ANY member failing (spawn, READY
            # timeout, admission refused) kills the WHOLE group — a
            # partial mesh must never linger as orphan processes or
            # reach the dispatch set
            for p in procs:
                p.kill()
            raise
        with self._mu:
            self._spawned_groups[gid] = procs
        self._stop.procs.extend(procs)  # fleet stop() reaps them too
        return {"ok": True, "op": "scale_up_group", "group": gid,
                "endpoints": endpoints,
                "pids": [p.pid for p in procs],
                "spawn_seconds": round(time.monotonic() - t0, 3),
                "groups": self.replica_count()}

    def scale_down(self) -> dict:
        if self._grouped:
            return self._scale_down_group()
        with self._mu:
            if not self._spawned:
                raise RuntimeError(
                    "nothing to retire: this scaler spawned no "
                    "replicas beyond the base fleet")
            rid = max(self._spawned)   # newest-first
            proc = self._spawned.pop(rid)
        snap = self.router.remove_replica(rid)
        try:
            proc.stdin.close()   # replicas exit on stdin EOF
        except Exception:
            pass
        try:
            proc.wait(timeout=10)
        except Exception:
            proc.kill()
        try:
            self._stop.procs.remove(proc)
        except ValueError:
            pass
        return {"ok": True, "op": "scale_down", "replica": rid,
                "served_requests": snap.get("requests"),
                "replicas": self.replica_count()}

    def _scale_down_group(self) -> dict:
        with self._mu:
            if not self._spawned_groups:
                raise RuntimeError(
                    "nothing to retire: this scaler spawned no "
                    "groups beyond the base fleet")
            gid = max(self._spawned_groups)   # newest-first
            procs = self._spawned_groups.pop(gid)
        self.router.remove_group(gid)
        for proc in procs:
            try:
                proc.stdin.close()   # replicas exit on stdin EOF
            except Exception:
                pass
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except Exception:
                proc.kill()
            try:
                self._stop.procs.remove(proc)
            except ValueError:
                pass
        return {"ok": True, "op": "scale_down_group", "group": gid,
                "groups": self.replica_count()}


def run_closed_loop(engine, make_feed, concurrency, duration_s,
                    deadline_ms):
    from paddle_tpu.serving import ServerOverloaded

    t_end = time.monotonic() + duration_s
    lock = threading.Lock()
    lat_ms, counts = [], {"rejected": 0, "failed": 0, "submitted": 0}

    def worker():
        while time.monotonic() < t_end:
            feed, _n = make_feed()
            t0 = time.monotonic()
            try:
                with lock:
                    counts["submitted"] += 1
                engine.infer_sync(feed, deadline_ms=deadline_ms,
                                  timeout=60)
                with lock:
                    lat_ms.append((time.monotonic() - t0) * 1e3)
            except ServerOverloaded:
                with lock:
                    counts["rejected"] += 1
                time.sleep(0.005)  # back off as the error instructs
            except Exception:
                with lock:
                    counts["failed"] += 1

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return {"concurrency": concurrency,
            "submitted": counts["submitted"],
            "client_rejected": counts["rejected"],
            "client_failed": counts["failed"], "client_lat_ms": lat_ms}


def run_ramp(engine, make_feed, concurrencies, step_duration_s,
             deadline_ms):
    """Stepped-concurrency closed loop: one closed-loop step per level,
    each reported separately (completed/achieved QPS/p50/p99/rejected)
    so the knee — where added concurrency stops buying throughput and
    starts buying latency — is visible in one run."""
    steps, all_lat = [], []
    for c in concurrencies:
        t0 = time.monotonic()
        r = run_closed_loop(engine, make_feed, int(c), step_duration_s,
                            deadline_ms)
        wall = time.monotonic() - t0
        lat = np.asarray(r["client_lat_ms"])
        all_lat.extend(r["client_lat_ms"])
        steps.append({
            "concurrency": int(c),
            "completed": int(lat.size),
            "achieved_qps": round(lat.size / wall, 2) if wall else None,
            "p50_ms": round(float(np.percentile(lat, 50)), 3)
            if lat.size else None,
            "p99_ms": round(float(np.percentile(lat, 99)), 3)
            if lat.size else None,
            "client_rejected": r["client_rejected"],
            "client_failed": r["client_failed"],
        })
    return {"ramp": [int(c) for c in concurrencies],
            "step_duration_s": step_duration_s, "steps": steps,
            "submitted": sum(s["completed"] + s["client_rejected"]
                             + s["client_failed"] for s in steps),
            "client_rejected": sum(s["client_rejected"]
                                   for s in steps),
            "client_failed": sum(s["client_failed"] for s in steps),
            "client_lat_ms": all_lat}


def _sparse_table_main(args):
    """``--sparse-table``: Zipf traffic against the train-and-serve
    sparse stack; same open/closed/ramp protocols, one JSON report
    with per-tier hit accounting and the staleness gate's counters."""
    rng = np.random.RandomState(args.seed)
    perm = rng.permutation(args.vocab)
    router, reps, _servers, trainer, stop_stack = build_sparse_stack(
        args.vocab, args.dim, shards=args.shards,
        staleness_bound=args.staleness_bound)
    make_feed = sparse_feed_maker(rng, args.vocab, args.slots,
                                  args.batch_min, args.batch_max,
                                  skew=args.skew, perm=perm)
    push_stop = threading.Event()
    pushes = [0]

    def pusher():
        trng = np.random.RandomState(args.seed + 1)
        while not push_stop.is_set():
            ids = zipf_ids(trng, args.vocab, 64, skew=args.skew,
                           perm=perm)
            trainer.push(ids, (trng.randn(len(ids), args.dim)
                               * 0.01).astype(np.float32))
            pushes[0] += 1
            push_stop.wait(args.train_push_every)

    pt = None
    if args.train_push_every > 0:
        pt = threading.Thread(target=pusher, daemon=True)
        pt.start()
    t0 = time.monotonic()
    try:
        if args.mode == "open":
            client = run_open_loop(router, make_feed, args.qps,
                                   args.duration, args.deadline_ms)
        elif args.mode == "ramp":
            levels = [int(c) for c in args.ramp.split(",")
                      if c.strip()]
            client = run_ramp(router, make_feed, levels,
                              args.step_duration, args.deadline_ms)
        else:
            client = run_closed_loop(router, make_feed,
                                     args.concurrency, args.duration,
                                     args.deadline_ms)
        wall = time.monotonic() - t0
        push_stop.set()
        if pt is not None:
            pt.join(timeout=10)
        stats = reps[0].stats()
    finally:
        push_stop.set()
        stop_stack()

    lat = np.asarray(client.pop("client_lat_ms"))
    report = {
        "metric": "sparse_load_gen", "mode": args.mode,
        "vocab": args.vocab, "slots": args.slots, "dim": args.dim,
        "skew": args.skew, "shards": args.shards,
        "duration_s": round(wall, 2),
        "completed": int(lat.size),
        "achieved_qps": round(lat.size / wall, 2) if wall > 0
        else None,
        "p50_ms": round(float(np.percentile(lat, 50)), 3)
        if lat.size else None,
        "p99_ms": round(float(np.percentile(lat, 99)), 3)
        if lat.size else None,
        "trainer_pushes": pushes[0],
        "tiers": stats.get("tiers"),
        "staleness": stats.get("staleness"),
    }
    report.update(client)
    print(json.dumps(report), flush=True)
    return 1 if client.get("client_failed") else 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model-dir", default=None)
    ap.add_argument("--synthetic", action="store_true",
                    help="build a throwaway MLP instead of loading")
    ap.add_argument("--mode", choices=("open", "closed", "ramp"),
                    default="open")
    ap.add_argument("--qps", type=float, default=100.0)
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--duration", type=float, default=5.0)
    ap.add_argument("--ramp", default="1,2,4,8",
                    help="comma-separated concurrency levels for "
                    "--mode ramp")
    ap.add_argument("--step-duration", type=float, default=2.0,
                    help="seconds per ramp step")
    ap.add_argument("--replicas", type=int, default=0,
                    help="drive a fleet of N replica subprocesses "
                    "behind a ServingRouter instead of the in-process "
                    "engine")
    ap.add_argument("--policy", choices=("least_loaded",
                                         "round_robin"),
                    default="least_loaded",
                    help="router dispatch policy (with --replicas)")
    ap.add_argument("--group-size", type=int, default=1,
                    help="sharded replica groups: --replicas counts "
                    "GROUPS of this many member processes each; "
                    "member 0 executes one pjit'd forward over "
                    "--mesh-axes, the rest are the group's lease "
                    "surface. Any member dying evicts the whole "
                    "group; the report carries group-evict/retry "
                    "counts.")
    ap.add_argument("--mesh-axes", default=None,
                    help="JSON axis dict for the group executor's "
                    "mesh, e.g. '{\"tp\": 2}' (with --group-size)")
    ap.add_argument("--hidden", type=int, default=32,
                    help="synthetic model hidden width")
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--wait-us", type=int, default=2000)
    ap.add_argument("--queue-size", type=int, default=256)
    ap.add_argument("--batch-min", type=int, default=1)
    ap.add_argument("--batch-max", type=int, default=8)
    ap.add_argument("--deadline-ms", type=float, default=None)
    ap.add_argument("--no-warmup", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sparse-table", action="store_true",
                    help="drive the sparse serving plane instead of a "
                    "dense model: Zipf id-stream traffic against a "
                    "SparseServingReplica over in-process pserver "
                    "shards (docs/serving.md §Sparse serving), with "
                    "an optional concurrent trainer pushing into the "
                    "SAME tables (--train-push-every)")
    ap.add_argument("--vocab", type=int, default=4096,
                    help="sparse id space (with --sparse-table)")
    ap.add_argument("--slots", type=int, default=3,
                    help="ids per example (with --sparse-table)")
    ap.add_argument("--dim", type=int, default=16,
                    help="embedding dim (with --sparse-table)")
    ap.add_argument("--skew", type=float, default=0.9,
                    help="Zipf skew of the id stream")
    ap.add_argument("--shards", type=int, default=2,
                    help="pserver shard count (with --sparse-table)")
    ap.add_argument("--staleness-bound", type=int, default=8,
                    help="replica max_staleness_steps")
    ap.add_argument("--train-push-every", type=float, default=0.0,
                    help="seconds between concurrent trainer pushes "
                    "into the served tables (0 = serve-only)")
    args = ap.parse_args(argv)

    if args.sparse_table:
        return _sparse_table_main(args)
    if not args.model_dir and not args.synthetic:
        ap.error("pass --model-dir or --synthetic")

    from paddle_tpu.serving import ServingConfig, ServingEngine

    model_dir = args.model_dir
    if model_dir is None:
        model_dir = build_synthetic_model(
            tempfile.mkdtemp(prefix="load_gen_model_"),
            hidden=args.hidden)
    rng = np.random.RandomState(args.seed)
    stop_fleet = None
    if args.replicas > 0:
        engine, stop_fleet = spawn_fleet(
            model_dir, args.replicas, max_batch=args.max_batch,
            wait_us=args.wait_us, queue_size=args.queue_size,
            policy=args.policy, group_size=args.group_size,
            mesh_axes=json.loads(args.mesh_axes)
            if args.mesh_axes else None)
        with open(os.path.join(model_dir,
                               "__signature__.json")) as f:
            sig = json.load(f)
        make_feed = _feed_maker_from_sig(
            sig, rng, args.batch_min,
            min(args.batch_max, args.max_batch))
    else:
        cfg = ServingConfig(max_batch_size=args.max_batch,
                            max_queue_wait_us=args.wait_us,
                            max_queue_size=args.queue_size,
                            warmup=not args.no_warmup)
        engine = ServingEngine(model_dir, cfg)
        make_feed = _feed_maker(engine, rng, args.batch_min,
                                min(args.batch_max, args.max_batch))

    t0 = time.monotonic()
    if args.mode == "open":
        client = run_open_loop(engine, make_feed, args.qps,
                               args.duration, args.deadline_ms)
    elif args.mode == "ramp":
        levels = [int(c) for c in args.ramp.split(",") if c.strip()]
        client = run_ramp(engine, make_feed, levels,
                          args.step_duration, args.deadline_ms)
    else:
        client = run_closed_loop(engine, make_feed, args.concurrency,
                                 args.duration, args.deadline_ms)
    wall = time.monotonic() - t0
    stats = engine.stats()
    if stop_fleet is not None:
        stop_fleet()
    else:
        engine.shutdown(drain=True, timeout=30)

    lat = np.asarray(client.pop("client_lat_ms"))
    report = {
        "metric": "serving_load_gen",
        "mode": args.mode,
        "replicas": args.replicas,
        "duration_s": round(wall, 2),
        "completed": int(lat.size),
        "achieved_qps": round(lat.size / wall, 2) if wall > 0 else None,
        "p50_ms": round(float(np.percentile(lat, 50)), 3)
        if lat.size else None,
        "p95_ms": round(float(np.percentile(lat, 95)), 3)
        if lat.size else None,
        "p99_ms": round(float(np.percentile(lat, 99)), 3)
        if lat.size else None,
        "engine": stats,
    }
    if args.replicas > 0:
        # per-replica attribution: who served what, at what tail, and
        # who shed (stats is the router snapshot here)
        report["per_replica"] = {
            rid: {k: s[k] for k in ("endpoint", "healthy", "requests",
                                    "failures", "sheds", "p50_ms",
                                    "p99_ms", "queue_depth")}
            for rid, s in stats["replicas"].items()}
        if args.group_size > 1:
            # group serving: evict/readmit transitions + retry volume
            # (the acceptance numbers for sharded group inference)
            rc = stats["router"]
            report["group_size"] = args.group_size
            report["groups"] = stats.get("groups", {})
            report["group_evictions"] = rc.get("group_evictions", 0)
            report["group_readmissions"] = rc.get(
                "group_readmissions", 0)
            report["retries"] = rc.get("retries", 0)
    report.update(client)
    print(json.dumps(report), flush=True)
    return 1 if client.get("client_failed") else 0


if __name__ == "__main__":
    sys.exit(main())
