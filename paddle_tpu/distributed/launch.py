"""Multi-process training launcher.

Reference: python/paddle/distributed/launch.py:1-200 — spawns one
trainer process per GPU card with PADDLE_TRAINER_ID /
PADDLE_CURRENT_ENDPOINT / PADDLE_TRAINERS_NUM /
PADDLE_TRAINER_ENDPOINTS in each child's environment.

TPU redesign: the process unit is a HOST, not a chip — one process
per host owns all its local chips and `jax.distributed` federates
hosts into one global device mesh (parallel/multihost.py consumes the
same PADDLE_* spelling this launcher writes, so reference launch
scripts port by changing the module name). A chip belongs to ONE
process: ``--nproc_per_node``/``--server_num``/``--serving_replicas``
setups that put several processes on a node are for
``JAX_PLATFORMS=cpu`` simulation. On a TPU host the first child to
initialize JAX takes the chips and every other child dies at backend
init with JAX's own "Unable to initialize backend 'tpu': ABORTED: The
TPU is already in use by process with pid N" (or, when two start at
once, "...accessing libtpu multi-process lockfile"): exit 1 within
seconds, with ``JAX_PLATFORMS`` set or unset; the launcher then
terminates the fleet and returns 1 (``--nproc_per_node=2`` on one
v5e chip: 20 s — chip run, PR 21, libtpu 0.0.34). Nothing hangs and
no child runs on the CPU unasked.

Usage:
    python -m paddle_tpu.distributed.launch train.py --your --args
    python -m paddle_tpu.distributed.launch \
        --cluster_node_ips=10.0.0.1,10.0.0.2 --node_ip=10.0.0.1 \
        train.py --your --args
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import threading
import time
from argparse import REMAINDER, ArgumentParser


def _parse_args(argv=None):
    parser = ArgumentParser(
        description="start multi-process training "
        "(PADDLE_TRAINER_* env contract; see "
        "paddle_tpu.parallel.multihost.init_parallel_env)")
    parser.add_argument(
        "--cluster_node_ips", default="127.0.0.1",
        help="comma-separated ips of all training nodes")
    parser.add_argument(
        "--node_ip", default="127.0.0.1",
        help="this node's ip (must appear in --cluster_node_ips)")
    parser.add_argument(
        "--started_port", type=int, default=6170,
        help="first coordinator port on each node")
    parser.add_argument(
        "--nproc_per_node", type=int, default=1,
        help="processes per node (TPU: 1 process owns every local "
        "chip; >1 is for JAX_PLATFORMS=cpu simulation)")
    parser.add_argument(
        "--log_dir", default=None,
        help="redirect each worker's output to <log_dir>/worker.N.log")
    parser.add_argument(
        "--server_num", type=int, default=0,
        help="pserver processes to spawn on this node (PS mode); each "
        "runs the same script with PADDLE_TRAINING_ROLE=PSERVER")
    parser.add_argument(
        "--servers_started_port", type=int, default=7170,
        help="first pserver port on each node (PS mode)")
    parser.add_argument(
        "--serving_replicas", type=int, default=0,
        help="serving-replica processes to spawn on this node "
        "(serving fleet mode); each runs the same script with "
        "PADDLE_TRAINING_ROLE=SERVING and its replica id/endpoint "
        "in PADDLE_SERVING_* (serving/replica.py consumes them)")
    parser.add_argument(
        "--serving_started_port", type=int, default=8170,
        help="first serving-replica port on each node")
    parser.add_argument(
        "--journal_dir", default=None,
        help="directory for per-worker structured event journals "
        "(events.<role>.jsonl, observability.journal); defaults to "
        "--log_dir when that is set")
    parser.add_argument(
        "--compile_cache_dir", default=None,
        help="persistent AOT compile-cache directory shared by every "
        "worker (PADDLE_TPU_COMPILE_CACHE_DIR). Default: inherit the "
        "launcher's env var if set, else compile_cache.store_dir() "
        "(under JAX_COMPILATION_CACHE_DIR, else the checkout's "
        ".jax_cache/) — so fleets share one cache and warm restarts "
        "perform zero XLA compiles (docs/compile.md). Pass an empty "
        "string to disable stamping.")
    parser.add_argument(
        "training_script",
        help="the script to launch (followed by its own args)")
    parser.add_argument("training_script_args", nargs=REMAINDER)
    return parser.parse_args(argv)


def get_cluster_env(args):
    """Build the per-process env dicts (exposed for tests)."""
    ips = [ip.strip() for ip in args.cluster_node_ips.split(",")
           if ip.strip()]
    if args.node_ip not in ips:
        raise ValueError(
            "--node_ip %s is not in --cluster_node_ips %s"
            % (args.node_ip, args.cluster_node_ips))
    nper = args.nproc_per_node
    endpoints = ["%s:%d" % (ip, args.started_port + i)
                 for ip in ips for i in range(nper)]
    node_index = ips.index(args.node_ip)
    envs = []
    for local_rank in range(nper):
        rank = node_index * nper + local_rank
        env = {
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_CURRENT_ENDPOINT": endpoints[rank],
            "PADDLE_TRAINERS_NUM": str(len(endpoints)),
            "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
            "PADDLE_TRAINING_ROLE": "TRAINER",
        }
        _stamp_role(env, args, "trainer-%d" % rank)
        envs.append(env)
    return envs


def get_server_env(args):
    """Per-pserver-process env dicts for PS mode (``--server_num``):
    the PADDLE_PSERVER_* spelling plus the same role/journal stamping
    trainers get, so fleet logs and journals stay attributable."""
    ips = [ip.strip() for ip in args.cluster_node_ips.split(",")
           if ip.strip()]
    if args.node_ip not in ips:
        raise ValueError(
            "--node_ip %s is not in --cluster_node_ips %s"
            % (args.node_ip, args.cluster_node_ips))
    nserv = int(args.server_num or 0)
    endpoints = ["%s:%d" % (ip, args.servers_started_port + j)
                 for ip in ips for j in range(nserv)]
    node_index = ips.index(args.node_ip)
    envs = []
    for local in range(nserv):
        sid = node_index * nserv + local
        env = {
            "PADDLE_PSERVER_ID": str(sid),
            "PADDLE_CURRENT_ENDPOINT": endpoints[sid],
            "PADDLE_PSERVER_ENDPOINTS": ",".join(endpoints),
            "PADDLE_TRAINERS_NUM": str(
                len(ips) * args.nproc_per_node),
            "PADDLE_TRAINING_ROLE": "PSERVER",
        }
        _stamp_role(env, args, "pserver-%d" % sid)
        envs.append(env)
    return envs


def get_serving_env(args):
    """Per-serving-replica env dicts for fleet serving mode
    (``--serving_replicas``): PADDLE_SERVING_REPLICA_ID + the fleet's
    endpoint universe (the router's ``ServingRouter(endpoints)``
    input), with the same role/journal stamping trainers and pservers
    get so replica journals merge into the fleet timeline."""
    ips = [ip.strip() for ip in args.cluster_node_ips.split(",")
           if ip.strip()]
    if args.node_ip not in ips:
        raise ValueError(
            "--node_ip %s is not in --cluster_node_ips %s"
            % (args.node_ip, args.cluster_node_ips))
    nrep = int(getattr(args, "serving_replicas", 0) or 0)
    endpoints = ["%s:%d" % (ip, args.serving_started_port + k)
                 for ip in ips for k in range(nrep)]
    node_index = ips.index(args.node_ip)
    envs = []
    for local in range(nrep):
        rid = node_index * nrep + local
        env = {
            "PADDLE_SERVING_REPLICA_ID": str(rid),
            "PADDLE_CURRENT_ENDPOINT": endpoints[rid],
            "PADDLE_SERVING_ENDPOINTS": ",".join(endpoints),
            "PADDLE_TRAINING_ROLE": "SERVING",
        }
        _stamp_role(env, args, "serving-%d" % rid)
        envs.append(env)
    return envs


def _journal_dir(args):
    return getattr(args, "journal_dir", None) or \
        getattr(args, "log_dir", None)


def default_compile_cache_dir(args=None):
    """The fleet-shared executable-store directory: an explicit
    ``--compile_cache_dir`` wins; an empty string disables stamping;
    otherwise the launcher's own PADDLE_TPU_COMPILE_CACHE_DIR (every
    child inherits the env anyway — returning it keeps the contract
    visible), else ``compile_cache.store_dir()`` — under
    ``JAX_COMPILATION_CACHE_DIR`` when that is set, else under the
    checkout's ``.jax_cache/`` — so even ad-hoc fleets share warm
    executables across restarts."""
    explicit = getattr(args, "compile_cache_dir", None) \
        if args is not None else None
    if explicit is not None:
        return explicit or None  # "" = opt out
    env = os.environ.get("PADDLE_TPU_COMPILE_CACHE_DIR")
    if env is not None:
        # an INHERITED "" is the documented disabled value
        # (compile_cache.active() reads it as off) — honor it as an
        # explicit opt-out, don't fall through and re-enable
        return env or None
    from ..compile_cache import store_dir
    return store_dir()


def _stamp_role(env, args, role):
    """Role tag + role-stamped event-journal path (the observability
    plane's per-process identity: journal events carry the role, and
    each worker writes its own events.<role>.jsonl). The same dir is
    stamped as the flight-recorder blackbox dir, so a worker that
    wedges or gets SIGTERMed leaves blackbox.<role>.json next to its
    journal (observability.health.FlightRecorder)."""
    env["PADDLE_TPU_ROLE"] = role
    jdir = _journal_dir(args)
    if jdir:
        env["PADDLE_TPU_EVENT_JOURNAL"] = os.path.join(
            jdir, "events.%s.jsonl" % role)
        env.setdefault("PADDLE_TPU_BLACKBOX_DIR", jdir)
    # one persistent AOT compile cache per FLEET (same dir in every
    # worker): replica N's first compile is replica N+1's cache hit,
    # and a warm restart performs zero XLA compiles (compile_cache.py;
    # concurrent writers are safe — atomic tmp+rename entries)
    if getattr(args, "compile_cache_dir", None) == "":
        # explicit opt-out must beat an INHERITED env var too: the
        # child env is built as dict(os.environ, **env), and
        # compile_cache.active() reads "" as disabled
        env["PADDLE_TPU_COMPILE_CACHE_DIR"] = ""
    else:
        cdir = default_compile_cache_dir(args)
        if cdir:
            env["PADDLE_TPU_COMPILE_CACHE_DIR"] = cdir


def _prefix_pump(pipe, role, sink):
    """Copy a worker's merged stdout/stderr to ``sink`` with each line
    prefixed by its role tag, so interleaved fleet logs stay
    attributable to the worker that wrote them."""
    try:
        for line in pipe:
            sink.write("[%s] %s" % (role, line))
            sink.flush()
    except ValueError:
        pass  # sink closed mid-shutdown
    finally:
        pipe.close()


def launch(args, poll_interval_s=0.2, term_grace_s=10.0):
    # pservers and serving replicas first (their peers connect to
    # them), then trainers. Log files keep the historical
    # worker.<trainer_id>.log names; other roles get worker.<role>.log.
    specs = [(env["PADDLE_TPU_ROLE"], "worker.%s.log"
              % env["PADDLE_TPU_ROLE"], env)
             for env in get_server_env(args)]
    specs += [(env["PADDLE_TPU_ROLE"], "worker.%s.log"
               % env["PADDLE_TPU_ROLE"], env)
              for env in get_serving_env(args)]
    specs += [(env["PADDLE_TPU_ROLE"], "worker.%s.log"
               % env["PADDLE_TRAINER_ID"], env)
              for env in get_cluster_env(args)]
    jdir = _journal_dir(args)
    if jdir:
        os.makedirs(jdir, exist_ok=True)
    procs, logs, pumps = [], [], []
    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)
    for role, logname, env in specs:
        cmd = [sys.executable, "-u", args.training_script] \
            + args.training_script_args
        full = dict(os.environ, **env)
        if args.log_dir:
            out = open(os.path.join(args.log_dir, logname), "w")
            logs.append(out)
            procs.append(subprocess.Popen(cmd, env=full, stdout=out,
                                          stderr=out))
        else:
            # no log dir: pipe through a role-prefixing pump so the
            # shared console stays attributable
            p = subprocess.Popen(cmd, env=full,
                                 stdout=subprocess.PIPE,
                                 stderr=subprocess.STDOUT, text=True)
            t = threading.Thread(target=_prefix_pump,
                                 args=(p.stdout, role, sys.stdout),
                                 daemon=True)
            t.start()
            pumps.append(t)
            procs.append(p)
    rc = 0
    try:
        # Poll EVERY worker: the first failure anywhere triggers
        # terminate-all immediately. (A sequential p.wait() blocked on
        # worker 0, so a crash in worker N>0 wedged the surviving
        # collective until worker 0 happened to exit on its own.)
        while True:
            statuses = [p.poll() for p in procs]
            failed = [s for s in statuses if s is not None and s != 0]
            if failed:
                rc = failed[0]
                # one dead worker wedges the collective — take the
                # rest down (the reference launcher's terminate-all)
                for q in procs:
                    if q.poll() is None:
                        q.send_signal(signal.SIGTERM)
                deadline = time.time() + term_grace_s
                while time.time() < deadline and \
                        any(q.poll() is None for q in procs):
                    time.sleep(poll_interval_s)
                break
            if all(s is not None for s in statuses):
                break
            time.sleep(poll_interval_s)
    finally:
        for q in procs:
            if q.poll() is None:
                q.kill()
        for t in pumps:
            t.join(timeout=5)
        for f in logs:
            f.close()
    return rc


def main(argv=None):
    args = _parse_args(argv)
    return launch(args)


if __name__ == "__main__":
    sys.exit(main())
