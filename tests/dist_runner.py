"""Standalone distributed-model runner, launched as a subprocess by
test_fleet.py — the analog of the reference's dist_mnist.py +
TestDistRunnerBase (test_dist_base.py:38): builds a small model,
trains N steps through the fleet, prints the loss trace as JSON.

Every process feeds the IDENTICAL global batch; the dp sharding
splits it across processes' devices (the sync-SGD semantics whose
loss trace must equal a single-process run — test_dist_base.py:316).
"""

import json
import os
import sys

if __name__ == "__main__":
    # subprocess mode: one CPU device, set before any jax import
    # (paddle imports are lazy inside the run_* functions, so this is
    # early enough). Guarded so importing this module for its helpers
    # (test_fleet.py, __graft_entry__._dryrun_ps) does NOT mutate the
    # importing process's environment.
    os.environ["JAX_PLATFORMS"] = "cpu"
    # one CPU device per process (the parent test env forces 8)
    os.environ["XLA_FLAGS"] = ""

import numpy as np  # noqa: E402


def build_model():
    import paddle_tpu as fluid
    from paddle_tpu import layers

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 42
    with fluid.program_guard(main, startup):
        if os.environ.get("DIST_MODEL") == "mnist":
            # the MNIST MLP of the reference's dist_mnist.py
            x = layers.data("x", shape=[16, 784],
                            append_batch_size=False)
            y = layers.data("y", shape=[16, 1], dtype="int64",
                            append_batch_size=False)
            h = layers.fc(x, size=64, act="relu",
                          param_attr=fluid.ParamAttr(name="w1"))
            pred = layers.fc(h, size=10, act="softmax",
                             param_attr=fluid.ParamAttr(name="w2"))
            loss = layers.mean(layers.cross_entropy(pred, y))
        else:
            x = layers.data("x", shape=[8, 6],
                            append_batch_size=False)
            y = layers.data("y", shape=[8, 1],
                            append_batch_size=False)
            h = layers.fc(x, size=16, act="relu",
                          param_attr=fluid.ParamAttr(name="w1"))
            pred = layers.fc(h, size=1,
                             param_attr=fluid.ParamAttr(name="w2"))
            loss = layers.reduce_mean(
                layers.square_error_cost(input=pred, label=y))
    return main, startup, loss


def batches(n_steps):
    if os.environ.get("DIST_MODEL") == "mnist":
        from paddle_tpu.dataset import mnist
        it = mnist.train()()
        for _ in range(n_steps):
            xs, ys = zip(*[next(it) for _ in range(16)])
            yield (np.stack(xs).astype(np.float32),
                   np.stack(ys).reshape(16, 1).astype(np.int64))
        return
    rs = np.random.RandomState(7)
    for _ in range(n_steps):
        x = rs.rand(8, 6).astype(np.float32)
        y = (x.sum(1, keepdims=True) * 0.5).astype(np.float32)
        yield x, y


def _lr():
    # the 784-wide MNIST MLP needs a gentler step than the tiny
    # regression model
    return 0.01 if os.environ.get("DIST_MODEL") == "mnist" else 0.1


def _maybe_gloo():
    """Arm gloo CPU collectives ONLY for a process that will actually
    call jax.distributed.initialize (fleet mode at trainers > 1):
    this jaxlib's make_gloo_tcp_collectives requires a live
    DistributedRuntimeClient, so setting gloo in a single process now
    crashes CPU backend creation with "distributed_client: NoneType"
    instead of being silently ignored (env drift: older jaxlibs
    accepted None). The local reference run never initializes
    jax.distributed and must never set gloo — single-device numerics
    are identical either way."""
    import jax
    if int(os.environ.get("PADDLE_TRAINERS_NUM", "1")) > 1:
        jax.config.update("jax_cpu_collectives_implementation",
                          "gloo")


def run_local(n_steps):
    import paddle_tpu as fluid

    main, startup, loss = build_model()
    with fluid.program_guard(main, startup):
        fluid.optimizer.SGD(_lr()).minimize(loss)
    exe = fluid.Executor()
    exe.run(startup)
    load_path = os.environ.get("DIST_LOAD_INIT")
    if load_path:
        # start from the params a PS trainer adopted from the server
        # (server init uses different RNG folds than local startup)
        scope = fluid.global_scope()
        for name, val in np.load(load_path).items():
            if scope.has_var(name):
                scope.set_var(name, val)
    out = []
    for x, y in batches(n_steps):
        (lv,) = exe.run(main, feed={"x": x, "y": y},
                        fetch_list=[loss])
        out.append(float(np.asarray(lv).reshape(-1)[0]))
    return out


def run_fleet(n_steps):
    _maybe_gloo()
    import paddle_tpu as fluid
    from paddle_tpu.incubate.fleet.base import role_maker
    from paddle_tpu.incubate.fleet.collective import fleet

    fleet.init(role_maker.PaddleCloudRoleMaker(is_collective=True))
    main, startup, loss = build_model()
    with fluid.program_guard(main, startup):
        opt = fleet.distributed_optimizer(fluid.optimizer.SGD(0.1))
        opt.minimize(loss)
    exe = fluid.Executor()
    exe.run(startup)
    out = []
    for x, y in batches(n_steps):
        (lv,) = exe.run(fleet.main_program, feed={"x": x, "y": y},
                        fetch_list=[loss])
        out.append(float(np.asarray(lv).reshape(-1)[0]))
    return out


def _ps_fleet():
    import paddle_tpu as fluid  # noqa: F401
    from paddle_tpu.incubate.fleet.base import role_maker
    from paddle_tpu.incubate.fleet.parameter_server import (
        ParameterServerFleet)
    f = ParameterServerFleet()
    f.init(role_maker.PaddleCloudRoleMaker(is_collective=False))
    return f


def _ps_minimize(f, fluid, loss):
    """Sync-SGD objective: the pserver SUMS the N trainers' grads, so
    each trainer minimizes loss/N on the identical global batch —
    summed server grad == the local-run grad and every trainer's
    (unscaled) loss trace must equal the local trace. Server and
    trainer must build the SAME program for grad names to align.

    DIST_PS_ASYNC=1 flips to asynchronous SGD (ListenAndServ
    RunAsyncLoop semantics): every arriving grad optimizes
    immediately, no barrier and no 1/N scaling — trainers only
    guarantee convergence, not trace equality."""
    from paddle_tpu import layers
    if os.environ.get("DIST_PS_ASYNC"):
        opt = f.distributed_optimizer(fluid.optimizer.SGD(_lr()))
        opt._strategy.async_mode = True
        opt.minimize(loss)
        return
    n = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    obj = loss if n == 1 else layers.scale(loss, scale=1.0 / n)
    opt = f.distributed_optimizer(fluid.optimizer.SGD(_lr()))
    opt.minimize(obj)


def run_pserver():
    """PS server process: build the same model, split the optimize
    ops, serve until the trainer COMPLETEs (the reference's
    exe.run(pserver_program) process)."""
    import paddle_tpu as fluid
    f = _ps_fleet()
    main, startup, loss = build_model()
    with fluid.program_guard(main, startup):
        _ps_minimize(f, fluid, loss)
    f.init_server()
    print("SERVER_READY", flush=True)
    f.run_server()
    print("SERVER_DONE", flush=True)


def run_ps_trainer(n_steps):
    import paddle_tpu as fluid
    f = _ps_fleet()
    main, startup, loss = build_model()
    with fluid.program_guard(main, startup):
        _ps_minimize(f, fluid, loss)
    exe = fluid.Executor()
    exe.run(startup)
    f.init_worker()
    save_path = os.environ.get("DIST_SAVE_INIT")
    if save_path and os.environ.get("PADDLE_TRAINER_ID") == "0":
        # snapshot the ADOPTED initial params so a local reference run
        # can be seeded from the identical starting point
        scope = fluid.global_scope()
        blk = main.global_block()
        params = {n: np.asarray(scope.find_var(n))
                  for n, v in blk.vars.items()
                  if v.persistable and scope.has_var(n)}
        np.savez(save_path, **params)
    out = []
    for x, y in batches(n_steps):
        (lv,) = exe.run(f.main_program, feed={"x": x, "y": y},
                        fetch_list=[loss])
        out.append(float(np.asarray(lv).reshape(-1)[0]))
    f.stop_worker()
    return out


# --- orchestration helpers (imported by test_fleet.py and the driver
# dryrun in __graft_entry__.py — one copy of the port/readiness/parse
# plumbing) -----------------------------------------------------------------

def free_port():
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def parse_losses(stdout, what="runner"):
    for line in stdout.splitlines():
        if line.startswith("LOSSES:"):
            return json.loads(line[len("LOSSES:"):])
    raise AssertionError("no LOSSES line from %s:\n%s"
                         % (what, stdout[-2000:]))


def spawn_pserver(env, stderr_file, timeout=180):
    """Start the pserver subprocess and wait for SERVER_READY.

    stderr goes to a FILE, not a pipe: an undrained pipe fills up on
    XLA warnings and deadlocks the whole exchange, and reading a pipe
    of a still-live process to build an error message blocks forever.
    Returns the Popen; raises (after killing the server) if it never
    becomes ready."""
    import select
    import subprocess
    import time

    server = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "pserver"],
        env=env, stdout=subprocess.PIPE, stderr=stderr_file,
        text=True)
    deadline = time.time() + timeout
    line = ""
    while time.time() < deadline:
        ready, _, _ = select.select([server.stdout], [], [], 1.0)
        if ready:
            line = server.stdout.readline()
            if "SERVER_READY" in line:
                return server
        if server.poll() is not None:
            break
    server.kill()
    stderr_file.flush()
    stderr_file.seek(0)
    raise AssertionError("pserver never became ready:\n%s"
                         % stderr_file.read()[-3000:])


def run_ps_trainers(envs, n_steps, timeout=300):
    """Run one ps_trainer subprocess per env CONCURRENTLY (the sync
    barrier needs all trainers in flight); kill every straggler on
    any failure so no subprocess leaks into the caller. Returns each
    trainer's stdout."""
    import subprocess

    import threading
    import time

    procs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "ps_trainer",
         str(n_steps)],
        env=e, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True) for e in envs]
    # drain every pipe CONCURRENTLY: a sequentially-read sibling can
    # fill its pipe with XLA warnings, block, and stall the sync
    # barrier for everyone
    bufs = [[] for _ in procs]

    def drain(stream, sink):
        for ln in stream:
            sink.append(ln)

    readers = [threading.Thread(target=drain,
                                args=(p.stdout, bufs[i]), daemon=True)
               for i, p in enumerate(procs)]
    for t in readers:
        t.start()
    deadline = time.time() + timeout
    try:
        for p in procs:
            p.wait(timeout=max(deadline - time.time(), 1))
        for t in readers:
            t.join(timeout=10)
        outs = ["".join(b) for b in bufs]
        for r, (p, out) in enumerate(zip(procs, outs)):
            if p.returncode != 0:
                raise AssertionError("ps trainer %d failed:\n%s"
                                     % (r, out[-3000:]))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return outs


if __name__ == "__main__":
    mode = sys.argv[1]
    if mode == "pserver":
        run_pserver()
        sys.exit(0)
    n_steps = int(sys.argv[2])
    if mode == "local":
        losses = run_local(n_steps)
    elif mode == "ps_trainer":
        losses = run_ps_trainer(n_steps)
    else:
        losses = run_fleet(n_steps)
    print("LOSSES:" + json.dumps(losses))
