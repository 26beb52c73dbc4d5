#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of standard output, one JSON object: whether
the timed path's output was correct, the dispatches attempted and
failed, the cell's end-to-end metrics (``--trace 0``) or per-layer
metrics (``--trace 1``), and the device. On an earlier line, and in
``benchmark/out/``, the per-dispatch completion intervals.

Everything that belongs to one configuration, traffic mix or metric is
a file found by its name: ``configs/<config>.json``,
``traffic/<traffic>.json``, ``metrics/<metric>.json`` + ``.py``,
``reference/<config>.py``, ``limits/<workload>.json``. Nothing here
names a model.

``--rehearse-cpu`` runs the same phases at the toy sizes the files give
under ``JAX_PLATFORMS=cpu``. It is a request, never a fallback, and it
prints no metric: a CPU run says nothing about the chip.
"""

import argparse
import importlib
import json
import os
import shutil
import sys
import time

T_START = time.perf_counter()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def say(msg):
    print("[bench] " + msg, file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload, rehearsal=False):
    """The cell's entry, configuration and traffic, by name; with
    ``rehearsal`` the toy sizes the files themselves give."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit("no workload %r in BENCHMARK.json (%s)"
                         % (workload, sorted(cells)))
    cell = cells[workload]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    config = load_json(ROOT, files[cell["config"]])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    args = dict(config["args"])
    if rehearsal:
        args.update(config["rehearsal_args"])
        traffic = {**traffic, **traffic["rehearsal"]}
    return {"bench": bench, "cell": cell, "config": config,
            "traffic": traffic, "args": args,
            "chips": int(cell["chips"]), "rehearsal": rehearsal}


def find_devices(chips, rehearsal):
    import jax
    devs = jax.devices()
    if rehearsal:
        if jax.default_backend() != "cpu":
            raise SystemExit("--rehearse-cpu needs JAX_PLATFORMS=cpu "
                             "(backend is %r)" % jax.default_backend())
    elif devs[0].platform != "tpu":
        raise SystemExit("no accelerator: JAX's backend is %r. This "
                         "benchmark measures on a TPU only"
                         % devs[0].platform)
    if len(devs) < chips:
        raise SystemExit("the cell asks for %d chips, JAX sees %d"
                         % (chips, len(devs)))
    return devs[:chips]


def named(path):
    """``package.module:function`` -> the function."""
    module, _, name = path.partition(":")
    return getattr(importlib.import_module(module), name)


def reference_module(config):
    return importlib.import_module(
        "benchmark.reference." + config["reference"])


def make_batch(c, seed):
    """(batch, stats) from the generator the traffic file names."""
    return named(c["traffic"]["generator"])(c["traffic"], c["args"], seed)


def build_system(c, seed, devices):
    import jax
    jax.config.update("jax_default_prng_impl",
                      c["config"]["training"]["prng_impl"])
    batch, stats = make_batch(c, seed)
    adapter = importlib.import_module(
        "benchmark.adapters." + c["config"]["adapter"])
    spec = reference_module(c["config"]).param_spec(c["args"])
    system = adapter.build(c["config"], c["traffic"], seed, devices,
                           c["args"], spec)
    system.set_batch(batch)
    return system, batch, stats


def first_dispatches(system, n):
    """The compared dispatches, through the window's own call and feed:
    enqueue all, snapshot the state's norms after the first and the
    last, then read everything back."""
    import numpy as np
    handles, first, last = [], None, None
    for i in range(n):
        handles.append(system.dispatch())
        if i == 0:
            first = system.state_norms()
        if i == n - 1:
            last = first if n == 1 else system.state_norms()
            scaling = system.loss_scaling()
    as_float = lambda tree: {k: float(np.asarray(v).reshape(-1)[0])
                             for k, v in tree.items()}
    return {"loss": [float(np.asarray(h).reshape(-1)[0])
                     for h in handles],
            "first": {"m1": as_float(first["m1"])},
            "last": {"delta": as_float(last["delta"]),
                     "moved": as_float(last["moved"])},
            "loss_scaling": as_float(scaling)}


def reference_readings(c, batch, seed, mode="f32", rows=None,
                       mask_stream=1):
    """The reference following the compared dispatches' steps; ``rows``
    keeps only the first rows of the batch (a planted fault)."""
    from benchmark.reference import common
    spd = int(c["traffic"].get("iters", 1))
    n = int(c["traffic"]["compare_dispatches"])
    if rows is not None:
        batch = {k: v[:rows] for k, v in batch.items()}
    opt = c["config"]["training"]
    out = common.train(reference_module(c["config"]), c["args"], opt,
                       batch, seed, steps=n * spd, mode=mode,
                       rows_per_block=int(
                           c["traffic"]["reference_rows_per_block"]),
                       snapshot_steps=(spd, n * spd),
                       mask_stream=mask_stream)
    return {"loss": [out["loss"][k * spd - 1] for k in range(1, n + 1)],
            "loss_first_step": out["loss"][0], "grad1": out["grad1"],
            "first": {"m1": out["snap"][spd]["m1"]},
            "last": {k: out["snap"][n * spd][k]
                     for k in ("delta", "moved")}}


def traced_dispatches(system, n, trace_dir):
    """n dispatches, two in flight, under the profiler."""
    import jax
    import numpy as np
    from benchmark import trace_reduce
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    try:
        current = system.dispatch()
        for _ in range(n - 1):
            ahead = system.dispatch()
            np.asarray(current)
            current = ahead
        np.asarray(current)
    finally:
        jax.profiler.stop_trace()
    events = trace_reduce.load(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return trace_reduce.reduce(events)


def device_peak_bytes(device):
    """The most this device held. On this runtime a step program's
    temporaries are RESERVED when it first runs and stay so
    (``bytes_reserved``), outside ``bytes_in_use``: the peak is the
    larger of the allocator's own peak and what is in use now plus the
    most that was reserved."""
    st = device.memory_stats() or {}
    return max(st.get("peak_bytes_in_use", 0),
               st.get("bytes_in_use", 0)
               + st.get("peak_bytes_reserved", 0))


def read_metrics(names, ctx):
    out = {}
    for name in names:
        meta = load_json(HERE, "metrics", name + ".json")
        value = importlib.import_module(
            "benchmark.metrics." + name).read(ctx)
        if value is not None:
            out[name] = {"value": float(value), "unit": meta["unit"]}
    return out


def wanted(entries, workload):
    return [m["name"] for m in entries
            if "workloads" not in m or workload in m["workloads"]]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    a = ap.parse_args(argv)

    import numpy as np
    from benchmark import compare, peaks, window

    c = load_cell(a.workload, a.rehearse_cpu)
    devices = find_devices(c["chips"], a.rehearse_cpu)
    kind = devices[0].device_kind
    peak = None if a.rehearse_cpu else peaks.peak(kind)
    say("cell %s on %d x %s, seed %d" % (a.workload, len(devices), kind,
                                         a.seed))

    # -- set-up ---------------------------------------------------------
    t_import = time.perf_counter() - T_START
    system, batch, stats = build_system(c, a.seed, devices)
    t_built = time.perf_counter() - T_START
    n_compare = int(c["traffic"]["compare_dispatches"])
    program = first_dispatches(system, n_compare)
    say("set-up: %d dispatch(es) of %d step(s), loss %s, loss scaling "
        "%s" % (n_compare, system.steps_per_dispatch, program["loss"],
                program["loss_scaling"]))
    tel0 = system.telemetry()
    setup_s = time.perf_counter() - T_START
    say("set-up %.1fs: %.1fs to the devices, %.1fs program build, "
        "startup, weights and batch, %.1fs first dispatch(es) with the "
        "step's executable" % (setup_s, t_import, t_built - t_import,
                               setup_s - t_built))

    # -- the window -----------------------------------------------------
    def read(h):
        return float(np.asarray(h).reshape(-1)[0])

    win = window.run_window(system.dispatch, read, a.seconds)
    tel1 = system.telemetry()
    completions = win["completions"]
    iv = window.intervals(completions)
    compiles = tel1["xla_compiles"] - tel0["xla_compiles"]
    if compiles:
        raise SystemExit("%d XLA compile(s) inside the window"
                         % compiles)

    trace = None
    steps_traced = 0
    if a.trace:
        n_tr = int(c["traffic"]["trace_dispatches"])
        trace = traced_dispatches(
            system, n_tr, os.path.join(HERE, "out", "trace",
                                       "%s.%d" % (a.workload, a.seed)))
        steps_traced = n_tr * system.steps_per_dispatch
        if trace is None and not a.rehearse_cpu:
            raise SystemExit("the trace holds no device operation")

    peak_bytes = max(device_peak_bytes(d) for d in devices)
    artifacts = system.artifacts()
    system.free()

    os.makedirs(os.path.join(HERE, "out"), exist_ok=True)
    record = {"workload": a.workload, "seed": a.seed,
              "steps_per_dispatch": system.steps_per_dispatch,
              "dispatch_intervals_s": iv, "losses": win["losses"]}
    with open(os.path.join(HERE, "out", "%s.%d.dispatches.json"
                           % (a.workload, a.seed)), "w") as f:
        json.dump(record, f)
    print(json.dumps(record), flush=True)

    # -- correct: the first dispatches against the reference -------------
    t_ref = time.perf_counter()
    ref = reference_readings(c, batch, a.seed)
    values, notes = compare.readings(program, ref)
    limits = compare.load_limits(a.workload)
    correct, compared = compare.decide(
        values, limits["rehearsal" if a.rehearse_cpu else "chip"])
    say("reference: %.1fs; losses program %s reference %s (its first "
        "step's %.6g: a state left unchanged would read loss_gap %.4g); "
        "%s" % (time.perf_counter() - t_ref, program["loss"], ref["loss"],
                ref["loss_first_step"],
                abs(ref["loss_first_step"] / ref["loss"][-1] - 1.0), notes))

    # -- the result -------------------------------------------------------
    steps = len(completions) * system.steps_per_dispatch
    ctx = {"telemetry_before": tel0, "telemetry_after": tel1,
           "artifacts": artifacts, "intervals": iv, "steps": steps,
           "window_s": completions[-1], "batch_stats": stats,
           "trace": trace, "steps_traced": steps_traced,
           "config": c["config"], "traffic": c["traffic"],
           "args": c["args"], "chips": c["chips"], "peak": peak,
           "flops_per_step": named(c["config"]["step_flops"])(
               c["args"], stats["lengths"], stats["predictions"])}
    end_to_end = {
        "tokens_per_s": window.rate(completions,
                                    system.steps_per_dispatch,
                                    stats["tokens_per_step"],
                                    c["chips"]),
        "peak_hbm_gib": peak_bytes / 2.0 ** 30,
        "setup_s": setup_s}
    units = {m["name"]: m["unit"] for m in c["bench"]["end_to_end"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    result = {"correct": bool(correct), "attempted": len(completions) + 1,
              "failed": 0}
    if a.rehearse_cpu:
        result["rehearsal"] = "CPU at toy size: no metric is printed"
    elif a.trace:
        result["metrics"] = read_metrics(
            wanted(c["bench"]["per_layer"], a.workload), ctx)
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    else:
        result["metrics"] = {
            n: {"value": float(end_to_end[n]), "unit": units[n]}
            for n in wanted(c["bench"]["end_to_end"], a.workload)}
    result["device"] = device
    result["all_readings"] = values
    result["compared"] = compared
    for n, cmp_ in compared.items():
        say("compared %s = %.6g (limit %.6g)%s"
            % (n, cmp_["value"], cmp_["limit"],
               "" if cmp_["value"] <= cmp_["limit"] else "  <-- OVER"))
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
