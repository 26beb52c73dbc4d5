"""Where a train step's device time goes, by the program's own scopes:
capture a ``jax.profiler`` trace over a few dispatches and print
``profiler.device_summary_table`` (phase, layer kind, op type, what
stayed unscoped, what the idle gaps waited for), with the rate of the
same dispatches traced and untraced.

    python tools/profile_step.py [--iters 20] [--batch 64]
    python tools/profile_step.py --workload tfm_base_scan [--out t.json]
    python tools/profile_step.py --workload tfm_base_scan --memory

Without ``--workload`` the flagship transformer-base step at
``--batch``; with it, one cell of ``BENCHMARK.json`` exactly as the
benchmark builds it (its adapter, weights and batch from ``--seed``).
``--memory`` traces nothing: it builds the step with one dispatch and
prints what its executable holds in HBM (the compiler's count, the
state it takes by kind) and ``profiler.memory_table``: the scheduled
HLO's fullest moment by the same scopes, a model whose first line says
how much of the compiler's temporaries it covers.
A table is only as new as the executable: start from an empty compile
cache (``JAX_COMPILATION_CACHE_DIR`` to a fresh directory) after a
change to the scopes, or the store hands back an executable that
still carries the old names (docs/compile.md).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402


def flagship(args):
    """(dispatch, steps a dispatch) of transformer-base under AMP+Adam."""
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as amp
    from paddle_tpu.models import transformer as T

    cfg = T.TransformerConfig(src_vocab=30000, tgt_vocab=30000,
                              max_len=256, d_model=512, d_ffn=2048,
                              n_head=8, n_layer=6, dropout=0.1)
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = 1
    with fluid.program_guard(main_p, startup):
        avg_cost, _tok, _ = T.transformer(cfg)
        opt = amp.decorate(fluid.optimizer.AdamOptimizer(1e-3))
        opt.minimize(avg_cost)
    exe = fluid.Executor()
    exe.run(startup)
    feed = {k: jnp.asarray(v)
            for k, v in T.make_fake_batch(cfg, args.batch).items()}

    def dispatch():
        return exe.run_repeated(main_p, feed=feed,
                                fetch_list=[avg_cost],
                                iters=args.iters,
                                return_numpy=False)[0]
    return dispatch, args.iters


def cell(args):
    """(dispatch, steps a dispatch) of one benchmark cell."""
    from benchmark import run as bench
    c = bench.load_cell(args.workload, args.rehearse_cpu)
    devices = bench.find_devices(c["chips"], args.rehearse_cpu)
    system, _batch, _stats = bench.build_system(c, args.seed, devices)
    return system.dispatch, system.steps_per_dispatch


def timed(dispatch, n):
    """Seconds for n dispatches, two in flight, every loss read."""
    t0 = time.perf_counter()
    current = dispatch()
    for _ in range(n - 1):
        ahead = dispatch()
        np.asarray(current)
        current = ahead
    np.asarray(current)
    return time.perf_counter() - t0


def memory_report(dispatch, out):
    """The step's executable, built by one dispatch: its memory and
    state records and the scheduled HLO's fullest moment by scope."""
    from paddle_tpu import profiler
    print("building the step...", file=sys.stderr, flush=True)
    np.asarray(dispatch())
    table = profiler.device_memory_table()
    if table is None:
        raise SystemExit("no executable gives its optimized HLO here")
    gib = 2.0 ** 30
    print("executable %s (%s), %s: %s" % (
        table["entry"], table["shape_key"],
        "loaded from the store" if table["from_cache"] else "compiled",
        ", ".join("%s %.4f GiB" % (k[:-6], v / gib)
                  for k, v in (table["memory"] or {}).items()
                  if v is not None) or "no memory analysis"))
    state = table["state"]
    print("state it takes, per device: " + ", ".join(
        "%s %.4f GiB in %d leaves" % (k, state[k]["bytes"] / gib,
                                      state[k]["leaves"])
        for k in ("parameters", "optimizer_state", "other"))
        + "; feed %.4f GiB" % (state["feed_bytes"] / gib))
    print(profiler.format_memory_table(table))
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            json.dump(table, f, indent=1)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--dispatches", type=int, default=3)
    ap.add_argument("--rehearse-cpu", action="store_true")
    # a directory of this run's own: the reader takes every trace it
    # finds under it, so two runs sharing one read as one capture
    ap.add_argument("--trace-dir",
                    default="/tmp/flagship_trace.%d" % os.getpid())
    ap.add_argument("--memory", action="store_true",
                    help="no trace: the step's HBM by scope")
    ap.add_argument("--out", help="write the table as JSON here too")
    args = ap.parse_args()

    from paddle_tpu import profiler
    dispatch, spd = cell(args) if args.workload else flagship(args)
    if args.memory:
        return memory_report(dispatch, args.out)
    n = args.dispatches
    print("compiling + warmup...", file=sys.stderr, flush=True)
    timed(dispatch, 2)
    untraced = timed(dispatch, n)
    print("tracing...", file=sys.stderr, flush=True)
    profiler.reset_profiler()
    profiler.start_profiler("All", trace_path=args.trace_dir)
    traced = timed(dispatch, n)
    profiler.stop_profiler(steps=n * spd)
    print("%d dispatches of %d steps: %.4f s untraced, %.4f s traced "
          "(%.2f%% slower)" % (n, spd, untraced, traced,
                               100.0 * (traced / untraced - 1.0)))
    # which lowering each kind of site took, counted when the step was
    # traced (sdpa_ / moe_ / kda_ / rotary_lowering.*), and the
    # schedule the blocked flash kernels read off each site's shape
    # (flash_schedule.* / flash_backward.*)
    lowerings = {k: v for k, v in sorted(profiler.counter_values().items())
                 if "_lowering." in k
                 or k.startswith(("flash_schedule.", "flash_backward."))}
    print("lowerings: " + ", ".join("%s %g" % kv
                                    for kv in lowerings.items()))
    if args.out:
        table = dict(profiler.device_scope_table(), steps=n * spd,
                     untraced_s=untraced, traced_s=traced,
                     lowerings=lowerings)
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(table, f, indent=1)


if __name__ == "__main__":
    main()
