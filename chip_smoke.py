#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that training starts, compiles
and steps on the chip through the entry points a user calls.

    python chip_smoke.py                 # on a TPU host (chip tool)
    python chip_smoke.py --rehearse-cpu  # toy size on CPU, never a pass

It builds transformer-base exactly as examples/train_transformer.py
does — ``TransformerConfig()`` (d512, 6+6 layers, 8 heads, d_ffn 2048,
vocab 30,000, S=256, dropout 0.1, label smoothing), batch 64, bf16 AMP,
Adam, random weights from a seed — and drives it through
``layers`` -> ``Program`` -> ``amp.decorate`` -> ``Executor.run`` /
``run_repeated``. Phases, each of which must pass:

  device    JAX's default backend is ``tpu`` and its ``device_kind`` is
            in ``core.TPU_PEAK_BF16_FLOPS``; versions are printed.
  train     startup program, RUN_STEPS ``Executor.run`` steps, then one
            ``run_repeated(iters=SCAN_STEPS)`` scan on one fixed batch.
            Losses are finite, the first is within a band of ln(vocab)
            (what random weights must give), the trace falls, and the
            fetched arrays live on TPU devices.
  kernels   the compiled train step's optimized HLO holds Mosaic custom
            calls (the Pallas flash-attention pair at the 18 attention
            sites; 54 on jax 0.9.0). Zero while ``FLAGS.sdpa_auto_flash``
            is on means the kernel quietly gave way to the jnp
            reference: a failure. The dp4 step is held to the same.
  compile   seconds to build each executable, whether it came from the
            persistent store, and the cache directory in use
            (``compile_cache.enable()``: ``JAX_COMPILATION_CACHE_DIR``
            if set, else ``.jax_cache/`` in the checkout).
  dp4       only when four or more devices are visible: the same model
            through ``CompiledProgram.with_data_parallel`` over a
            ``{"dp": 4}`` mesh of the first four.
            Batch 64 divides by 4 (``feed_sharding`` replicates a batch
            that dp does not divide). Parameters and feeds span four
            devices, every chip reports memory in use, the startup
            program's one-device parameters are re-placed once, and
            the loss trace matches the one-chip trace within rtol 2e-3.

Every exception propagates. Only a run in which every phase passed on
a TPU prints, as the last line of stdout,

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and exits 0. ``--rehearse-cpu`` is a request, not a fallback: it needs
``JAX_PLATFORMS=cpu``, runs the same phases at toy size with the kernels
in interpret mode, and ends without that line.
"""

import argparse
import importlib.metadata
import json
import math
import sys
import time

import jax
import jaxlib
import numpy as np
from jax.sharding import PartitionSpec

import paddle_tpu as fluid
from paddle_tpu import compile_cache, parallel
from paddle_tpu.contrib import mixed_precision as amp
from paddle_tpu.core import TPU_PEAK_BF16_FLOPS
from paddle_tpu.core.flags import FLAGS
from paddle_tpu.models import transformer as T

BATCH = 64          # divisible by dp=4
RUN_STEPS = 5
SCAN_STEPS = 5
DP = 4
# first loss of a randomly initialized model over ln(vocab): uniform
# predictions give exactly 1.0
INIT_LOSS_BAND = (0.9, 1.25)
MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'


def say(msg):
    print("[smoke] " + msg, flush=True)


def check(ok, what):
    if not ok:
        raise SystemExit("[smoke] FAILED: " + what)


def build(cfg):
    """Fresh (main, startup, loss): same names, same seeds every
    call, so two trajectories start from identical weights."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            avg_cost, _token_num, _ = T.transformer(cfg)
            opt = amp.decorate(fluid.optimizer.Adam(learning_rate=1e-3))
            opt.minimize(avg_cost)
    return main, startup, avg_cost


def loss_of(fetched):
    return float(np.asarray(fetched).reshape(-1)[0])


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy size on JAX_PLATFORMS=cpu; never prints "
                    "the pass line")
    args = ap.parse_args()
    t_start = time.perf_counter()
    cache_root = compile_cache.enable()

    # -- device ------------------------------------------------------------
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say("platform=%(platform)s device_kind=%(kind)r count=%(count)d"
        % device)
    say("jax=%s jaxlib=%s libtpu=%s python=%s"
        % (jax.__version__, jaxlib.__version__,
           importlib.metadata.version("libtpu"),
           sys.version.split()[0]))
    say("compile cache root: %s (executable store: %s)"
        % (cache_root, compile_cache.active().dir))
    on_chip = not args.rehearse_cpu
    if on_chip:
        check(jax.default_backend() == "tpu",
              "JAX's default backend is %r, not 'tpu' — no chip here, or "
              "another process holds it" % jax.default_backend())
        check(device["kind"] in TPU_PEAK_BF16_FLOPS,
              "device_kind %r is not in core.TPU_PEAK_BF16_FLOPS %s"
              % (device["kind"], sorted(TPU_PEAK_BF16_FLOPS)))
        cfg = T.TransformerConfig()      # transformer-base, full width
        place = fluid.TPUPlace(0)
    else:
        check(jax.default_backend() == "cpu",
              "--rehearse-cpu is a CPU rehearsal: run it under "
              "JAX_PLATFORMS=cpu (backend is %r)" % jax.default_backend())
        say("*** CPU REHEARSAL at toy size — proves the script, says "
            "nothing about the chip ***")
        cfg = T.TransformerConfig(src_vocab=1000, tgt_vocab=1000,
                                  max_len=32, d_model=64, d_ffn=128,
                                  n_head=4, n_layer=1)
        place = fluid.CPUPlace()
    check(BATCH % DP == 0, "batch %d must divide by dp=%d" % (BATCH, DP))
    feed = T.make_fake_batch(cfg, BATCH)
    say("model: d%d %d+%d layers %d heads ffn %d vocab %d S=%d dropout "
        "%.1f, batch %d, bf16 AMP, Adam"
        % (cfg.d_model, cfg.n_layer, cfg.n_layer, cfg.n_head, cfg.d_ffn,
           cfg.tgt_vocab, cfg.max_len, cfg.dropout, BATCH))

    # -- train: one device -------------------------------------------------
    main_prog, startup, avg_cost = build(cfg)
    exe = fluid.Executor(place)
    exe.run(startup)
    losses = []
    for step in range(RUN_STEPS):
        t0 = time.perf_counter()
        lv, = exe.run(main_prog, feed=feed, fetch_list=[avg_cost],
                      return_numpy=False)
        losses.append(loss_of(lv))   # the readback closes the step
        say("run step %d: loss=%.4f (%.2fs)"
            % (step, losses[-1], time.perf_counter() - t0))
        check({d.platform for d in lv.devices()} == {device["platform"]},
              "fetched loss lives on %s" % lv.devices())
    t0 = time.perf_counter()
    lv, = exe.run_repeated(main_prog, feed=feed, fetch_list=[avg_cost],
                           iters=SCAN_STEPS, return_numpy=False)
    scan_loss = loss_of(lv)
    say("run_repeated(iters=%d): last loss=%.4f (%.2fs)"
        % (SCAN_STEPS, scan_loss, time.perf_counter() - t0))
    check({d.platform for d in lv.devices()} == {device["platform"]},
          "scan's fetched loss lives on %s" % lv.devices())
    check(np.isfinite(losses + [scan_loss]).all(),
          "non-finite loss in %s" % (losses + [scan_loss]))
    ratio = losses[0] / math.log(cfg.tgt_vocab)
    check(INIT_LOSS_BAND[0] <= ratio <= INIT_LOSS_BAND[1],
          "first loss %.4f is %.3f x ln(vocab)=%.4f, outside %s"
          % (losses[0], ratio, math.log(cfg.tgt_vocab), INIT_LOSS_BAND))
    check(losses[-1] < losses[0] and scan_loss < losses[-1],
          "loss is not falling: run %s, scan %.4f" % (losses, scan_loss))
    say("loss trace: first=%.4f (%.3f x ln V) last run=%.4f last "
        "scan=%.4f — finite and falling"
        % (losses[0], ratio, losses[-1], scan_loss))

    # -- kernels + compile report ------------------------------------------
    def report_executables(exe_, train_prog, tag=""):
        for rec in exe_.aot_artifacts():
            check(rec["mode"] == "xla"
                  and rec["optimized_hlo"] is not None,
                  "no compiled HLO for %s" % rec["entry"])
            n_mosaic = rec["optimized_hlo"].count(MOSAIC_CALL)
            is_train = rec["program_uid"] == train_prog._uid
            say("executable %s%-12s %-7s %7.2fs %s  Mosaic custom "
                "calls: %d"
                % (tag, rec["entry"], "train" if is_train else "startup",
                   rec["build_seconds"],
                   "loaded from store" if rec["from_cache"]
                   else "compiled", n_mosaic))
            if is_train and on_chip and FLAGS.sdpa_auto_flash:
                check(n_mosaic > 0,
                      "the compiled %s%s step holds no Mosaic custom "
                      "call while FLAGS.sdpa_auto_flash is on: "
                      "attention gave way to the jnp reference"
                      % (tag, rec["entry"]))

    report_executables(exe, main_prog)
    tel = exe.telemetry()
    say("compile totals: %d XLA compiles, %d store loads, %.2fs; store "
        "stats %s" % (tel["xla_compiles"], tel["cache_loads"],
                      tel["compile_seconds_total"],
                      json.dumps(tel["compile_cache"])))

    # -- dp4: the same model over four devices -----------------------------
    if device["count"] >= DP:
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            main4, startup4, cost4 = build(cfg)
            exe4 = fluid.Executor(place)
            exe4.run(startup4)
            prog4 = fluid.CompiledProgram(main4).with_data_parallel(
                loss_name=cost4.name,
                mesh=parallel.make_mesh({"dp": DP}, devs[:DP]))
            block = main4.global_block()
            params = [n for n, v in block.vars.items()
                      if v.persistable and scope.has_var(n)]
            check(all(len(scope.find_var(n).devices()) == 1
                      for n in params),
                  "startup left parameters on more than one device")
            losses4 = []
            for step in range(RUN_STEPS):
                lv, = exe4.run(prog4, feed=feed, fetch_list=[cost4])
                losses4.append(loss_of(lv))
                say("dp%d step %d: loss=%.4f (one chip %.4f)"
                    % (DP, step, losses4[-1], losses[step]))
                if step == 0:
                    # re-placed by the first step, so every later
                    # step's placement check is a no-op
                    for n in params:
                        val, want = scope.find_var(n), \
                            prog4.persist_sharding(block.vars[n])
                        check(val.sharding == want
                              and len(val.sharding.device_set) == DP,
                              "%s is on %s, wanted %s"
                              % (n, val.sharding, want))
            w = scope.find_var(params[0])
            say("parameters: %d arrays, each on %d devices (e.g. %s %s "
                "shard %s)"
                % (len(params), DP, params[0], w.sharding.spec,
                   w.sharding.shard_shape(w.shape)))
            for name, arr in sorted(feed.items()):
                sh = prog4.feed_sharding(arr.shape, name)
                check(sh.spec == PartitionSpec(
                    "dp", *[None] * (arr.ndim - 1))
                    and len(sh.device_set) == DP,
                    "feed %s is %s, not batch-sharded over dp"
                    % (name, sh.spec))
            say("feeds: batch-sharded over dp, shard %s of %s"
                % (sh.shard_shape(arr.shape), arr.shape))
            for d in devs[:DP]:
                stats = d.memory_stats()
                if on_chip:
                    check(stats and stats["bytes_in_use"] > (64 << 20),
                          "%s reports %s" % (d, stats))
                say("%s bytes_in_use=%s"
                    % (d, stats and stats["bytes_in_use"]))
        report_executables(exe4, main4, tag="dp%d " % DP)
        np.testing.assert_allclose(
            losses4, losses, rtol=2e-3,
            err_msg="dp%d loss trace diverged from the one-chip trace"
            % DP)
        say("dp%d loss trace matches one chip within rtol 2e-3" % DP)
    else:
        say("dp%d phase skipped: %d device(s) visible"
            % (DP, device["count"]))

    say("all phases passed in %.1fs" % (time.perf_counter() - t_start))
    if not on_chip:
        say("*** CPU REHEARSAL complete — not a chip result, no pass "
            "line ***")
        return
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
