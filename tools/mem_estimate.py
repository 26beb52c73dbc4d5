"""Compile-only HBM estimate for a train-step at a given batch size.

Sizing tool: batch size and depth of a configuration are decided by
asking the compiler for the peak allocation instead of probing with
real steps until one runs out of memory.

    python tools/mem_estimate.py resnet50 64 96 128
    python tools/mem_estimate.py transformer 64 96

Prints one JSON line per batch with the compiler's memory_analysis
(``compile_cache.memory_record``: no step is ever launched; only the
startup program runs, which allocates just the parameters).
"""

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402

from paddle_tpu import compile_cache  # noqa: E402

jax.config.update("jax_default_prng_impl", "rbg")
compile_cache.enable()

import numpy as np  # noqa: E402


def _build(model, batch):
    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as amp

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 1
    rs = np.random.RandomState(0)
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            if model == "resnet50":
                from paddle_tpu.models import resnet as R
                img = fluid.layers.data("img", shape=[3, 224, 224],
                                        dtype="float32")
                label = fluid.layers.data("label", shape=[1],
                                          dtype="int64")
                pred = R.resnet50(img)
                loss, _ = R.loss_and_acc(pred, label)
                opt = amp.decorate(
                    fluid.optimizer.MomentumOptimizer(0.1, 0.9))
                opt.minimize(loss)
                feed = {"img": rs.rand(batch, 3, 224, 224)
                        .astype(np.float32),
                        "label": rs.randint(0, 1000, (batch, 1))
                        .astype(np.int64)}
            elif model == "transformer":
                from paddle_tpu.models import transformer as T
                cfg = T.TransformerConfig(
                    src_vocab=30000, tgt_vocab=30000, max_len=256,
                    d_model=512, d_ffn=2048, n_head=8, n_layer=6,
                    dropout=0.1)
                loss, _tok, _ = T.transformer(cfg)
                opt = amp.decorate(fluid.optimizer.AdamOptimizer(1e-3))
                opt.minimize(loss)
                feed = T.make_fake_batch(cfg, batch)
            else:
                raise SystemExit("unknown model %r" % model)
    return main, startup, loss, feed


def estimate(model, batch):
    import paddle_tpu as fluid
    from paddle_tpu.executor import run_block

    main, startup, loss, feed = _build(model, batch)
    scope = fluid.core.Scope()
    exe = fluid.Executor()
    with fluid.scope_guard(scope):
        exe.run(startup)           # params only — safe allocation
        block = main.global_block()
        persist = {n: scope.find_var(n) for n, v in block.vars.items()
                   if v.persistable and scope.has_var(n)
                   and scope.find_var(n) is not None}
        feed_dev = {k: jax.numpy.asarray(v) for k, v in feed.items()}

        def step(persist_vals, feed_vals, key):
            env = dict(persist_vals)
            env.update(feed_vals)
            run_block(block, env, key)
            return ({n: env[n] for n in persist_vals},
                    env[loss.name])

        key = jax.random.key(0)
        lowered = jax.jit(step, donate_argnums=(0,)).lower(
            persist, feed_dev, key)
        # the record every executable of an Executor carries
        # (telemetry()["memory"]), in GiB
        record = compile_cache.memory_record(lowered.compile()) or {}
        return dict({"model": model, "batch": batch},
                    **{k.replace("_bytes", "_gib"): round(v / 2**30, 3)
                       for k, v in record.items() if v is not None})


def main():
    args = sys.argv[1:]
    if not args:
        raise SystemExit(__doc__)
    model, batches = args[0], [int(b) for b in args[1:]] or [64]
    for b in batches:
        try:
            row = estimate(model, b)
        except Exception as e:  # noqa: BLE001
            row = {"model": model, "batch": b,
                   "error": repr(e)[:300]}
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
