"""Perf-lever in-model A/B on the chip.

Measures transformer-base b64 steps/s for each lever in isolation and
combined, against the all-off baseline. One fresh program + Executor per config: the executor
jit cache does not key on these trace-time flags.

    python tools/lever_ab.py            # all configs
    python tools/lever_ab.py fast       # baseline + shipped FINAL only
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402

import numpy as np  # noqa: E402

import bench  # noqa: E402
from paddle_tpu import compile_cache  # noqa: E402
from paddle_tpu.core.flags import FLAGS  # noqa: E402

jax.config.update("jax_default_prng_impl", "rbg")
compile_cache.enable()

LEVERS = ("lean_xent_grad", "mxu_bias_grad", "multi_tensor_adam",
          "mxu_ln_grad")

CONFIGS = [
    ("all-off(r4-baseline)", {}, ""),
    ("lean_xent", {"lean_xent_grad": True}, ""),
    ("mxu_bias_grad", {"mxu_bias_grad": True}, ""),
    ("multi_tensor_adam_64k", {"multi_tensor_adam": True}, ""),
    # round-5 lever: layer_norm dScale/dBias on the MXU (the
    # mxu_bias_grad treatment extended to the LN affine tail)
    ("mxu_ln_grad", {"mxu_ln_grad": True}, ""),
    ("sdpa:pallas", {}, "scaled_dot_product_attention:pallas"),
    # the shipped default configuration (headline)
    ("FINAL(lean+biasgrad,adam-off)+sdpa:pallas",
     {"lean_xent_grad": True, "mxu_bias_grad": True},
     "scaled_dot_product_attention:pallas"),
    # round-5 candidate: headline + LN grads on MXU
    ("FINAL+mxu_ln_grad",
     {"lean_xent_grad": True, "mxu_bias_grad": True,
      "mxu_ln_grad": True},
     "scaled_dot_product_attention:pallas"),
]


def main():
    fast = "fast" in sys.argv[1:]
    # fast = baseline + the SHIPPED headline config (selected by name,
    # not list position — experimental candidates appended to CONFIGS
    # must not silently replace the +12% witness)
    shipped = next(c for c in CONFIGS if c[0].startswith("FINAL("))
    configs = ([CONFIGS[0], shipped] if fast else CONFIGS)
    print("devices:", jax.devices(), flush=True)
    results = []
    for name, flags, mix in configs:
        for lever in LEVERS:
            setattr(FLAGS, lever, flags.get(lever, False))
        FLAGS.op_library = mix
        t0 = time.time()
        try:
            cfg, run, tokens, _wire = bench._build_transformer_step(64, 256)
            sps = bench._timed_loop(run, 3, 25)
            mfu = bench._mfu(
                bench.transformer_flops_per_step(cfg, 64), sps)
            row = {"config": name, "steps_per_s": round(sps, 3),
                   "tokens_per_s": round(tokens * sps, 1),
                   "mfu": mfu, "wall_s": round(time.time() - t0, 1)}
        except Exception as e:  # noqa: BLE001
            row = {"config": name, "error": repr(e)[:300],
                   "wall_s": round(time.time() - t0, 1)}
        finally:
            FLAGS.op_library = ""
        results.append(row)
        print(json.dumps(row), flush=True)
        with open(".lever_ab.jsonl", "a") as fh:
            fh.write(json.dumps(row) + "\n")
        from paddle_tpu.core.scope import global_scope
        global_scope().drop_all()
    best = max((r for r in results if "steps_per_s" in r),
               key=lambda r: r["steps_per_s"], default=None)
    print("BEST:", json.dumps(best), flush=True)


if __name__ == "__main__":
    main()
