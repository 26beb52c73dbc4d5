"""Chip-measured refer-vs-pallas win table at flagship shapes.

The analog of the reference's operators/jit/benchmark.cc +
jit/README.en.md discipline: every kernel in the default library mix
must WIN at its target shape, proven by an in-tree benchmark table.
Run on the real chip:

    python tools/kernel_table.py            # all kernels, markdown out
    python tools/kernel_table.py --json     # machine-readable lines

Each row times the base XLA lowering against the pallas variant
through the real executor (fwd+bwd where differentiable) at the
transformer-base flagship shape, and verdicts win/lose (op level
only: ROADMAP D3 decides from in-model rows).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import ml_dtypes  # noqa: E402
import numpy as np  # noqa: E402

_BF16 = ml_dtypes.bfloat16

# flagship shapes: transformer-base NMT (BASELINE.json config 3) at
# batch 64, S=256, d_model 512, H=8, vocab 30k
_B, _S, _D, _H, _V = 64, 256, 512, 8, 30000

CASES = [
    # (op, inputs builder, attrs, grad?, output index to time)
    ("scaled_dot_product_attention",
     lambda rs: {"Q": rs.rand(_B, _H, _S, _D // _H).astype("float32"),
                 "K": rs.rand(_B, _H, _S, _D // _H).astype("float32"),
                 "V": rs.rand(_B, _H, _S, _D // _H).astype("float32")},
     {"causal": True}, True),
    # the IN-MODEL condition of the round-4 +12% winner: bf16
    # operands + dropout (single-k-block kernels, in-kernel PRNG).
    # The f32/no-dropout row above is kept as the honest contrast —
    # the kernel LOSES there and the mix demotion logic must see both.
    ("scaled_dot_product_attention",
     lambda rs: {"Q": rs.rand(_B, _H, _S, _D // _H).astype(_BF16),
                 "K": rs.rand(_B, _H, _S, _D // _H).astype(_BF16),
                 "V": rs.rand(_B, _H, _S, _D // _H).astype(_BF16)},
     {"causal": True, "dropout_rate": 0.1}, True, 0,
     "sdpa[bf16+dropout]"),
    ("layer_norm",
     lambda rs: {"X": rs.rand(_B * _S, _D).astype("float32"),
                 "Scale": rs.rand(_D).astype("float32"),
                 "Bias": rs.rand(_D).astype("float32")},
     {"begin_norm_axis": 1}, True),
    # out_index 1 = Loss: timing Softmax (index 0) would let XLA
    # dead-code the cross-entropy path this kernel targets
    ("softmax_with_cross_entropy",
     lambda rs: {"Logits": rs.rand(_B * _S, _V).astype("float32"),
                 "Label": rs.randint(0, _V, (_B * _S, 1))
                 .astype("int64")},
     {}, True, 1),
    ("fused_linear_xent",
     lambda rs: {"X": rs.rand(_B * _S, _D).astype("float32"),
                 "W": (rs.rand(_D, _V).astype("float32") * 0.02),
                 "Label": rs.randint(0, _V, (_B * _S, 1))
                 .astype("int64")},
     {"epsilon": 0.1}, True),
    ("adam",
     lambda rs: {"Param": rs.rand(_D, 4 * _D).astype("float32"),
                 "Grad": rs.rand(_D, 4 * _D).astype("float32"),
                 "Moment1": rs.rand(_D, 4 * _D).astype("float32"),
                 "Moment2": rs.rand(_D, 4 * _D).astype("float32"),
                 "LearningRate": np.asarray([1e-3], np.float32),
                 "Beta1Pow": np.asarray([0.9], np.float32),
                 "Beta2Pow": np.asarray([0.999], np.float32)},
     {}, False),
]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--only", help="comma-separated op subset")
    args = ap.parse_args(argv)

    from op_bench import bench_op

    def emit(r):
        # stream each row the moment it's measured: a compile that
        # never ends then costs only the tail of the table, never the
        # rows already on stdout
        if args.json:
            print(json.dumps(r), flush=True)
        elif "error" in r:
            print("| %s | ERROR %s | | | |" % (r["op"], r["error"]),
                  flush=True)
        else:
            print("| %s | %.3f | %.3f | %.2fx | %s |"
                  % (r["op"], r["base_ms"], r["pallas_ms"],
                     r["speedup"], r["winner"]), flush=True)

    if not args.json:
        print("| op | base (XLA) ms | pallas ms | speedup | winner |")
        print("|---|---|---|---|---|")

    try:
        stall_s = float(os.environ.get("KERNEL_TABLE_STALL_S", 360))
    except (TypeError, ValueError):
        stall_s = 360.0

    rs = np.random.RandomState(0)
    only = set(args.only.split(",")) if args.only else None
    # the 30k-vocab cases run ~10-40 ms/step — cap their in-graph
    # iters so each timed dispatch stays under a few seconds (an
    # explicit smaller --iters is still honored)
    heavy_cap = {"softmax_with_cross_entropy": 30,
                 "fused_linear_xent": 30}
    per_op_iters = {op: min(args.iters, cap)
                    for op, cap in heavy_cap.items()}
    for case in CASES:
        op, mk, attrs, grad = case[:4]
        out_index = case[4] if len(case) > 4 else 0
        label = case[5] if len(case) > 5 else op
        if only and op not in only and label not in only:
            continue

        def stalled(op=label):
            emit({"op": op, "error": "stalled >%.0fs (wedged compile?)"
                  % stall_s})
            os._exit(2)

        guard = threading.Timer(stall_s, stalled)
        guard.daemon = True
        guard.start()
        try:
            results = bench_op(op, mk(rs), attrs,
                               iters=per_op_iters.get(op, args.iters),
                               grad=grad, out_index=out_index)
        except Exception as e:  # keep the table going per-op
            emit({"op": label, "error": repr(e)})
            continue
        finally:
            guard.cancel()
        by_lib = {r["library"]: r for r in results}
        base = by_lib.get("base")
        pallas = by_lib.get("pallas")
        if not base or not pallas:
            emit({"op": label, "error": "missing variant: %s"
                  % sorted(by_lib)})
            continue
        b_ms = base["us_per_call"] / 1e3
        p_ms = pallas["us_per_call"] / 1e3
        speedup = b_ms / p_ms if p_ms else 0.0
        emit({"op": label, "base_ms": round(b_ms, 3),
              "pallas_ms": round(p_ms, 3),
              "speedup": round(speedup, 3),
              "winner": "pallas" if speedup > 1.0 else "xla"})


if __name__ == "__main__":
    main()
