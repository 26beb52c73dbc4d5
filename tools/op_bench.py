"""Per-op micro-benchmark — the analog of the reference's
paddle/fluid/operators/benchmark/op_tester.cc (time one op from a
config) and operators/jit/benchmark.cc (compare implementations and
report the best).

Times a single op through the real executor, once per registered
library variant (base XLA lowering vs pallas kernels), and prints one
JSON line per variant plus the winner:

    python tools/op_bench.py matmul --inputs X=256x256,Y=256x256
    python tools/op_bench.py softmax --inputs X=512x512 --grad
    python tools/op_bench.py --list          # ops with variants
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402


def parse_inputs(spec):
    """"X=2x3,Y=3x4" or "X=2x3:int64" → {slot: ndarray}."""
    out = {}
    if not spec:
        return out
    rs = np.random.RandomState(0)
    for part in spec.split(","):
        slot, shape = part.split("=")
        dtype = "float32"
        if ":" in shape:
            shape, dtype = shape.split(":")
        dims = tuple(int(d) for d in shape.split("x"))
        if np.issubdtype(np.dtype(dtype), np.integer):
            out[slot] = rs.randint(0, 8, dims).astype(dtype)
        else:
            out[slot] = rs.rand(*dims).astype(dtype)
    return out


def parse_attrs(spec):
    out = {}
    if not spec:
        return out
    for part in spec.split(","):
        k, v = part.split("=")
        for cast in (int, float):
            try:
                out[k] = cast(v)
                break
            except ValueError:
                continue
        else:
            out[k] = {"True": True, "False": False}.get(v, v)
    return out


def _build_timed_program(op_type, np_inputs, attrs, grad, out_index):
    """One-op program shaped for honest in-graph repetition.

    The timing loop lives ON-DEVICE (Executor.run_repeated lax.scan —
    timing one dispatch per call measures the host's dispatch cost,
    not a microsecond op). Inside a scan two compiler hazards would void
    the measurement, both defeated by a persistable f32[1] accumulator
    ``bench_acc``:

    - loop-invariant hoisting: identical inputs per step let XLA lift
      the op out of the loop. The first float input is perturbed by
      ``acc * 1e-30`` (bit-identical in f32, but data-dependent).
    - dead-code elimination: only the LAST step's fetches leave the
      scan, so unconsumed per-step outputs die. The op's timed output
      and every input gradient are reduced and folded into
      ``acc += total * 1e-30``, which each step carries forward.
    """
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu import ops as registry

    main = fluid.Program()
    with fluid.program_guard(main):
        block = main.global_block()
        acc = block.create_var(name="bench_acc", shape=[1],
                               dtype="float32", persistable=True)
        feed, op_inputs, grad_roots = {}, {}, []
        perturbed = False
        for slot, val in np_inputs.items():
            if isinstance(val, (list, tuple)):
                raise NotImplementedError(
                    "variadic input slots are not supported by the "
                    "timed builder")
            name = slot.lower()
            var = layers.data(name, shape=list(val.shape),
                              append_batch_size=False,
                              dtype=str(val.dtype))
            is_float = np.issubdtype(val.dtype, np.floating)
            var.stop_gradient = not is_float
            feed[name] = val
            use = var
            if not perturbed and is_float:
                use = layers.elementwise_add(
                    var, layers.scale(acc, scale=1e-30))
                perturbed = True
            if is_float:
                grad_roots.append(var)
            op_inputs[slot] = [use]
        if not perturbed:
            print("WARNING: %s has no float input to perturb — the "
                  "scan's anti-hoisting defense does not apply and "
                  "XLA may lift the op out of the timed loop"
                  % op_type, file=sys.stderr)
        opdef = registry.get(op_type)
        out_vars, op_outputs = [], {}
        for slot in opdef.output_slots:
            variadic = slot.endswith("*")
            sname = slot[:-1] if variadic else slot
            vs = [block.create_var(
                name="out_%s_0" % sname.lower(), shape=(),
                dtype="float32")]
            op_outputs[sname] = vs
            out_vars.extend(vs)
        block.append_op(type=op_type, inputs=op_inputs,
                        outputs=op_outputs, attrs=attrs or {})
        total = layers.reduce_sum(out_vars[out_index])
        if grad:
            gs = fluid.gradients(total, grad_roots)
            for g in gs:
                if g is not None:
                    total = layers.elementwise_add(
                        total, layers.reduce_sum(g))
        upd = layers.elementwise_add(
            acc, layers.scale(layers.reshape(total, [1]),
                              scale=1e-30))
        block.append_op(type="assign", inputs={"X": [upd]},
                        outputs={"Out": [acc]})
    return main, feed, acc


def _null_overhead_s(iters):
    """Constant dispatch+readback cost subtracted from every op
    timing. Delegates to the canonical measurer in bench.py
    (_dispatch_overhead_s — one null-scan protocol, maintained in one
    place); the null step itself is ~µs, so the overhead is
    iters-independent."""
    del iters
    from bench import _dispatch_overhead_s
    return _dispatch_overhead_s()


def bench_op(op_type, np_inputs, attrs, iters=100, warmup=None,
             grad=False, out_index=0, stage=True):
    """Time one op per registered library variant: `iters` in-graph
    steps per dispatch (run_repeated), two timed dispatches (best
    wins), null-overhead-corrected. `warmup` is accepted for API
    compatibility; the compile dispatch IS the warmup."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import ops as registry

    opdef = registry.get(op_type)
    libraries = [None] + sorted(opdef.variants)
    null_s = _null_overhead_s(iters)
    results = []
    for lib in libraries:
        main, feed, acc = _build_timed_program(
            op_type, np_inputs, attrs, grad, out_index)
        if stage:
            # stage the feed on device ONCE — run_repeated's
            # jnp.asarray passes jax.Arrays through, so the timed
            # dispatch carries no host->device traffic
            feed = {k: jax.device_put(v) for k, v in feed.items()}
        exe = fluid.Executor()
        fluid.global_scope().set_var("bench_acc",
                                     np.zeros((1,), np.float32))
        run = lambda: exe.run_repeated(  # noqa: E731
            main, feed=feed, fetch_list=[acc], iters=iters,
            library=lib or "")
        out = run()                       # compile + warmup
        if not np.all(np.isfinite(np.asarray(out[0]))):
            raise FloatingPointError(
                "%s/%s produced non-finite accumulator"
                % (op_type, lib or "base"))
        best = None
        for _ in range(2):
            t0 = time.perf_counter()
            run()                         # returns after readback
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        # same correction policy as bench._timed_loop: when the null
        # overhead is >90% of the measurement (tiny ops on a fast
        # local backend), extrapolating through the subtraction is
        # meaningless — report uncorrected (conservative) instead of
        # a near-zero artifact
        corrected = best - null_s if null_s <= best * 0.9 else best
        us = max(corrected, 1e-9) / iters * 1e6
        results.append({
            "op": op_type, "library": lib or "base",
            "us_per_call": round(us, 2),
            "iters": iters, "grad": grad, "protocol": "scan",
            "overhead_ms": round(null_s * 1e3, 1),
            "inputs": {k: list(np.shape(v))
                       for k, v in np_inputs.items()},
        })
    best = min(results, key=lambda r: r["us_per_call"])
    for r in results:
        r["best"] = r["library"] == best["library"]
    return results


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("op", nargs="?", help="op type to benchmark")
    ap.add_argument("--inputs", default="", help="X=2x3,Y=3x4[:dtype]")
    ap.add_argument("--attrs", default="", help="k=v,k2=v2")
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--grad", action="store_true",
                    help="include backward in the timed program")
    ap.add_argument("--list", action="store_true",
                    help="list ops that have library variants")
    args = ap.parse_args(argv)

    if args.list:
        from paddle_tpu import ops as registry
        for t in registry.all_op_types():
            v = registry.get(t).variants
            if v:
                print(t, "->", ", ".join(sorted(v)))
        return 0

    if not args.op:
        ap.error("op required (or --list)")
    results = bench_op(args.op, parse_inputs(args.inputs),
                       parse_attrs(args.attrs), iters=args.iters,
                       warmup=args.warmup, grad=args.grad)
    for r in results:
        print(json.dumps(r))
    return 0


if __name__ == "__main__":
    sys.exit(main())
