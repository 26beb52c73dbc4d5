"""Program rewrite for mixed precision: insert casts around white-list
ops.

Reference: python/paddle/fluid/contrib/mixed_precision/fp16_utils.py
(_insert_cast_op / rewrite_program). The reference retypes every var
and inserts cast ops both directions; here only *inputs* of white-list
ops are cast down — the op then computes in bf16 (jnp type promotion),
and the first consumer that mixes in a float32 operand promotes back.
Parameters themselves keep float32 storage (master weights by
construction, the role of the reference's master-weight copies), and
XLA fuses the casts into the surrounding kernels so the rewrite costs
nothing at run time."""

from __future__ import annotations

from ... import framework
from ...framework import convert_dtype

# Output slots that stay float32 by lowering contract even when the
# op itself runs on low-precision inputs (the lowering computes them
# in f32 internally and returns f32) — marking them "low" would make
# downstream gray consumers cast genuine f32 operands down
# (e.g. per-token loss weights multiplied into the Loss).
F32_CONTRACT_OUTPUTS = {
    "softmax_with_cross_entropy": ("Loss",),
    "fused_linear_xent": ("Loss",),
    "layer_norm": ("Mean", "Variance"),
    "moe_held_experts": ("CountersOut",),
    "kda_attention": ("CountersOut",),
}

# Input slots never cast down when a gray op goes low: training
# targets must reach the lowering at full precision (a bf16-rounded
# soft label loses ~3 decimal digits the loss then inherits; the
# black-list era kept them exactly f32).
F32_CONTRACT_INPUTS = {
    "softmax_with_cross_entropy": ("Label",),
    "fused_linear_xent": ("Label",),
    "moe_held_experts": ("Weight", "Counters"),
    "kda_attention": ("G", "Counters"),
}


def rewrite_program(main_program, amp_lists, dest_dtype="bfloat16"):
    """Insert casts so the low-precision region PROPAGATES through the
    forward graph (reference: fp16_utils.py rewrite_program's
    white/black/gray semantics; forward ops only — backward
    regenerates through the vjp of the rewritten forward):

    - white ops: every float32 input is cast down; their float outputs
      become low-precision.
    - gray ops: FOLLOW their inputs — if any float input is already
      low, remaining float32 float inputs (residual branches, biases,
      LN scales) are cast down too and the outputs stay low. This is
      what keeps the residual stream bf16 end-to-end: without it every
      ``bf16 matmul out + f32 residual`` add re-promotes to f32 and
      the entire inter-matmul activation traffic (residuals, LN,
      dropout, [B,S,D] saves for backward) runs at double width —
      measured round 4 as the dominant non-MXU HBM load at flagship
      shape.
    - black and unlisted ops: low inputs are cast UP to float32
      explicitly (there may be no f32 operand left to trigger
      promotion), outputs leave the low region.

    Returns the number of casts inserted."""
    dest_dtype = convert_dtype(dest_dtype)

    def is_float(var):
        return var is not None and var.dtype in (
            "float32", "float64", "float16", "bfloat16")

    # low set is program-wide: a white op's bf16 output in a parent
    # block must still trigger gray propagation / black up-casts when
    # read inside a sub-block (while/cond bodies)
    low = set()   # vars carrying dest_dtype as a result of the pass
    n_inserted = [0]
    for block in main_program.blocks:
        new_ops = []
        # per-block cast caches so one var feeding several ops is cast
        # once (XLA would CSE it anyway; this keeps the program small)
        cast_down, cast_up = {}, {}

        def insert_cast(name, var, to_dtype, cache, sink):
            if name not in cache:
                n_inserted[0] += 1
                cast_var = block.create_var(
                    name=framework.unique_name.generate(
                        name + ".cast_" + to_dtype),
                    shape=tuple(var.shape),
                    dtype=to_dtype,
                    stop_gradient=var.stop_gradient)
                sink.append(framework.Operator(
                    block, "cast",
                    inputs={"X": [name]},
                    outputs={"Out": [cast_var.name]},
                    attrs={"dtype": to_dtype,
                           # AMP's own layer kind in a device trace
                           "op_namescope": "/amp/"}))
                cache[name] = cast_var.name
            return cache[name]

        for op in block.ops:
            role = op.attrs.get("op_role")
            if role in ("backward", "optimize") or op.type == "cast":
                new_ops.append(op)
                for n in op.output_arg_names:
                    cast_down.pop(n, None)
                    cast_up.pop(n, None)
                    low.discard(n)
                continue
            white = op.type in amp_lists.white_list
            gray = op.type in amp_lists.gray_list
            float_ins = []
            contract_ins = []  # F32-contract slots (e.g. Label)
            keep_f32_slots = F32_CONTRACT_INPUTS.get(op.type, ())
            for slot, names in op.inputs.items():
                dest = (contract_ins if slot in keep_f32_slots
                        else float_ins)
                for j, name in enumerate(names):
                    var = block._find_var_recursive(name)
                    if is_float(var):
                        dest.append((names, j, name, var))
            any_low = any(name in low or var.dtype == dest_dtype
                          for _, _, name, var in float_ins)
            if white or (gray and any_low):
                for names, j, name, var in float_ins:
                    if var.dtype != "float32" or name in low:
                        continue
                    names[j] = insert_cast(name, var, dest_dtype,
                                           cast_down, new_ops)
                new_ops.append(op)
                f32_slots = F32_CONTRACT_OUTPUTS.get(op.type, ())
                exempt = set()
                for slot in f32_slots:
                    exempt.update(op.outputs.get(slot, ()))
                for n in op.output_arg_names:
                    if n in exempt:
                        continue
                    v = block._find_var_recursive(n)
                    if is_float(v) or v is None:
                        low.add(n)
            elif gray:
                # no low input: pass through untouched, stays f32
                new_ops.append(op)
            else:
                # black or unlisted: pull low inputs back to f32
                # contract slots (labels) are exempt from cast-DOWN,
                # not from cast-UP: an in-graph low-precision label
                # still gets pulled back to f32 here (ADVICE r4)
                for names, j, name, var in float_ins + contract_ins:
                    if name in low or var.dtype == dest_dtype:
                        names[j] = insert_cast(name, var, "float32",
                                               cast_up, new_ops)
                new_ops.append(op)
            # a write to a var invalidates its cached casts and any
            # stale low marking from a previous write
            for n in op.output_arg_names:
                cast_down.pop(n, None)
                cast_up.pop(n, None)
                if not (white or (gray and any_low)):
                    low.discard(n)
        block.ops = new_ops
    main_program._bump()
    return n_inserted[0]
