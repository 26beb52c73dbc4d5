"""Static-graph autodiff: append_backward.

Reference: python/paddle/fluid/backward.py (append_backward:394,
_find_op_path_:579, _append_backward_ops_:252 querying C++ per-op
GradOpMakers via core.get_grad_op_desc, dedup of repeated grads via
inserted sum ops _addup_repetitive_outputs_:135, pruning :204).

TPU-native redesign: the walk over ops in reverse and the @GRAD naming
convention are kept — users see the same program structure — but there
are no hand-written per-op grad kernels. Each appended ``vjp`` op records
its forward op's signature; at trace time the executor calls jax.vjp on
the forward lowering (executor._run_vjp_op), so gradients are exact by
construction and XLA CSE merges the re-traced forward with the original.
Gradient accumulation for vars consumed by multiple ops happens by
add-accumulation into the @GRAD env entry (no explicit sum ops needed).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Set

from . import framework, ops
from .core.enforce import InvalidArgumentError, enforce
from .framework import Variable, grad_var_name


def _op_path_to(block, target_op_index: int,
                stop_vars: Set[str]) -> List[int]:
    """Indices of ops (ascending) whose outputs can influence the target
    op, not crossing stop-gradient barriers (reference:
    backward.py:579 _find_op_path_)."""
    needed: Set[str] = set()
    target = block.ops[target_op_index]
    needed.update(target.input_arg_names)
    path = [target_op_index]
    for i in range(target_op_index - 1, -1, -1):
        op = block.ops[i]
        outs = set(op.output_arg_names)
        if outs & needed:
            path.append(i)
            for n in op.input_arg_names:
                if n not in stop_vars:
                    needed.add(n)
    path.reverse()
    return path


def _collect_stop_vars(block, no_grad_set) -> Set[str]:
    stop = set(no_grad_set or ())
    for name, var in block.vars.items():
        if var.stop_gradient:
            stop.add(name)
    return stop


def _scope_of(fwd) -> dict:
    """A gradient op carries its forward op's name scope: a layer's
    backward is charged to that layer (executor.run_block)."""
    scope = fwd.attrs.get("op_namescope")
    return {"op_namescope": scope} if scope else {}


def _append_sparse_lookup_grad(block, fwd, stop_vars) -> bool:
    """Append a lookup_table_grad op producing a SparseRows table
    gradient (the SelectedRows path of lookup_table_op.cc). Returns
    False when the table doesn't need a grad (caller falls through to
    the generic machinery, which will also produce nothing)."""
    w_name = fwd.inputs["W"][0]
    if w_name in stop_vars:
        return False
    w = block._find_var_recursive(w_name)
    out_name = fwd.outputs["Out"][0]
    og = grad_var_name(out_name)
    if not block.has_var(og):
        return False
    gn = grad_var_name(w_name)
    if not block.has_var(gn):
        block.create_var(name=gn, shape=w.shape, dtype=w.dtype,
                         stop_gradient=True)
    block.append_op(
        type="lookup_table_grad",
        inputs={"Ids": list(fwd.inputs["Ids"]), "OutGrad": [og]},
        outputs={"WGrad": [gn]},
        attrs={"height": int(w.shape[0]),
               "padding_idx": fwd.attrs.get("padding_idx", -1),
               "op_role": "backward", **_scope_of(fwd)})
    return True


def append_backward(loss: Variable, parameter_list=None, no_grad_set=None,
                    callbacks=None, grad_suffix=""):
    """Append gradient ops for ``loss`` to its program; returns
    [(param, grad_var)] like the reference (backward.py:394).

    ``grad_suffix`` namespaces this pass's gradient vars
    (``x@GRAD<suffix>``) — the analog of the reference's @RENAME@
    dedup (backward.py:135): a second differentiation over the same
    program (calc_gradient for a gradient penalty, then minimize)
    must not accumulate into the first pass's ``@GRAD`` vars.
    """
    enforce(isinstance(loss, Variable), "loss must be a Variable")
    program = loss.block.program
    block = program.global_block()

    def gname(n):
        return grad_var_name(n) + grad_suffix

    # producer op of loss
    target_index = None
    for i in range(len(block.ops) - 1, -1, -1):
        if loss.name in block.ops[i].output_arg_names:
            target_index = i
            break
    enforce(target_index is not None,
            "loss %r has no producer op in the program" % loss.name)

    stop_vars = _collect_stop_vars(block, no_grad_set)
    path = _op_path_to(block, target_index, stop_vars)

    # d(loss)/d(loss) = 1
    loss_grad = block.create_var(
        name=gname(loss.name), shape=loss.shape, dtype=loss.dtype,
        persistable=False, stop_gradient=True)
    block.append_op(
        type="fill_constant",
        outputs={"Out": [loss_grad]},
        attrs={"shape": tuple(loss.shape), "dtype": loss.dtype,
               "value": 1.0, "op_role": "backward",
               **_scope_of(block.ops[target_index])})

    # reverse walk, one vjp op per differentiable forward op
    for i in reversed(path):
        fwd = block.ops[i]
        if fwd.type == "vjp":
            # differentiate THROUGH a previous pass's gradient op:
            # double backward (reference exercises this via
            # unittests/gradient_checker.py / gradient-penalty models)
            _append_vjp2(block, fwd, i, stop_vars, gname, grad_suffix)
            continue
        if fwd.type == "vjp2":
            enforce(False, "third-order differentiation through a "
                    "vjp2 op is not supported")
        if not ops.has(fwd.type):
            continue
        opdef = ops.get(fwd.type)
        if not opdef.differentiable:
            continue

        if fwd.type == "lookup_table" and fwd.attrs.get("is_sparse"):
            # sparse embedding: emit the dedicated SparseRows grad op
            # (reference: lookup_table_op.cc is_sparse grad ->
            # SelectedRows) instead of the dense generic vjp
            if _append_sparse_lookup_grad(block, fwd, stop_vars):
                continue

        grad_outputs: Dict[str, List[str]] = {}
        any_grad = False
        for slot, _variadic in opdef.input_slots:
            if slot in opdef.nondiff_slots:
                continue
            names = fwd.inputs.get(slot, [])
            gnames = []
            for n in names:
                if n in stop_vars:
                    continue
                v = block._find_var_recursive(n)
                if v is not None and v.dtype in ("float32", "float64",
                                                 "float16", "bfloat16"):
                    gn = gname(n)
                    if not block.has_var(gn):
                        # NOT stop_gradient: a later pass must be able
                        # to differentiate through this pass's grads
                        # (gradient-penalty double backward)
                        block.create_var(name=gn, shape=v.shape,
                                         dtype=v.dtype,
                                         stop_gradient=False)
                    gnames.append(gn)
                    any_grad = True
            if gnames:
                grad_outputs[slot + "@GRAD"] = gnames
        if not any_grad:
            continue

        if fwd.type == "while" and not fwd.attrs.get("max_iters"):
            # surface the XLA constraint at BUILD time (here) instead
            # of as a trace-time failure deep in the executor: an
            # unbounded lax.while_loop is forward-only
            enforce(False,
                    "gradients through a While loop need a trip "
                    "bound: build it as layers.While(cond, "
                    "max_iters=<bound>) so it lowers to a "
                    "differentiable lax.scan (op #%d)" % i)

        out_grad_inputs = [gname(n) for n in fwd.output_arg_names]
        # the forward op's attrs parameterize its lowering again under
        # jax.vjp; its name scope is not one of them but the gradient
        # op's own
        fwd_attrs = dict(fwd.attrs)
        fwd_attrs.pop("op_namescope", None)
        block.append_op(
            type="vjp",
            inputs={"FwdIn": fwd.input_arg_names,
                    "OutGrad": [g for g in out_grad_inputs
                                if block.has_var(g)]},
            outputs=grad_outputs,
            attrs={
                "fwd_type": fwd.type,
                "fwd_inputs": {k: list(v) for k, v in fwd.inputs.items()},
                "fwd_outputs": {k: list(v)
                                for k, v in fwd.outputs.items()},
                "fwd_attrs": fwd_attrs,
                "fwd_op_index": i,
                "no_grad_vars": tuple(sorted(stop_vars)),
                "grad_suffix": grad_suffix,
                "op_role": "backward",
                **_scope_of(fwd),
            })

    # collect (param, grad) pairs
    params = block.all_parameters()
    if parameter_list is not None:
        wanted = {p if isinstance(p, str) else p.name
                  for p in parameter_list}
        params = [p for p in params if p.name in wanted]
    result = []
    for p in params:
        if not p.trainable:
            continue
        gn = gname(p.name)
        if block.has_var(gn):
            result.append((p, block.var(gn)))
    return result


def _append_vjp2(block, vop, i, stop_vars, gname, grad_suffix):
    """Append the second-order gradient op for a first-pass ``vjp`` op.

    A vjp op is a pure function (FwdIn, OutGrad) -> input-grads (the
    pullback of its forward op). Differentiating through it is
    jax.vjp of that pullback application (executor._run_vjp2_op);
    here we only declare which of its inputs receive this pass's
    gradients and which of its products carry upstream cotangents.
    """
    inner_suffix = vop.attrs.get("grad_suffix", "")

    grad_outputs = {"FwdIn@GRAD": [], "OutGrad@GRAD": []}
    fwd_in = list(vop.inputs.get("FwdIn", []))
    out_grad = list(vop.inputs.get("OutGrad", []))
    any_grad = False
    for key, names in (("FwdIn@GRAD", fwd_in),
                       ("OutGrad@GRAD", out_grad)):
        for n in names:
            if n in stop_vars:
                continue
            v = block._find_var_recursive(n)
            if v is None or v.dtype not in ("float32", "float64",
                                            "float16", "bfloat16"):
                continue
            gn = gname(n)
            if not block.has_var(gn):
                block.create_var(name=gn, shape=v.shape, dtype=v.dtype,
                                 stop_gradient=False)
            grad_outputs[key].append(gn)
            any_grad = True
    if not any_grad:
        return

    # upstream cotangents: this pass's grads of the vjp op's products
    up = [gname(g) for g in
          (n for outs in vop.outputs.values() for n in outs)]
    block.append_op(
        type="vjp2",
        inputs={"FwdIn": fwd_in, "OutGrad": out_grad,
                "UpGrad": [g for g in up if block.has_var(g)]},
        outputs=grad_outputs,
        attrs=dict(vop.attrs, grad_suffix_inner=inner_suffix,
                   grad_suffix=grad_suffix,
                   no_grad_vars_outer=tuple(sorted(stop_vars)),
                   op_role="backward"))


def calc_gradient(targets, inputs, target_gradients=None,
                  no_grad_set=None):
    """Reference: backward.py:619. Gradients of targets w.r.t. inputs.

    Multiple targets follow the reference semantics: the returned
    grads are ``d(sum_i <targets[i], target_gradients[i]>)/d(inputs)``
    (cotangents default to ones). Each call namespaces its gradient
    vars with a fresh suffix, so calc_gradient composes with a later
    ``minimize``/``append_backward`` over the same program — the
    double-backward (gradient-penalty) pattern.
    """
    if isinstance(targets, Variable):
        targets = [targets]
    if isinstance(inputs, Variable):
        inputs = [inputs]
    enforce(len(targets) >= 1, "calc_gradient needs at least 1 target")
    if target_gradients is None:
        target_gradients = [None] * len(targets)
    if isinstance(target_gradients, Variable):
        target_gradients = [target_gradients]
    enforce(len(target_gradients) == len(targets),
            "target_gradients must match targets (%d vs %d)"
            % (len(target_gradients), len(targets)))

    program = targets[0].block.program
    block = program.global_block()
    count = getattr(program, "_calc_grad_count", 0)
    program._calc_grad_count = count + 1
    suffix = "@CG%d" % count

    # combined scalar: sum_i <t_i, tg_i>; its backward yields exactly
    # the requested vector-Jacobian products
    from . import layers
    with framework.program_guard(program):
        terms = []
        for t, tg in zip(targets, target_gradients):
            if tg is None:
                terms.append(layers.reduce_sum(t))
            else:
                terms.append(layers.reduce_sum(
                    layers.elementwise_mul(t, tg)))
        combined = terms[0]
        for t in terms[1:]:
            combined = layers.elementwise_add(combined, t)

    stop = set(no_grad_set or ())
    for tg in target_gradients:
        if tg is not None:
            stop.add(tg.name)
    append_backward(combined, no_grad_set=stop, grad_suffix=suffix)
    outs = []
    for iv in inputs:
        gn = grad_var_name(iv.name) + suffix
        outs.append(block.var(gn) if block.has_var(gn) else None)
    return outs


def gradients(targets, inputs, target_gradients=None, no_grad_set=None):
    return calc_gradient(targets, inputs, target_gradients, no_grad_set)
