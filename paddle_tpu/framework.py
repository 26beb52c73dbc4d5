"""Program / Block / Variable / Operator — the user-facing static-graph IR.

Reference: python/paddle/fluid/framework.py (Variable:366, Operator:927,
Block:1375 append_op:1671, Program:2714, Parameter:3498) and the protobuf
ProgramDesc IR it mirrors (paddle/fluid/framework/framework.proto:184).

TPU-native redesign: the reference serializes this graph to protobuf and
hands it to a C++ op-by-op interpreter (executor.cc:415). Here the Program
is *lightweight metadata only* — at run time the Executor traces every op
through its pure-JAX implementation into ONE XLA computation, compiles it
once, and launches a single device program per step. Ops never execute
individually on device; the graph exists so users keep the reference's
declarative build-then-run workflow (layers append ops, optimizers append
backward + update ops, transpilers rewrite programs).
"""

from __future__ import annotations

import contextlib
import copy
import itertools
from typing import Any, Dict, List, Optional

import numpy as np

from . import unique_name
from .core.enforce import (InvalidArgumentError, NotFoundError, enforce)

# ---------------------------------------------------------------------------
# dtype handling (reference: framework.proto VarType:105; convert_np_dtype)
# ---------------------------------------------------------------------------

_DTYPE_ALIASES = {
    "float32": "float32", "fp32": "float32", "f32": "float32",
    "float64": "float64", "fp64": "float64", "double": "float64",
    "float16": "float16", "fp16": "float16",
    "bfloat16": "bfloat16", "bf16": "bfloat16",
    "int8": "int8", "uint8": "uint8",
    "int16": "int16", "int32": "int32", "int64": "int64",
    "bool": "bool",
}


def convert_dtype(dtype) -> str:
    if dtype is None:
        return "float32"
    if isinstance(dtype, str):
        key = dtype.lower()
        if key in _DTYPE_ALIASES:
            return _DTYPE_ALIASES[key]
        raise InvalidArgumentError("unsupported dtype string %r" % dtype)
    try:
        return _DTYPE_ALIASES[np.dtype(dtype).name]
    except Exception:
        pass
    name = getattr(dtype, "name", None) or str(dtype)
    if name in _DTYPE_ALIASES:
        return _DTYPE_ALIASES[name]
    raise InvalidArgumentError("unsupported dtype %r" % (dtype,))


# ---------------------------------------------------------------------------
# Variable / Parameter
# ---------------------------------------------------------------------------

class Variable:
    """Symbolic tensor in a Block (reference: framework.py:366).

    ``shape`` may contain -1 in the leading (batch) position for feed
    variables; concrete shapes are bound at trace time from the feed. All
    other dims are static — XLA compiles static shapes; ragged data is
    padded/bucketed at the pipeline boundary (replaces the reference's
    LoDTensor, lod_tensor.h:110).
    """

    def __init__(self, block, name=None, shape=None, dtype=None,
                 persistable=False, stop_gradient=False, is_data=False,
                 lod_level=0, **kwargs):
        self.block = block
        self.name = name or unique_name.generate("_generated_var")
        self.shape = tuple(shape) if shape is not None else ()
        self.dtype = convert_dtype(dtype)
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.is_data = is_data
        self.lod_level = lod_level
        # Optional jax.sharding.PartitionSpec annotation consumed by the
        # parallel layer (replaces the reference's multi_devices_graph_pass
        # per-device cloning: sharding is declarative here).
        self.sharding = kwargs.get("sharding", None)
        self.op = None  # producer op, set by append_op

    # -- fluid-compatible sugar --------------------------------------------
    @property
    def grad_name(self):
        return grad_var_name(self.name)

    def astype(self, dtype):
        from .layers import tensor as _t
        return _t.cast(self, dtype)

    def __repr__(self):
        return "Variable(%s, shape=%s, dtype=%s%s)" % (
            self.name, self.shape, self.dtype,
            ", persistable" if self.persistable else "")

    __str__ = __repr__

    # Operator overloads route through the layers API so expressions like
    # ``a + b`` append ops exactly as fluid's math_op_patch does.
    def _binary(self, other, fn, reverse=False):
        from .layers import math_op_patch as mop
        return mop.binary(self, other, fn, reverse)

    def __add__(self, o):
        return self._binary(o, "elementwise_add")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binary(o, "elementwise_sub")

    def __rsub__(self, o):
        return self._binary(o, "elementwise_sub", reverse=True)

    def __mul__(self, o):
        return self._binary(o, "elementwise_mul")

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binary(o, "elementwise_div")

    def __rtruediv__(self, o):
        return self._binary(o, "elementwise_div", reverse=True)

    def __pow__(self, o):
        return self._binary(o, "elementwise_pow")

    def __neg__(self):
        from .layers import nn
        return nn.scale(self, scale=-1.0)

    def __lt__(self, o):
        return self._binary(o, "less_than")

    def __le__(self, o):
        return self._binary(o, "less_equal")

    def __gt__(self, o):
        return self._binary(o, "greater_than")

    def __ge__(self, o):
        return self._binary(o, "greater_equal")

    def __getitem__(self, item):
        from .layers import tensor as _t
        return _t._getitem(self, item)


def grad_var_name(name: str) -> str:
    """Reference: framework ``GradVarName`` — appends @GRAD."""
    return name + "@GRAD"


class Parameter(Variable):
    """Trainable persistable variable (reference: framework.py:3498)."""

    def __init__(self, block, shape, dtype, **kwargs):
        enforce(shape is not None and len(shape) >= 0, "param needs shape")
        kwargs.setdefault("persistable", True)
        super().__init__(block, shape=shape, dtype=dtype, **kwargs)
        self.trainable = kwargs.get("trainable", True)
        self.optimize_attr = kwargs.get("optimize_attr", {"learning_rate": 1.0})
        self.regularizer = kwargs.get("regularizer", None)
        self.gradient_clip_attr = kwargs.get("gradient_clip_attr", None)
        self.do_model_average = kwargs.get("do_model_average", None)
        self.is_distributed = kwargs.get("is_distributed", False)


# ---------------------------------------------------------------------------
# Operator
# ---------------------------------------------------------------------------

class Operator:
    """One op record (reference: framework.py:927 / OpDesc framework.proto:43).

    inputs/outputs map slot name -> list of variable names, exactly like
    OpDesc's name->var-list maps. ``attrs`` must be trace-time constants
    (python scalars/tuples/strings) — they parameterize the JAX lowering.
    """

    def __init__(self, block, type, inputs=None, outputs=None, attrs=None):
        self.block = block
        self.type = type
        self.inputs: Dict[str, List[str]] = {}
        self.outputs: Dict[str, List[str]] = {}
        self.attrs: Dict[str, Any] = dict(attrs or {})

        def _norm(mapping):
            out = {}
            for slot, vars_ in (mapping or {}).items():
                if vars_ is None:
                    out[slot] = []
                elif isinstance(vars_, (list, tuple)):
                    out[slot] = [v.name if isinstance(v, Variable) else v
                                 for v in vars_]
                else:
                    v = vars_
                    out[slot] = [v.name if isinstance(v, Variable) else v]
            return out

        self.inputs = _norm(inputs)
        self.outputs = _norm(outputs)

    def input(self, slot):
        return self.inputs.get(slot, [])

    def output(self, slot):
        return self.outputs.get(slot, [])

    @property
    def input_arg_names(self):
        return [n for vs in self.inputs.values() for n in vs]

    @property
    def output_arg_names(self):
        return [n for vs in self.outputs.values() for n in vs]

    def attr(self, name):
        return self.attrs.get(name)

    def has_attr(self, name):
        return name in self.attrs

    def _set_attr(self, name, val):
        self.attrs[name] = val

    def __repr__(self):
        return "{%s: (%s) -> (%s)}" % (
            self.type,
            ", ".join("%s=%s" % kv for kv in self.inputs.items()),
            ", ".join("%s=%s" % kv for kv in self.outputs.items()))

    __str__ = __repr__


# ---------------------------------------------------------------------------
# Block
# ---------------------------------------------------------------------------

class Block:
    """Reference: framework.py:1375 / BlockDesc framework.proto:171."""

    def __init__(self, program, idx, parent_idx=-1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars: Dict[str, Variable] = {}
        self.ops: List[Operator] = []

    @property
    def parent_block(self):
        if self.parent_idx == -1:
            return None
        return self.program.block(self.parent_idx)

    def create_var(self, **kwargs) -> Variable:
        name = kwargs.get("name") or unique_name.generate("_generated_var")
        kwargs["name"] = name
        if name in self.vars:
            return self.vars[name]
        var = Variable(self, **kwargs)
        self.vars[name] = var
        self.program._bump()
        return var

    def create_parameter(self, **kwargs) -> Parameter:
        name = kwargs.get("name") or unique_name.generate("_generated_param")
        kwargs.pop("name", None)
        # Parameters always live in block 0 (reference: framework.py
        # Block.create_parameter promotes to global block).
        gblock = self.program.global_block()
        param = Parameter(gblock, name=name, **kwargs)
        gblock.vars[name] = param
        self.program._bump()
        return param

    def var(self, name) -> Variable:
        v = self._find_var_recursive(name)
        if v is None:
            raise NotFoundError("variable %r not found in block %d" %
                                (name, self.idx))
        return v

    def has_var(self, name) -> bool:
        return self._find_var_recursive(name) is not None

    def _find_var_recursive(self, name) -> Optional[Variable]:
        b = self
        while b is not None:
            if name in b.vars:
                return b.vars[name]
            b = b.parent_block
        return None

    def all_parameters(self) -> List[Parameter]:
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    def append_op(self, type, inputs=None, outputs=None, attrs=None,
                  index=None) -> Operator:
        role = getattr(self.program, "_current_op_role", None)
        if role is not None and (attrs is None
                                 or "op_role" not in attrs):
            attrs = dict(attrs or {}, op_role=role)
        if _name_scope_stack and (attrs is None
                                  or "op_namescope" not in attrs):
            # which layer kind built this op (reference: append_op
            # stamping _full_name_scope()): metadata only, read by the
            # executor to name the op's lowering in the device trace
            attrs = dict(attrs or {}, op_namescope=_full_name_scope())
        op = Operator(self, type, inputs, outputs, attrs)
        if index is None:
            self.ops.append(op)
        else:
            self.ops.insert(index, op)
        for slot_vars in (outputs or {}).values():
            vs = slot_vars if isinstance(slot_vars, (list, tuple)) else [slot_vars]
            for v in vs:
                if isinstance(v, Variable):
                    v.op = op
        _infer_shapes(self, op)
        self.program._bump()
        return op

    def prepend_op(self, **kwargs) -> Operator:
        return self.append_op(index=0, **kwargs)

    def __repr__(self):
        lines = ["Block(%d) {" % self.idx]
        for v in self.vars.values():
            lines.append("  " + repr(v))
        for op in self.ops:
            lines.append("  " + repr(op))
        lines.append("}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Shape/dtype inference at op-append time
# ---------------------------------------------------------------------------

# Placeholder concrete sizes substituted for -1 (batch) dims during
# abstract evaluation; every -1 in one op shares one sentinel (the
# dims represent the same unknown batch — mixing two would break
# broadcasting under eval_shape), but the sentinel is chosen per op to
# collide with none of the op's concrete dims or integer attrs, so a
# real dimension of 8191 (vocab padded to a prime, etc.) can no longer
# be silently mis-inferred as dynamic. Primes: no product of smaller
# concrete dims can equal one.
_DYN_SENTINELS = (8191, 7919, 7883, 7877, 7873, 7867, 7853, 7841)


def _pick_dyn_dim(avoid):
    for p in _DYN_SENTINELS:
        if p not in avoid:
            return p
    p = 15013
    while p in avoid:
        p += 2
    return p


# True while ``_infer_shapes`` evaluates an op's lowering abstractly at
# graph-build time (on the variables' declared types, before any AMP
# rewrite): counters that say which lowering a site TOOK
# (``sdpa_lowering.*``, ``moe_lowering.*``) leave that pass out.
_inferring_shapes = False


def inferring_shapes() -> bool:
    return _inferring_shapes


def _infer_shapes(block, op):
    """Infer output var shapes/dtypes with jax.eval_shape over the op's
    lowering (the analog of the reference's per-op InferShape,
    operator.cc:933 — but derived from the single source of truth, the
    lowering itself). Best-effort: failures leave shapes unknown."""
    if op.type in ("vjp", "vjp2"):
        return
    try:
        from . import ops as _ops
        if not _ops.has(op.type):
            return
        opdef = _ops.get(op.type)
    except Exception:
        return
    import jax
    import numpy as _np

    had_dyn = False
    arg_structs = []
    try:
        avoid = set()
        for slot, _variadic in opdef.input_slots:
            for n in op.inputs.get(slot, []):
                v = block._find_var_recursive(n)
                if v is not None and v.shape:
                    avoid.update(d for d in v.shape if d > 0)

        def _collect_ints(a):
            if isinstance(a, bool):
                return
            if isinstance(a, int):
                avoid.add(a)
            elif isinstance(a, (list, tuple)):
                for e in a:
                    _collect_ints(e)

        for a in op.attrs.values():
            _collect_ints(a)
        # primes defend against products of concrete dims equaling the
        # sentinel; pairwise sums defend concat-style derived dims.
        # Iterate a snapshot: mutating avoid mid-loop would pair
        # against already-added sums (order-dependent triple sums)
        if len(avoid) <= 64:
            base = list(avoid)
            for x in base:
                for y in base:
                    avoid.add(x + y)
        dyn_dim = _pick_dyn_dim(avoid)
        for slot, variadic in opdef.input_slots:
            names = op.inputs.get(slot, [])
            structs = []
            for n in names:
                v = block._find_var_recursive(n)
                if v is None or v.shape is None:
                    return
                shape = []
                for d in v.shape:
                    if d == -1:
                        had_dyn = True
                        shape.append(dyn_dim)
                    else:
                        shape.append(d)
                structs.append(jax.ShapeDtypeStruct(
                    tuple(shape), _np.dtype(v.dtype)))
            if variadic:
                arg_structs.append(structs)
            elif not names:
                arg_structs.append(None)
            else:
                arg_structs.append(structs[0])
        attrs = {k: v for k, v in op.attrs.items()
                 if k not in ("op_role", "op_namescope", "gate")}
        if opdef.needs_rng:
            def fn(*args, **kw):
                import jax as _jax
                kw = dict(kw)
                kw["rng"] = _jax.random.key(0)
                return opdef.fn(*args, **kw)
        else:
            fn = opdef.fn
        attrs.pop("rng", None)
        global _inferring_shapes
        with _trace_program_guard(block.program):
            _inferring_shapes = True
            try:
                out = jax.eval_shape(lambda *a: fn(*a, **attrs),
                                     *arg_structs)
            finally:
                _inferring_shapes = False
    except Exception as e:
        # Best-effort by design (abstract eval can't see runtime-only
        # constructs), but a typo'd op should not fail silently: under
        # FLAGS_infer_shape_debug the failure surfaces here, at the
        # append_op site, instead of as a confusing trace error later.
        from .core.flags import FLAGS as _FLAGS
        if _FLAGS.infer_shape_debug:
            import warnings
            warnings.warn(
                "shape inference failed for op %r: %s: %s"
                % (op.type, type(e).__name__, e), stacklevel=3)
        return

    nslots = len(opdef.output_slots)
    if nslots == 1:
        out = (out,)
    for slot, res in zip(opdef.output_slots, out):
        variadic = slot.endswith("*")
        sname = slot[:-1] if variadic else slot
        names = op.outputs.get(sname, [])
        results = list(res) if variadic else [res]
        for n, r in zip(names, results):
            v = block._find_var_recursive(n)
            if v is None or getattr(r, "shape", None) is None:
                continue
            # multiples of the sentinel are flatten/tile products of
            # the dynamic dim (the sentinel is a large prime no real
            # dim combination reaches) — map them back to -1 too
            shape = tuple(
                -1 if (had_dyn and d >= dyn_dim and d % dyn_dim == 0)
                else d for d in r.shape)
            if v.shape == () or v.shape is None or v.shape == shape:
                if not v.persistable:
                    v.shape = shape
                    v.dtype = convert_dtype(r.dtype)


# ---------------------------------------------------------------------------
# Program
# ---------------------------------------------------------------------------

_program_uid_counter = itertools.count(1)


class Program:
    """Reference: framework.py:2714 / ProgramDesc framework.proto:184.

    ``_version`` increments on every mutation; the Executor uses
    ``(_uid, _version)`` as its compilation-cache key (the analog of the
    reference re-Preparing an ExecutorPrepareContext when the program
    changes). ``_uid`` is assigned monotonically — unlike ``id()``, it
    can never be reused after a program is garbage-collected, so a cache
    hit always belongs to THIS program.
    """

    def __init__(self):
        self.blocks: List[Block] = [Block(self, 0)]
        self.current_block_idx = 0
        self._uid = next(_program_uid_counter)
        self._version = 0
        self._seed = 0
        self._is_test = False
        # Set by optimizers/transpilers for introspection parity.
        self._op_role_var = []
        # Parallel/compile options attached by CompiledProgram.
        self._exec_strategy = None
        self._build_strategy = None

    # -- structure ---------------------------------------------------------
    def global_block(self) -> Block:
        return self.blocks[0]

    def block(self, idx) -> Block:
        return self.blocks[idx]

    def current_block(self) -> Block:
        return self.blocks[self.current_block_idx]

    def _create_block(self, parent_idx=None) -> Block:
        new_idx = len(self.blocks)
        parent = self.current_block_idx if parent_idx is None else parent_idx
        b = Block(self, new_idx, parent)
        self.blocks.append(b)
        self.current_block_idx = new_idx
        self._bump()
        return b

    def _rollback(self):
        self.current_block_idx = self.current_block().parent_idx

    def _bump(self):
        self._version += 1

    # -- properties --------------------------------------------------------
    @property
    def random_seed(self):
        return self._seed

    @random_seed.setter
    def random_seed(self, seed):
        self._seed = int(seed)

    @property
    def num_blocks(self):
        return len(self.blocks)

    def all_parameters(self) -> List[Parameter]:
        return self.global_block().all_parameters()

    def list_vars(self):
        for b in self.blocks:
            yield from b.vars.values()

    # -- cloning (reference: Program.clone, strips training-only behavior) -
    def clone(self, for_test=False) -> "Program":
        p = copy.deepcopy(self)
        p._is_test = for_test
        if for_test:
            # Strip backward + optimizer ops (the reference prunes ops
            # with OpRole Backward/Optimize, framework.py clone:2770) —
            # otherwise "evaluation" runs would update parameters.
            for b in p.blocks:
                b.ops = [op for op in b.ops
                         if op.attrs.get("op_role") not in
                         ("backward", "optimize")]
                for op in b.ops:
                    if "is_test" in _TEST_MODE_OPS.get(op.type, ()):
                        op.attrs["is_test"] = True
                # Prune vars no surviving op references (optimizer
                # state, grads) — otherwise every eval step would
                # shuttle dead Adam moments through the jitted program.
                live = Program._referenced_names(b)
                b.vars = {n: v for n, v in b.vars.items()
                          if n in live or v.is_data}
        p._bump()
        return p

    @staticmethod
    def _referenced_names(block) -> set:
        """Every var name an op of ``block`` reads or writes."""
        live = set()
        for op in block.ops:
            for ns in op.inputs.values():
                live.update(ns)
            for ns in op.outputs.values():
                live.update(ns)
        return live

    def _prune(self, targets) -> "Program":
        """Slice the program to the ops needed to compute ``targets``
        (reference: Program._prune → C++ framework/prune.cc). Walks the
        op list backward keeping producers of needed vars."""
        target_names = set()
        for t in targets:
            target_names.add(t.name if isinstance(t, Variable) else t)
        p = copy.deepcopy(self)
        # prune the ROOT block only: sub-blocks (while/rnn bodies) are
        # executed by their parent op and their ops never produce the
        # root fetch names — slicing them against root targets would
        # empty them (prune.cc keeps sub-blocks of kept ops whole)
        b = p.global_block()
        needed = set(target_names)
        kept = []
        for op in reversed(b.ops):
            out_names = [n for ns in op.outputs.values() for n in ns]
            if any(n in needed for n in out_names):
                kept.append(op)
                for ns in op.inputs.values():
                    needed.update(ns)
        kept.reverse()
        b.ops = kept
        live = Program._referenced_names(b)
        # only sub-blocks reachable from KEPT ops survive (prune.cc
        # semantics); unreachable bodies are emptied — block indices
        # must stay stable, so the Block objects themselves remain
        reachable = set()
        frontier = list(b.ops)
        while frontier:
            op = frontier.pop()
            idx = op.attrs.get("sub_block")
            if isinstance(idx, int) and idx not in reachable \
                    and 0 <= idx < len(p.blocks):
                reachable.add(idx)
                frontier.extend(p.blocks[idx].ops)
        for sub in p.blocks[1:]:
            if sub.idx in reachable:
                # vars closed over by surviving sub-block ops resolve
                # through the parent chain — keep them live in root
                live |= Program._referenced_names(sub)
            else:
                sub.ops = []
                sub.vars = {}
        b.vars = {n: v for n, v in b.vars.items()
                  if n in live or n in target_names}
        p._bump()
        return p

    # -- serialization (reference: ProgramDesc protobuf round-trip;
    #    framework.proto:184 / Program.parse_from_string) ------------------
    def to_dict(self) -> dict:
        blocks = []
        for b in self.blocks:
            vars_ = []
            for v in b.vars.values():
                d = {"name": v.name, "shape": list(v.shape),
                     "dtype": v.dtype, "persistable": v.persistable,
                     "stop_gradient": v.stop_gradient,
                     "is_data": v.is_data, "lod_level": v.lod_level}
                if isinstance(v, Parameter):
                    d["is_parameter"] = True
                    d["trainable"] = v.trainable
                    d["optimize_attr"] = v.optimize_attr
                vars_.append(d)
            ops_ = [{"type": op.type,
                     "inputs": {k: list(vv) for k, vv in
                                op.inputs.items()},
                     "outputs": {k: list(vv) for k, vv in
                                 op.outputs.items()},
                     "attrs": op.attrs} for op in b.ops]
            blocks.append({"idx": b.idx, "parent_idx": b.parent_idx,
                           "vars": vars_, "ops": ops_})
        out = {"version": 1, "seed": self._seed,
               "is_test": self._is_test, "blocks": blocks}
        if getattr(self, "_anomaly_guard", None) is not None:
            # carry the guard config (loss name) so a round-tripped
            # program keeps the loss-finiteness check, not only the
            # gate attrs
            out["anomaly_guard"] = dict(self._anomaly_guard)
        return out

    @staticmethod
    def from_dict(desc: dict) -> "Program":
        enforce(desc.get("version") == 1,
                "unsupported program version %r" % desc.get("version"))
        p = Program()
        p._seed = desc.get("seed", 0)
        p._is_test = desc.get("is_test", False)
        for bd in desc["blocks"]:
            if bd["idx"] == 0:
                b = p.global_block()
            else:
                b = Block(p, bd["idx"], bd["parent_idx"])
                p.blocks.append(b)
            for vd in bd["vars"]:
                kw = dict(shape=vd["shape"], dtype=vd["dtype"],
                          name=vd["name"],
                          persistable=vd["persistable"],
                          stop_gradient=vd["stop_gradient"],
                          is_data=vd["is_data"],
                          lod_level=vd["lod_level"])
                if vd.get("is_parameter"):
                    v = Parameter(b, trainable=vd.get("trainable", True),
                                  optimize_attr=vd.get("optimize_attr"),
                                  **kw)
                else:
                    v = Variable(b, **kw)
                b.vars[vd["name"]] = v
            for od in bd["ops"]:
                op = Operator(b, od["type"])
                op.inputs = {k: list(v) for k, v in od["inputs"].items()}
                op.outputs = {k: list(v) for k, v in
                              od["outputs"].items()}
                op.attrs = dict(od["attrs"])
                b.ops.append(op)
        # a guarded train program round-trips its gate attrs; restore
        # the guard config (with its loss name) or, for descs written
        # before the config was serialized, sniff the gate attrs
        # (resilience.guard.FLAG_KEY — string literal to avoid a cycle)
        if desc.get("anomaly_guard") is not None:
            p._anomaly_guard = dict(desc["anomaly_guard"])
        elif any(op.attrs.get("gate") == "__guard_all_finite__"
                 for blk in p.blocks for op in blk.ops):
            p._anomaly_guard = {"loss": None}
        p._bump()
        return p

    def __deepcopy__(self, memo):
        p = Program.__new__(Program)
        memo[id(self)] = p
        p.blocks = []
        p.current_block_idx = self.current_block_idx
        # a clone is a DIFFERENT program: fresh cache identity
        p._uid = next(_program_uid_counter)
        p._version = self._version
        p._seed = self._seed
        p._is_test = self._is_test
        p._op_role_var = list(self._op_role_var)
        p._exec_strategy = self._exec_strategy
        p._build_strategy = self._build_strategy
        if getattr(self, "_anomaly_guard", None) is not None:
            # cloned gate attrs need the guard marker or the gated ops
            # would dangle on the missing flag (a for_test clone prunes
            # the gated ops, so carrying the marker there is inert)
            p._anomaly_guard = dict(self._anomaly_guard)
        if hasattr(self, "_distributed_lookups"):
            # >HBM table metadata (layers.embedding is_distributed=True)
            p._distributed_lookups = [dict(d) for d in
                                      self._distributed_lookups]
        for b in self.blocks:
            nb = Block(p, b.idx, b.parent_idx)
            p.blocks.append(nb)
        for b, nb in zip(self.blocks, p.blocks):
            for name, v in b.vars.items():
                kw = dict(shape=v.shape, dtype=v.dtype, name=v.name,
                          persistable=v.persistable,
                          stop_gradient=v.stop_gradient, is_data=v.is_data,
                          lod_level=v.lod_level, sharding=v.sharding)
                if isinstance(v, Parameter):
                    nv = Parameter(nb, trainable=v.trainable,
                                   optimize_attr=v.optimize_attr,
                                   regularizer=v.regularizer, **kw)
                else:
                    nv = Variable(nb, **kw)
                nb.vars[name] = nv
            for op in b.ops:
                nop = Operator(nb, op.type)
                nop.inputs = {k: list(v) for k, v in op.inputs.items()}
                nop.outputs = {k: list(v) for k, v in op.outputs.items()}
                nop.attrs = copy.deepcopy(op.attrs, memo)
                nb.ops.append(nop)
        return p

    def __repr__(self):
        return "\n".join(repr(b) for b in self.blocks)

    __str__ = __repr__


# Ops whose behavior flips in inference mode (reference: clone(for_test)).
_TEST_MODE_OPS = {
    "dropout": ("is_test",),
    "batch_norm": ("is_test",),
}


# ---------------------------------------------------------------------------
# Default programs + guards (reference: framework.py two global programs)
# ---------------------------------------------------------------------------

_main_program_ = Program()
_startup_program_ = Program()


def default_main_program() -> Program:
    return _main_program_


def default_startup_program() -> Program:
    return _startup_program_


def switch_main_program(program: Program) -> Program:
    global _main_program_
    prev, _main_program_ = _main_program_, program
    return prev


def switch_startup_program(program: Program) -> Program:
    global _startup_program_
    prev, _startup_program_ = _startup_program_, program
    return prev


@contextlib.contextmanager
def program_guard(main_program, startup_program=None):
    prev_main = switch_main_program(main_program)
    prev_startup = None
    if startup_program is not None:
        prev_startup = switch_startup_program(startup_program)
    try:
        yield
    finally:
        switch_main_program(prev_main)
        if prev_startup is not None:
            switch_startup_program(prev_startup)


@contextlib.contextmanager
def op_role_guard(program, role):
    """Stamp ``op_role`` on every op appended to ``program`` inside the
    block (unless an op sets its own). The analog of the reference's
    ``program._optimized_guard`` / OpRole attr machinery
    (framework.py:1268): clone(for_test=True) prunes by op_role, so
    machinery appended AROUND the optimizer (AMP loss scaling, grad
    clipping) must carry the optimize role or a test clone keeps ops
    that reference pruned gradient vars."""
    prev = getattr(program, "_current_op_role", None)
    program._current_op_role = role
    try:
        yield
    finally:
        program._current_op_role = prev


def _reset_default_programs():
    """Test helper: fresh default programs + name generator."""
    global _main_program_, _startup_program_
    _main_program_ = Program()
    _startup_program_ = Program()
    unique_name.switch()
    return _main_program_, _startup_program_


# ---------------------------------------------------------------------------
# Tracing-program context. Structured control-flow ops (ops/
# control_flow_ops.py) hold only a sub-block *index* in their attrs —
# attrs must stay deep-copyable metadata — and resolve it through this
# guard, which the Executor (and _infer_shapes) set around tracing.
# ---------------------------------------------------------------------------

_tracing_program: Optional["Program"] = None


@contextlib.contextmanager
def _trace_program_guard(program):
    global _tracing_program
    prev, _tracing_program = _tracing_program, program
    try:
        yield
    finally:
        _tracing_program = prev


def _current_tracing_program() -> Optional["Program"]:
    return _tracing_program


# ---------------------------------------------------------------------------
# name_scope (reference framework.py name_scope): names the layer kind
# of the ops built under it. append_op stamps it as ``op_namescope``,
# backward ops inherit their forward op's, and executor.run_block
# lowers each op under jax.named_scope("<phase>/<layer>/<op type>"),
# which is what a device trace is charged to (profiler.scope_table).
# ---------------------------------------------------------------------------

_name_scope_stack: List[str] = []


@contextlib.contextmanager
def name_scope(prefix):
    _name_scope_stack.append(prefix)
    try:
        yield
    finally:
        _name_scope_stack.pop()


def _full_name_scope() -> str:
    """``/outer/inner/`` (reference: framework.py _full_name_scope)."""
    return "/" + "/".join(_name_scope_stack) + "/"


def innermost_scope(op_namescope) -> str:
    """``/optimizer/clip/`` -> ``clip``; ``-`` where an op has none."""
    parts = [p for p in (op_namescope or "").split("/") if p]
    return parts[-1] if parts else "-"


def cpu_places(device_count=None):
    """List of CPUPlace (reference framework.py:153: CPU_NUM env, else
    one per core — here one entry per requested slot; the Executor
    targets whatever backend JAX sees either way)."""
    import multiprocessing
    import os

    from .core import CPUPlace
    if device_count is None:
        device_count = int(os.environ.get(
            "CPU_NUM", multiprocessing.cpu_count()))
    return [CPUPlace()] * device_count


def cuda_places(device_ids=None):
    """One Place per visible ACCELERATOR device (reference
    framework.py:112 — FLAGS_selected_gpus / all visible devices; the
    TPU analog enumerates jax.devices())."""
    import jax

    from .core import CUDAPlace
    if device_ids is None:
        device_ids = range(len(jax.devices()))
    return [CUDAPlace(int(i)) for i in device_ids]


def cuda_pinned_places(device_count=None):
    """Host staging places (reference framework.py:182); host memory
    is uniform here, so these mirror cpu_places."""
    from .core import CUDAPinnedPlace
    if device_count is None:
        return [CUDAPinnedPlace()]
    return [CUDAPinnedPlace()] * device_count
