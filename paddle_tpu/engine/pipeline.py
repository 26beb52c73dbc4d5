"""Pipeline stages inside the ONE traced step: gpipe / 1F1B microbatch
scheduling as a first-class engine axis.

A ``PipelinePlan`` partitions a CONTIGUOUS window of a block's forward
ops into ``n_stages`` structurally-identical segments, splits the batch
into ``n_micro`` microbatches, and traces the WHOLE schedule — stacked
per-stage params, stage-shift activation transfers, per-microbatch
backward, gradient accumulation — inside the same step trace that the
guard, collective, sharded-update, and chunk-scan stages splice into
(engine/step_engine.py). The optimizer tail of the block is untouched:
the schedule writes the region's output and every ``@GRAD`` entry the
sequential trace would have produced, so guard × collectives ×
sharded-update × mesh compose with pp exactly as they compose without
it.

Two schedules, one traced tick body:

  gpipe   all M forwards then all M backwards — two ``lax.scan``s of
          ``M + P - 1`` ticks each. Live activations: M microbatch
          inputs per stage (the ring must hold every in-flight
          microbatch until its backward drains).
  1f1b    the steady-state interleave: ONE fused scan of
          ``M + 2P - 1`` ticks whose body runs a forward AND a
          backward tick (each masked by its schedule table), so
          microbatch m's backward at stage s fires at tick
          ``m + 2P - 1 - s`` — the saved-input ring caps at
          ``min(M, 2P - 1)`` microbatches per stage instead of
          gpipe's M, and the measured idle-slot (bubble) fraction
          drops from ``(P-1)/(M+P-1)`` to ``(P-1)/(M+2P-1)``.

The activation shift between adjacent stages is a ``jnp.roll`` of the
stage axis; GSPMD propagates the mesh's ``pp`` sharding through the
scan and lowers the rotation to its own collective. The explicit
formulation — ``lax.ppermute`` under ``shard_map`` plus a
``with_sharding_constraint`` pinning the stage axis to ``pp`` — is kept
behind ``PADDLE_TPU_PP_EXPLICIT_SPMD=1``: on the emulated CPU mesh the
partitioner mis-lowers BOTH (pipelined outputs come back scaled by
exactly dp**2), while the unannotated roll is bit-exact against the
sequential trace. Real TPU backends may opt in to the one-ICI-hop
ppermute form.

Backward is rematerialized: only each stage's INPUT rides the ring;
the stage body is recomputed inside ``jax.vjp`` per microbatch. The
loss tail (the forward ops after the staged region) additionally runs
ONCE at full batch for exact loss/fetch values; its per-microbatch
vjp seeds the pipeline cotangents with ``1/M`` — valid because bind
validates the loss is a scalar batch-mean reduction (``mean`` /
``reduce_mean``), under which the full-batch loss is the mean of the
per-microbatch losses. Equality with the sequential trace therefore
holds up to microbatch reassociation (documented tolerances in
tests/test_step_engine.py).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.enforce import InvalidArgumentError, enforce

__all__ = ["SCHEDULES", "PipelinePlan", "infer_segments",
           "schedule_tables", "bubble_fraction",
           "peak_live_microbatches", "schedule_forward",
           "stack_stage_params", "validate_microbatches",
           "gpipe_apply_inner"]

SCHEDULES = ("gpipe", "1f1b")

# Op types a staged region/tail may not contain: cross-microbatch batch
# statistics (batch_norm) and host-side sparse rows (lookup_table) both
# break the "microbatches are independent rows" contract the schedule
# is built on; rng ops are rejected separately (the per-op key would
# differ between the full-batch trace and the per-microbatch one).
_REJECT_OP_TYPES = frozenset({"batch_norm", "lookup_table"})


# ---------------------------------------------------------------------
# schedule tables: the static (tick, stage) -> microbatch maps
# ---------------------------------------------------------------------

def _check_sched(schedule, n_micro, n_stages):
    enforce(schedule in SCHEDULES,
            "pipeline schedule must be one of %s, got %r",
            SCHEDULES, schedule)
    enforce(n_stages >= 2, "pipeline needs n_stages >= 2, got %r",
            n_stages)
    if n_micro < 1:
        raise ValueError("n_micro must be >= 1, got %r" % (n_micro,))


def schedule_tables(schedule: str, n_micro: int, n_stages: int):
    """-> (fwd_mb, bwd_mb) int32 arrays [T, P]: the microbatch index
    stage ``s`` works on at tick ``t`` (-1 = idle slot). gpipe's table
    is the fwd-only phase followed by the bwd-only phase; 1f1b fuses
    both into one steady-state table."""
    _check_sched(schedule, n_micro, n_stages)
    M, P = n_micro, n_stages
    t_idx = lambda T: np.arange(T)[:, None]          # noqa: E731
    s_idx = np.arange(P)[None, :]

    def valid(mb):
        return np.where((mb >= 0) & (mb < M), mb, -1).astype(np.int32)

    if schedule == "gpipe":
        Tf = M + P - 1
        fwd_phase = valid(t_idx(Tf) - s_idx)
        bwd_phase = valid(t_idx(Tf) - (P - 1 - s_idx))
        idle = np.full((Tf, P), -1, dtype=np.int32)
        fwd = np.concatenate([fwd_phase, idle])
        bwd = np.concatenate([idle, bwd_phase])
        return fwd, bwd
    T = M + 2 * P - 1
    fwd = valid(t_idx(T) - s_idx)
    bwd = valid(t_idx(T) - (2 * P - 1 - s_idx))
    return fwd, bwd


def bubble_fraction(schedule: str, n_micro: int, n_stages: int) -> float:
    """Fraction of (tick, stage) slots with neither a forward nor a
    backward microbatch — counted from the actual tables, not a closed
    form, so the bench reports what the trace really schedules."""
    fwd, bwd = schedule_tables(schedule, n_micro, n_stages)
    return float(np.mean((fwd < 0) & (bwd < 0)))


def peak_live_microbatches(schedule: str, n_micro: int,
                           n_stages: int) -> int:
    """Saved-activation ring depth per stage: how many microbatch
    inputs are live between their forward and backward. gpipe holds
    all M; 1f1b's steady state caps at ``2P - 1`` (stage s has
    ``2(P-s) - 1`` in flight; the uniform ring takes the max)."""
    _check_sched(schedule, n_micro, n_stages)
    if schedule == "gpipe":
        return n_micro
    return min(n_micro, 2 * n_stages - 1)


def validate_microbatches(batch: int, n_micro: int):
    """The ONE divisibility/arity validation every pipeline entry
    point shares (error strings pinned by tests/test_pipeline.py)."""
    if n_micro < 1:
        raise ValueError("n_micro must be >= 1, got %r" % (n_micro,))
    if batch % n_micro != 0:
        raise ValueError("batch %d not divisible by n_micro %d"
                         % (batch, n_micro))


def stack_stage_params(per_stage_params):
    """[{...}, {...}, ...] (one pytree per stage, equal structure) ->
    one pytree with leading [P] stage axis."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params)


# ---------------------------------------------------------------------
# the stage shift: roll by default, explicit ppermute behind an env gate
# ---------------------------------------------------------------------

def _explicit_pp_spmd() -> bool:
    # The explicit SPMD formulation (shard_map ppermute + a pp
    # sharding constraint on stage-stacked tensors) is opt-in: on the
    # emulated CPU mesh the GSPMD partitioner mis-lowers a partitioned
    # stage-axis rotation inside lax.scan — pipelined outputs come
    # back scaled by exactly dp**2 — while the unannotated jnp.roll
    # formulation partitions correctly (bit-exact vs the sequential
    # trace on a pp=2 x dp=2 mesh). TPU backends can flip this on for
    # the guaranteed single-ICI-hop transfer per tick.
    import os
    return os.environ.get("PADDLE_TPU_PP_EXPLICIT_SPMD", "") == "1"


def _stage_shift(y, direction: int, mesh):
    """Shift the stage axis (axis 0) by one: ``direction=+1`` moves
    stage s's value to stage s+1 (the forward activation hop),
    ``direction=-1`` moves it to stage s-1 (the backward cotangent
    hop). The wrap-around entry is garbage either way and is
    overwritten by the injection slot. Under the opt-in explicit-SPMD
    gate a mesh with a matching ``pp`` axis uses ONE ``lax.ppermute``
    ICI hop per tick instead of the roll."""
    P = y.shape[0]
    if _explicit_pp_spmd() and mesh is not None \
            and "pp" in mesh.axis_names \
            and mesh.shape["pp"] == P and P > 1:
        from jax import shard_map
        from jax.sharding import PartitionSpec
        perm = [(i, (i + direction) % P) for i in range(P)]
        return shard_map(
            lambda a: lax.ppermute(a, "pp", perm),
            mesh=mesh, in_specs=PartitionSpec("pp"),
            out_specs=PartitionSpec("pp"), check_vma=False)(y)
    return jnp.roll(y, direction, axis=0)


def _pp_constrain(val, mesh):
    if _explicit_pp_spmd() and mesh is not None \
            and "pp" in mesh.axis_names and mesh.shape["pp"] > 1:
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.lax.with_sharding_constraint(
            val, NamedSharding(mesh, PartitionSpec("pp")))
    return val


# ---------------------------------------------------------------------
# functional scheduler (forward-only): the parallel/pipeline.py shim
# ---------------------------------------------------------------------

def schedule_forward(stage_fn, stacked_params, x_micro, *,
                     schedule: str = "gpipe", mesh=None):
    """Run ``x_micro [M, b, ...]`` through P stages (leading axis of
    ``stacked_params``'s leaves) on the schedule's forward table in ONE
    ``lax.scan``; returns ``y_micro [M, b, ...]``. Differentiable —
    ``jax.grad`` through the scan yields the pipelined backward."""
    leaves = jax.tree_util.tree_leaves(stacked_params)
    enforce(leaves, "stacked_params must have at least one leaf")
    P = leaves[0].shape[0]
    M = x_micro.shape[0]
    fwd_tbl, _ = schedule_tables(schedule, M, max(P, 2))
    if P < 2:  # degenerate single stage: table math still wants P>=2
        fwd_tbl = np.arange(M, dtype=np.int32)[:, None]
    vf = jax.vmap(stage_fn, in_axes=(0, 0))
    y0 = jnp.zeros((P,) + x_micro.shape[1:], x_micro.dtype)
    out0 = jnp.zeros((M + 1,) + x_micro.shape[1:], x_micro.dtype)

    def tick(carry, f_row):
        y_prev, outs = carry
        x_in = _stage_shift(y_prev, 1, mesh).at[0].set(
            x_micro[jnp.clip(f_row[0], 0, M - 1)])
        y = vf(stacked_params, x_in)
        slot = jnp.where(f_row[P - 1] >= 0, f_row[P - 1], M)
        outs = outs.at[slot].set(y[P - 1])
        return (y, outs), None

    # drop all-idle ticks (gpipe's table carries the bwd-only phase)
    rows = [r for r in np.asarray(fwd_tbl) if (r >= 0).any()]
    (_, outs), _ = lax.scan(tick, (y0, out0),
                            jnp.asarray(np.stack(rows)))
    return outs[:M]


def gpipe_apply_inner(stage_fn, stage_params, x_micro, *, axis_name,
                      n_stages):
    """Per-shard GPipe body (call inside shard_map) — the engine-owned
    implementation behind ``parallel.pipeline.gpipe_apply_inner``.

    stage_fn(params, x) -> y — one stage's computation; the SAME
    callable runs on every stage with that stage's params shard. Input
    and output must have identical shape/dtype (the activation that
    travels the pipe). ``x_micro [M, ...]``: every stage receives the
    same array, only stage 0 reads it. Returns ``y_micro [M, ...]``:
    real on the LAST stage, zeros elsewhere."""
    stage = lax.axis_index(axis_name)
    M = x_micro.shape[0]
    P = n_stages
    fwd_perm = [(i, (i + 1) % P) for i in range(P)]

    carry_act = jnp.zeros_like(x_micro[0])
    out_buf = jnp.zeros_like(x_micro)

    def tick(carry, t):
        act, outs = carry
        mb = lax.dynamic_index_in_dim(x_micro, jnp.minimum(t, M - 1),
                                      keepdims=False)
        inp = jnp.where(stage == 0, mb, act)
        y = stage_fn(stage_params, inp)
        done_idx = t - (P - 1)
        outs = lax.cond(
            jnp.logical_and(stage == P - 1, done_idx >= 0),
            lambda o: lax.dynamic_update_index_in_dim(
                o, y, jnp.maximum(done_idx, 0), 0),
            lambda o: o, outs)
        act_next = lax.ppermute(y, axis_name, fwd_perm)
        return (act_next, outs), None

    (_, out_buf), _ = lax.scan(tick, (carry_act, out_buf),
                               jnp.arange(M + P - 1))
    return out_buf


# ---------------------------------------------------------------------
# PipelinePlan: the engine-axis contract
# ---------------------------------------------------------------------

class PipelinePlan:
    """Stage partition of a block's forward op window + microbatch
    count + schedule. Rides ``BuildStrategy.pipeline`` into
    ``build_step`` (and keys the executor's jit cache via
    ``signature()``). ``segments=None`` infers the stage windows from
    the block's op-type structure at bind time."""

    def __init__(self, n_stages: int, n_micro: int,
                 schedule: str = "1f1b",
                 segments: Optional[Sequence[Sequence[int]]] = None):
        _check_sched(schedule, n_micro, n_stages)
        if segments is not None:
            segments = tuple(tuple(int(i) for i in seg)
                             for seg in segments)
            enforce(len(segments) == n_stages,
                    "segments has %d entries for n_stages=%d",
                    len(segments), n_stages)
        self.n_stages = int(n_stages)
        self.n_micro = int(n_micro)
        self.schedule = schedule
        self.segments = segments

    def signature(self):
        return ("pp", self.n_stages, self.n_micro, self.schedule,
                self.segments)

    @property
    def bubble_fraction(self) -> float:
        return bubble_fraction(self.schedule, self.n_micro,
                               self.n_stages)

    @property
    def peak_live_microbatches(self) -> int:
        return peak_live_microbatches(self.schedule, self.n_micro,
                                      self.n_stages)

    def __repr__(self):
        return ("PipelinePlan(n_stages=%d, n_micro=%d, schedule=%r)"
                % (self.n_stages, self.n_micro, self.schedule))

    def bind(self, block, mesh=None):
        """Validate the plan against a block and return the
        ``_BoundPipeline`` run_block splices at the region start.
        Raises InvalidArgumentError when the block's structure cannot
        be staged (the reason names the violated contract)."""
        if mesh is not None and "pp" in mesh.axis_names:
            enforce(mesh.shape["pp"] == self.n_stages,
                    "mesh 'pp' axis has %d devices but the plan has "
                    "%d stages — one stage per pp shard",
                    mesh.shape["pp"], self.n_stages)
        segments = self.segments
        if segments is None:
            segments = infer_segments(block, self.n_stages)
        return _BoundPipeline(self, block, segments, mesh)


def _forward_len(block) -> int:
    for i, op in enumerate(block.ops):
        if op.type in ("vjp", "vjp2") \
                or op.attrs.get("op_role") in ("backward", "optimize"):
            return i
    return len(block.ops)


def _op_sig(op):
    """Structural signature of one op: type + attrs (sans roles).
    Segments must match op-for-op on this."""
    attrs = {k: repr(v) for k, v in op.attrs.items()
             if k not in ("op_role", "op_namescope")}
    return (op.type, tuple(sorted(attrs.items())))


def infer_segments(block, n_stages: int) -> List[List[int]]:
    """Find P contiguous, structurally-identical, equal-length op
    windows ending before the loss tail. Tries the LONGEST segments
    first and the LATEST start first (minimal tail), validating each
    candidate with a full bind; raises when no partition binds."""
    P = n_stages
    fwd_len = _forward_len(block)
    last_err = None
    for L in range(fwd_len // P, 0, -1):
        for start in range(fwd_len - P * L, -1, -1):
            sig0 = [_op_sig(block.ops[start + j]) for j in range(L)]
            if any(_op_sig(block.ops[start + s * L + j]) != sig0[j]
                   for s in range(1, P) for j in range(L)):
                continue
            segs = [list(range(start + s * L, start + (s + 1) * L))
                    for s in range(P)]
            try:
                _BoundPipeline(
                    PipelinePlan(P, 1, "gpipe", segments=segs),
                    block, tuple(tuple(s) for s in segs), None)
                return segs
            except InvalidArgumentError as e:
                last_err = e
                continue
    raise InvalidArgumentError(
        "no %d-stage partition of the forward ops binds: the block "
        "needs %d contiguous structurally-identical op windows before "
        "the loss tail%s"
        % (P, P, " (last candidate failed: %s)" % last_err
           if last_err is not None else ""))


class _BoundPipeline:
    """A plan validated against one block: segment name maps, external
    classification, the run_block skip set, and ``execute`` (the traced
    schedule + env injection)."""

    def __init__(self, plan: PipelinePlan, block, segments, mesh):
        self.plan = plan
        self.block = block
        self.mesh = mesh
        P = plan.n_stages
        ops_l = block.ops
        fwd_len = _forward_len(block)

        segs = [list(seg) for seg in segments]
        enforce(len(segs) == P, "plan has %d segments for n_stages=%d",
                len(segs), P)
        L = len(segs[0])
        enforce(L >= 1 and all(len(s) == L for s in segs),
                "pipeline segments must be equal-length and non-empty")
        flat = [i for s in segs for i in s]
        enforce(flat == list(range(segs[0][0], segs[0][0] + P * L)),
                "pipeline segments must be contiguous op windows")
        self.region_start = segs[0][0]
        self.region_end = segs[0][0] + P * L
        enforce(self.region_end <= fwd_len,
                "pipeline segments reach op %d but the forward pass "
                "ends at op %d", self.region_end, fwd_len)
        self.fwd_len = fwd_len

        for i in range(self.region_start, fwd_len):
            self._check_stageable_op(ops_l[i], i)

        # grad suffix of the (single) backward pass over this block
        suffixes = {op.attrs.get("grad_suffix", "")
                    for op in ops_l if op.type in ("vjp", "vjp2")}
        enforce(len(suffixes) <= 1,
                "pipeline cannot stage a block with multiple backward "
                "passes (grad suffixes %s)", sorted(suffixes))
        self.grad_suffix = next(iter(suffixes), "")
        self.has_backward = bool(suffixes)

        for i, op in enumerate(ops_l):
            if op.type == "vjp2" and \
                    op.attrs.get("fwd_op_index", -1) >= self.region_start:
                raise InvalidArgumentError(
                    "double backward (vjp2, op #%d) through the "
                    "pipelined region is not supported" % i)

        self._map_segments(block, segs)
        self._classify_tail(block)

        skip = set(range(self.region_start, self.region_end))
        for i, op in enumerate(ops_l):
            if op.type in ("vjp", "vjp2") and \
                    self.region_start <= op.attrs.get("fwd_op_index",
                                                      -1) < fwd_len:
                skip.add(i)
        self.skip = frozenset(skip)

    # -- bind-time validation helpers ---------------------------------

    def _check_stageable_op(self, op, i):
        from .. import ops as ops_mod
        from ..ops.control_flow_ops import ARRAY_OP_TYPES
        if op.type in ("vjp", "vjp2") or not ops_mod.has(op.type):
            raise InvalidArgumentError(
                "pipeline region/tail op #%d (%r) has no plain "
                "lowering to stage" % (i, op.type))
        if op.type in _REJECT_OP_TYPES or op.type in ARRAY_OP_TYPES:
            raise InvalidArgumentError(
                "op type %r (op #%d) cannot be pipelined: it couples "
                "rows across the batch or requires eager execution"
                % (op.type, i))
        if ops_mod.get(op.type).needs_rng \
                and not op.attrs.get("is_test"):
            # is_test=True makes the rng key inert (dropout rate is
            # forced to 0 in the lowering), so inference-mode ops are
            # replay-safe even though the registry marks them rng
            raise InvalidArgumentError(
                "op type %r (op #%d) needs per-op rng: the "
                "per-microbatch replay would draw different keys than "
                "the sequential trace" % (op.type, i))

    def _map_segments(self, block, segs):
        """Build sigma[s]: segment-0 name -> segment-s name, classify
        region externals (stacked per-stage params vs shared consts),
        and pin the single homogeneous boundary variable."""
        P = self.plan.n_stages
        ops_l = block.ops
        seg_sets = [set(s) for s in segs]

        def producer(name):
            for i in range(self.fwd_len - 1, -1, -1):
                if name in ops_l[i].output_arg_names:
                    return i
            return None

        # positional name isomorphism: walking the segments op-by-op,
        # every (input+output) name of segment s must map 1:1 from the
        # name at the same position in segment 0
        sigmas: List[Dict[str, str]] = [{} for _ in range(P)]
        produced0 = set()
        ext_order: List[str] = []
        for j in range(len(segs[0])):
            o0 = ops_l[segs[0][j]]
            for s in range(P):
                os_ = ops_l[segs[s][j]]
                in0, ins = o0.input_arg_names, os_.input_arg_names
                out0, outs = o0.output_arg_names, os_.output_arg_names
                enforce(len(in0) == len(ins) and len(out0) == len(outs),
                        "segment op arity mismatch at op #%d vs #%d",
                        segs[0][j], segs[s][j])
                for n0, ns in zip(list(in0) + list(out0),
                                  list(ins) + list(outs)):
                    if n0 in sigmas[s]:
                        enforce(sigmas[s][n0] == ns,
                                "segment %d is not isomorphic to "
                                "segment 0: %r maps to both %r and %r",
                                s, n0, sigmas[s][n0], ns)
                    else:
                        sigmas[s][n0] = ns
            for n0 in o0.input_arg_names:
                if n0 not in produced0 and n0 not in ext_order:
                    ext_order.append(n0)
            produced0.update(o0.output_arg_names)

        # the boundary: for s >= 1 exactly ONE external image is
        # produced by the previous segment; its aligned position is
        # the stage input (identical across segments, or the stage
        # function cannot be one template)
        in_pos = None
        for s in range(1, P):
            bpos = [k for k, n0 in enumerate(ext_order)
                    if producer(sigmas[s][n0]) in seg_sets[s - 1]]
            enforce(len(bpos) == 1,
                    "exactly one activation must cross the stage %d->"
                    "%d boundary, found %d", s - 1, s, len(bpos))
            enforce(in_pos is None or in_pos == bpos[0],
                    "stage boundary variable position drifts across "
                    "segments")
            in_pos = bpos[0]
        self.in_name = ext_order[in_pos]
        p_in = producer(self.in_name)
        enforce(p_in is None or p_in < self.region_start,
                "segment 0's input %r must come from before the "
                "region", self.in_name)

        # segment 0's boundary-out (TEMPLATE name) is segment 1's
        # image of the stage input; the region output is the last
        # segment's image of that template name
        self.out_template = sigmas[1][self.in_name]
        enforce(self.out_template in produced0,
                "internal error: template boundary-out %r not "
                "produced by segment 0", self.out_template)
        self.out_name = sigmas[P - 1][self.out_template]

        # no cross-stage skip connections: a var produced in stage s
        # and consumed after that segment must be exactly the boundary
        # activation, consumed exactly by stage s+1 (or, for the last
        # stage, by the tail)
        for s in range(P):
            bvar = sigmas[s][self.out_template]
            for i in range(segs[s][-1] + 1, self.fwd_len):
                for n in ops_l[i].input_arg_names:
                    if producer(n) not in seg_sets[s]:
                        continue
                    if s + 1 < P:
                        enforce(n == bvar and i in seg_sets[s + 1],
                                "var %r produced in stage %d is "
                                "consumed at op #%d — only the "
                                "boundary activation may leave a "
                                "stage", n, s, i)
                    else:
                        enforce(n == bvar and i >= self.region_end,
                                "var %r produced in the last stage is "
                                "consumed at op #%d — only the region "
                                "output may feed the tail", n, i)

        # boundary homogeneity: the activation that travels the pipe
        # keeps one shape/dtype through every stage
        v_in = block.vars[self.in_name]
        v_out = block.vars[self.out_name]
        enforce(tuple(v_in.shape) == tuple(v_out.shape)
                and v_in.dtype == v_out.dtype,
                "stage input %r %s/%s and output %r %s/%s must have "
                "identical shape and dtype (the activation that "
                "travels the pipe)", self.in_name, v_in.shape,
                v_in.dtype, self.out_name, v_out.shape, v_out.dtype)

        # externals (minus the boundary): shared vs stacked
        self.stacked_names: List[str] = []
        self.shared_names: List[str] = []
        gname = lambda n: n + "@GRAD" + self.grad_suffix  # noqa: E731
        for k, n0 in enumerate(ext_order):
            if k == in_pos:
                continue
            names = [sigmas[s][n0] for s in range(P)]
            for n in names:
                p = producer(n)
                enforce(p is None or p < self.region_start,
                        "stage external %r is produced inside the "
                        "region (op #%s) — cross-stage skip "
                        "connections cannot be pipelined", n, p)
            if all(n == n0 for n in names):
                if self.has_backward and block.has_var(gname(n0)):
                    raise InvalidArgumentError(
                        "external %r is shared by every stage AND "
                        "receives gradients — the schedule cannot "
                        "accumulate a shared-stage grad; give each "
                        "stage its own parameter" % n0)
                self.shared_names.append(n0)
                continue
            shapes = {tuple(block.vars[n].shape) for n in names}
            dtypes = {block.vars[n].dtype for n in names}
            enforce(len(shapes) == 1 and len(dtypes) == 1,
                    "per-stage external %r cannot stack: shapes %s / "
                    "dtypes %s differ across stages", n0,
                    sorted(shapes), sorted(dtypes))
            enforce(all(block.vars[n].persistable for n in names),
                    "per-stage external %r must be persistable "
                    "parameters to stack across stages", n0)
            self.stacked_names.append(n0)

        self.sigmas = sigmas
        self.template = [(i, ops_l[i]) for i in segs[0]]
        self.segs = segs

    def _classify_tail(self, block):
        """Tail = forward ops after the region (the loss head). Runs
        full-batch in the normal trace for exact fetch values AND
        per-microbatch inside the schedule to seed cotangents."""
        ops_l = block.ops
        self.tail = [(i, ops_l[i])
                     for i in range(self.region_end, self.fwd_len)]
        gname = lambda n: n + "@GRAD" + self.grad_suffix  # noqa: E731

        produced = {self.out_name}
        self.tail_param_names: List[str] = []
        self.tail_batch_names: List[str] = []
        self.tail_shared_names: List[str] = []
        for i, op in self.tail:
            for n in op.input_arg_names:
                if n in produced or n in self.tail_param_names \
                        or n in self.tail_batch_names \
                        or n in self.tail_shared_names:
                    continue
                var = block.vars.get(n)
                enforce(var is not None,
                        "tail op #%d consumes unknown var %r", i, n)
                if var.persistable:
                    self.tail_param_names.append(n)
                elif var.is_data:
                    self.tail_batch_names.append(n)
                else:
                    prod = None
                    for j in range(self.region_start, self.region_end):
                        if n in ops_l[j].output_arg_names:
                            prod = j
                            break
                    if prod is not None:
                        raise InvalidArgumentError(
                            "tail op #%d consumes %r produced inside "
                            "the pipelined region (op #%d) — only the "
                            "final stage activation may feed the loss "
                            "tail" % (i, n, prod))
                    if self.has_backward and block.has_var(gname(n)):
                        raise InvalidArgumentError(
                            "tail input %r needs gradients but is "
                            "neither the stage output nor a "
                            "persistable parameter — a skip "
                            "connection around the pipeline region "
                            "cannot be staged" % n)
                    self.tail_shared_names.append(n)
            produced.update(op.output_arg_names)

        if not self.has_backward:
            self.loss_name = None
            return
        enforce(self.tail,
                "a pipelined training block needs a loss tail after "
                "the staged region (the backward seed op must follow "
                "at least one tail op)")
        loss_i, loss_op = self.tail[-1]
        # derive the loss var from the backward seed when present
        loss_name = loss_op.output_arg_names[0]
        suffix = "@GRAD" + self.grad_suffix
        if self.fwd_len < len(ops_l):
            seed = ops_l[self.fwd_len]
            if seed.type == "fill_constant" and seed.output_arg_names:
                cand = seed.output_arg_names[0]
                if cand.endswith(suffix):
                    loss_name = cand[:-len(suffix)]
        prod = None
        for i, op in self.tail:
            if loss_name in op.output_arg_names:
                prod = op
        enforce(prod is not None,
                "loss var %r is not produced by the pipeline tail",
                loss_name)
        enforce(prod.type in ("mean", "reduce_mean"),
                "the pipelined loss must be a batch-mean reduction "
                "(mean/reduce_mean) so per-microbatch losses combine "
                "as loss = (1/M) * sum(loss_m); got %r", prod.type)
        lv = block.vars[loss_name]
        numel = 1
        for d in lv.shape:
            numel *= max(int(d), 1)
        enforce(numel == 1,
                "the pipelined loss %r must be a scalar, got shape %s",
                loss_name, lv.shape)
        self.loss_name = loss_name

    # -- the traced schedule ------------------------------------------

    def execute(self, env: Dict, step_key, library=None):
        """Trace the full microbatch schedule into ``env``: writes the
        region output, the region-input grad, every per-stage param
        grad, and every tail param grad — exactly the entries the
        skipped sequential ops would have produced."""
        from ..executor import run_op

        plan, mesh = self.plan, self.mesh
        P, M = plan.n_stages, plan.n_micro
        x_full = env[self.in_name]
        B = int(x_full.shape[0])
        if B % M != 0:
            raise InvalidArgumentError(
                "pipeline: batch %d not divisible by n_micro %d"
                % (B, M))
        b = B // M
        feat = tuple(x_full.shape[1:])
        # feeds arrive as host numpy — promote before tracer indexing
        x_micro = jnp.asarray(x_full).reshape((M, b) + feat)

        stacked = [
            _pp_constrain(jnp.stack([env[self.sigmas[s][n0]]
                                     for s in range(P)]), mesh)
            for n0 in self.stacked_names]
        shared_vals = {n: env[n] for n in self.shared_names}

        def stage_fn(leaves, x):
            local = dict(shared_vals)
            local.update(zip(self.stacked_names, leaves))
            local[self.in_name] = x
            for gi, op in self.template:
                run_op(op, local, step_key, gi, library=library)
            return local[self.out_template]

        vf = jax.vmap(stage_fn, in_axes=(0, 0))
        fwd_tbl, bwd_tbl = schedule_tables(plan.schedule, M, P)
        S = peak_live_microbatches(plan.schedule, M, P)
        zP = jnp.zeros((P, b) + feat, x_full.dtype)
        saved0 = jnp.zeros((P, S + 1, b) + feat, x_full.dtype)
        buf0 = jnp.zeros((M + 1, b) + feat, x_full.dtype)
        arangeP = jnp.arange(P)

        def fwd_tick(carry, f_row):
            y_prev, saved, out_buf = carry
            x_in = _stage_shift(y_prev, 1, mesh).at[0].set(
                x_micro[jnp.clip(f_row[0], 0, M - 1)])
            y = _pp_constrain(vf(stacked, x_in), mesh)
            slots = jnp.where(f_row >= 0, f_row % S, S)
            saved = saved.at[arangeP, slots].set(x_in)
            ob = jnp.where(f_row[P - 1] >= 0, f_row[P - 1], M)
            out_buf = out_buf.at[ob].set(y[P - 1])
            return (y, saved, out_buf), None

        if not self.has_backward:
            (_, _, out_buf), _ = lax.scan(
                fwd_tick, (zP, saved0, buf0),
                jnp.asarray(fwd_tbl[np.any(fwd_tbl >= 0, axis=1)]))
            env[self.out_name] = out_buf[:M].reshape((B,) + feat)
            return

        tail_params = [env[n] for n in self.tail_param_names]
        tail_shared = {n: env[n] for n in self.tail_shared_names}
        bexts_micro = []
        for n in self.tail_batch_names:
            v = env[n]
            if int(v.shape[0]) != B:
                raise InvalidArgumentError(
                    "pipeline tail data var %r has leading dim %d; "
                    "expected the batch %d" % (n, v.shape[0], B))
            bexts_micro.append(
                jnp.asarray(v).reshape((M, b) + tuple(v.shape[1:])))

        def tail_fn(tparams, x, bexts):
            local = dict(tail_shared)
            local.update(zip(self.tail_param_names, tparams))
            local.update(zip(self.tail_batch_names, bexts))
            local[self.out_name] = x
            for gi, op in self.tail:
                run_op(op, local, step_key, gi, library=library)
            return local[self.loss_name]

        def stage_bwd(leaves, x, g):
            _, pull = jax.vjp(stage_fn, leaves, x)
            dl, dx = pull(g)
            return dx, dl

        vb = jax.vmap(stage_bwd, in_axes=(0, 0, 0))
        gacc0 = [jnp.zeros_like(a) for a in stacked]
        tg0 = [jnp.zeros_like(v) for v in tail_params]

        def bwd_half(saved, out_buf, dx_prev, gacc, tgacc, dxout,
                     b_row):
            """One backward tick (shared by the gpipe bwd phase and
            the fused 1f1b body). Reads the ring/out_buf BEFORE the
            caller's forward writes of the same tick."""
            bslots = jnp.where(b_row >= 0, b_row % S, S)
            x_saved = saved[arangeP, bslots]
            bl = b_row[P - 1]
            x_t = out_buf[jnp.clip(bl, 0, M - 1)]
            bx = [bm[jnp.clip(bl, 0, M - 1)] for bm in bexts_micro]
            loss_mb, pull = jax.vjp(
                lambda tp, xx: tail_fn(tp, xx, bx), tail_params, x_t)
            dtp, gseed = pull(jnp.full_like(loss_mb, 1.0 / M))
            live_t = bl >= 0
            tgacc = [a + jnp.where(live_t, d, jnp.zeros_like(d))
                     for a, d in zip(tgacc, dtp)]
            g_in = _stage_shift(dx_prev, -1, mesh).at[P - 1].set(gseed)
            dx, dl = vb(stacked, x_saved, g_in)
            live = b_row >= 0
            gacc = [a + jnp.where(
                live.reshape((P,) + (1,) * (d.ndim - 1)), d,
                jnp.zeros_like(d)) for a, d in zip(gacc, dl)]
            sl0 = jnp.where(b_row[0] >= 0, b_row[0], M)
            dxout = dxout.at[sl0].set(dx[0])
            return dx, gacc, tgacc, dxout

        if plan.schedule == "gpipe":
            fwd_rows = jnp.asarray(
                fwd_tbl[np.any(fwd_tbl >= 0, axis=1)])
            bwd_rows = jnp.asarray(
                bwd_tbl[np.any(bwd_tbl >= 0, axis=1)])
            (_, saved, out_buf), _ = lax.scan(
                fwd_tick, (zP, saved0, buf0), fwd_rows)

            def bwd_tick(carry, b_row):
                dx_prev, gacc, tgacc, dxout = carry
                return bwd_half(saved, out_buf, dx_prev, gacc, tgacc,
                                dxout, b_row), None

            (_, gacc, tgacc, dxout), _ = lax.scan(
                bwd_tick, (zP, gacc0, tg0, buf0), bwd_rows)
        else:
            def fused_tick(carry, rows):
                y_prev, dx_prev, saved, out_buf, gacc, tgacc, dxout \
                    = carry
                f_row, b_row = rows
                # backward FIRST: at S = 2P-1 the stage-0 ring slot a
                # backward reads is rewritten by the SAME tick's
                # forward
                dx, gacc, tgacc, dxout = bwd_half(
                    saved, out_buf, dx_prev, gacc, tgacc, dxout,
                    b_row)
                (y, saved, out_buf), _ = fwd_tick(
                    (y_prev, saved, out_buf), f_row)
                return (y, dx, saved, out_buf, gacc, tgacc,
                        dxout), None

            (_, _, _, out_buf, gacc, tgacc, dxout), _ = lax.scan(
                fused_tick, (zP, zP, saved0, buf0, gacc0, tg0, buf0),
                (jnp.asarray(fwd_tbl), jnp.asarray(bwd_tbl)))

        env[self.out_name] = out_buf[:M].reshape((B,) + feat)
        gname = lambda n: n + "@GRAD" + self.grad_suffix  # noqa: E731
        if self.block.has_var(gname(self.in_name)):
            env[gname(self.in_name)] = \
                dxout[:M].reshape((B,) + feat)
        for n0, g in zip(self.stacked_names, gacc):
            for s in range(P):
                ns = self.sigmas[s][n0]
                if self.block.has_var(gname(ns)):
                    env[gname(ns)] = g[s]
        for n, g in zip(self.tail_param_names, tgacc):
            if self.block.has_var(gname(n)):
                env[gname(n)] = g
