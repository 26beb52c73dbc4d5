"""CompiledProgram: attach distribution strategy to a Program.

Reference: python/paddle/fluid/compiler.py:49 (CompiledProgram,
with_data_parallel:117) which constructs a core.ParallelExecutor
(parallel_executor.cc:305) — per-device scopes, NCCL ctxs, param
broadcast, SSA-graph build with inserted AllReduce op handles
(multi_devices_graph_pass.cc).

TPU-native redesign: ALL of that machinery (≈35k LoC of graph passes +
op handles + NCCL helpers in the reference) collapses into sharding
annotations over a named mesh. ``with_data_parallel`` picks a mesh and
per-variable PartitionSpecs; the executor jits the step with those
shardings and the XLA GSPMD partitioner inserts all-reduce /
all-gather / reduce-scatter collectives over ICI.

BuildStrategy parity:
  - reduce_strategy=AllReduce (build_strategy.h:57): params replicated,
    gradient psum — classic DP.
  - reduce_strategy=Reduce: parameters + optimizer state sharded over
    the dp axis (the reference shards param *updates* across devices
    then broadcasts — the ZeRO precursor); here XLA emits
    reduce-scatter(grad) + all-gather(param) automatically.
  - fusion/memory toggles (:77-101) are accepted no-ops: XLA fuses and
    plans memory itself.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec

from .core.enforce import InvalidArgumentError, enforce
from .framework import Program, Variable
from .parallel import mesh as mesh_lib


class BuildStrategy:
    """Reference: framework/details/build_strategy.h:36."""

    class ReduceStrategy:
        AllReduce = 0
        Reduce = 1

    class GradientScaleStrategy:
        CoeffNumDevice = 0
        One = 1
        Customized = 2

    def __init__(self):
        self.reduce_strategy = BuildStrategy.ReduceStrategy.AllReduce
        self.gradient_scale_strategy = \
            BuildStrategy.GradientScaleStrategy.CoeffNumDevice
        # Gradient-sync transport over the dp axis (parallel/
        # collectives.py): None = implicit GSPMD all-reduce (the
        # compiler inserts it); "exact" = explicit psum via shard_map;
        # "rs_ag" = reduce-scatter + all-gather (arXiv:2004.13336,
        # bit-identical to exact); "q8" = block-quantized int8
        # all-reduce with per-parameter error feedback
        # (arXiv:2506.17615 analog); "sharded_update" /
        # "sharded_update_q8" = ZeRO-sharded weight update — gradients
        # are reduce-scattered (fp32 bit-exact, or int8+EF), the
        # optimizer runs on the 1/n shard over 1/n-sharded accumulator
        # slots, and the fresh PARAMS are all-gathered. See
        # docs/gradient_sync.md.
        self.gradient_sync = None
        # Param all-gather leg of the sharded_update modes: "fp32"
        # (bit-exact) or "q8" (int8 blocks + f32 scales on the wire,
        # with a param-side error-feedback residual and full-precision
        # master shards). Ignored by the non-sharded modes.
        self.param_gather = "fp32"
        # Pipeline (pp) stages inside the one traced step: an
        # engine.pipeline.PipelinePlan (n_stages, n_micro, schedule
        # "gpipe"/"1f1b") or None. The plan binds against the block at
        # step-assembly time; when the mesh carries a "pp" axis the
        # stage shifts route over it as ppermute hops. Composes with
        # every gradient_sync mode, the guard, and chunk scans — see
        # docs/step_engine.md.
        self.pipeline = None
        # fuse_elewise_add_act_ops runs the real ir pass (ir/passes.py);
        # the remaining toggles are accepted for parity — the XLA
        # compiler performs those fusions itself.
        self.fuse_elewise_add_act_ops = False
        self.fuse_all_reduce_ops = False
        self.fuse_all_optimizer_ops = False
        self.fuse_broadcast_ops = False
        self.memory_optimize = True
        self.enable_inplace = True
        self.enable_sequential_execution = False
        self.cache_runtime_context = True
        self.remove_unnecessary_lock = True
        self.sync_batch_norm = False
        self.num_trainers = 1
        self.trainer_id = 0


class ExecutionStrategy:
    """Reference: framework/details/execution_strategy.h. Thread-pool
    knobs have no meaning for a single fused XLA program; kept for API
    parity."""

    def __init__(self):
        self.num_threads = 0
        self.num_iteration_per_drop_scope = 1
        self.allow_op_delay = False
        self.use_experimental_executor = False


class CompiledProgram:
    """Reference: compiler.py:49."""

    _is_compiled = True

    def __init__(self, program, build_strategy=None):
        enforce(isinstance(program, Program),
                "CompiledProgram wraps a Program")
        self.program = program
        self._build_strategy = build_strategy or BuildStrategy()
        self._exec_strategy = None
        self._mesh = None
        self._loss_name = None
        self._share_vars_from = None

    # -- strategies --------------------------------------------------------
    def with_data_parallel(self, loss_name=None, build_strategy=None,
                           exec_strategy=None, share_vars_from=None,
                           places=None, mesh=None, axes=None):
        """Distribute over a device mesh. Default: pure DP over all
        visible devices. ``axes`` may request a multi-axis mesh, e.g.
        {"dp": 4, "tp": 2} — vars carrying .sharding PartitionSpecs
        (see parallel.api) then shard over those axes too."""
        self._loss_name = loss_name
        if build_strategy is not None:
            self._build_strategy = build_strategy
        self._exec_strategy = exec_strategy
        self._share_vars_from = share_vars_from
        if mesh is not None:
            self._mesh = mesh
        elif axes:
            self._mesh = mesh_lib.make_mesh(axes)
        elif places:
            # Respect WHICH devices the caller picked (a Place carries a
            # device_id), not just how many. Explicit places outrank the
            # ambient mesh_guard.
            devs = jax.devices()
            picked = [devs[getattr(p, "device_id", i)]
                      for i, p in enumerate(places)]
            self._mesh = mesh_lib.make_mesh({"dp": len(picked)}, picked)
        elif mesh_lib.current_mesh() is not None:
            self._mesh = mesh_lib.current_mesh()
        else:
            self._mesh = mesh_lib.data_parallel_mesh(jax.device_count())
        return self

    def with_inference_optimize(self, config=None):
        # Inference graph rewrites are XLA's job; parity no-op.
        return self

    # -- sharding assignment -----------------------------------------------
    def _mesh_spec(self, spec: PartitionSpec) -> PartitionSpec:
        """A var's declared PartitionSpec restricted to THIS mesh's
        axes: entries naming absent axes bind to None (replicated).
        Model libraries annotate for the largest mesh they support
        (moe_ffn's ep-sharded experts, shard_tp's tp weights); a
        smaller mesh must run the same program, just less sharded."""
        names = set(self._mesh.axis_names)

        def keep(e):
            if e is None:
                return None
            if isinstance(e, (tuple, list)):
                kept = tuple(a for a in e if a in names)
                return kept if kept else None
            return e if e in names else None

        return PartitionSpec(*(keep(e) for e in spec))

    def _var_spec(self, var: Variable) -> PartitionSpec:
        """PartitionSpec for a persistable var under the strategy."""
        if var.sharding is not None:
            return self._mesh_spec(var.sharding)
        if self._build_strategy.reduce_strategy == \
                BuildStrategy.ReduceStrategy.Reduce and var.persistable:
            # ZeRO-style: shard over dp on the first divisible dim.
            dp = self._mesh.shape.get("dp", 1)
            if dp > 1:
                dim = mesh_lib.first_divisible_dim(var.shape, dp)
                if dim is not None:
                    spec = [None] * len(var.shape)
                    spec[dim] = "dp"
                    return PartitionSpec(*spec)
        return PartitionSpec()

    def persist_sharding(self, var: Variable) -> NamedSharding:
        return NamedSharding(self._mesh, self._var_spec(var))

    def feed_sharding(self, shape, name=None) -> NamedSharding:
        """Batch-shard a feed over dp when its leading dim divides
        evenly; otherwise replicate (partial final batches, scalar
        feeds like learning rates). Under an sp axis the SEQUENCE dim
        (dim 1 of a [batch, seq, ...] feed) additionally shards over
        sp when divisible — activations then enter the step already
        sequence-sharded, and the zigzag/Ulysses schedules' shard_map
        in_specs meet data laid out where they want it instead of
        forcing a gather-then-scatter (the resharding-collective
        posture of arXiv:2112.01075). A feed var annotated via
        parallel.shard uses its own spec. The pp axis never shards
        feeds: microbatching happens INSIDE the step trace (the
        schedule reshapes the batch), and what the pp axis carries is
        the stacked stage-parameter/activation axis, not data."""
        if name is not None:
            var = self.program.global_block().vars.get(name)
            if var is not None and var.sharding is not None:
                return NamedSharding(self._mesh,
                                     self._mesh_spec(var.sharding))
        spec = [None] * len(shape)
        dp = self._mesh.shape.get("dp", 1)
        if dp > 1 and len(shape) > 0 and shape[0] % dp == 0:
            spec[0] = "dp"
        # the sp gate is independent of dp: an sp-only serving mesh
        # (enable_mesh({"sp": n})) or a partial final batch must still
        # sequence-shard a divisible seq dim
        sp = self._mesh.shape.get("sp", 1)
        if sp > 1 and len(shape) > 1 and shape[1] % sp == 0:
            spec[1] = "sp"
        return NamedSharding(self._mesh, PartitionSpec(*spec))

    def _fingerprint(self):
        """Stable identity for the executor's jit cache (NOT id(): a
        GC'd CompiledProgram's address can be reused, and strategies
        mutate in place)."""
        mesh = self._mesh
        # Only persistable vars can reach persist_sharding, so the scan
        # stays O(#params), not O(#vars), on the per-step hot path.
        var_specs = tuple(sorted(
            (n, str(v.sharding)) for n, v in
            self.program.global_block().vars.items()
            if v.persistable and v.sharding is not None))
        pplan = getattr(self._build_strategy, "pipeline", None)
        return (tuple(d.id for d in mesh.devices.flat),
                mesh.axis_names, tuple(mesh.shape.values()),
                self._build_strategy.reduce_strategy,
                self._build_strategy.gradient_sync,
                getattr(self._build_strategy, "param_gather", "fp32"),
                pplan.signature() if pplan is not None else None,
                var_specs)

    def grad_sync_plan(self, block):
        """Explicit-collective rewrite plan for the executor (None when
        gradient_sync is unset or the block has no optimizer)."""
        gs = self._build_strategy.gradient_sync
        if not gs:
            return None
        from .parallel import collectives
        return collectives.make_plan(
            block, gs, self._mesh,
            param_gather=getattr(self._build_strategy, "param_gather",
                                 "fp32"))

    # -- execution ---------------------------------------------------------
    def _prepare_run(self, scope=None):
        """State prep shared by EVERY dispatch path — per-step run()
        and the executor's pipelined chunk scan: fuse pass,
        gradient-sync validation, sharded/residual state conversion,
        and the one-shot rewrite-verify memo. Must run BEFORE a caller
        snapshots the persistable carry (ensure_sharded_state rewrites
        block shapes and scope values). Idempotent per version."""
        from .core.scope import global_scope
        if self._build_strategy.fuse_elewise_add_act_ops and \
                not getattr(self, "_fuse_done", False):
            from . import ir
            ir.apply_passes(self.program, ["fuse_elewise_add_act_pass"])
            self._fuse_done = True
        gs = self._build_strategy.gradient_sync
        if gs:
            from .parallel import collectives
            enforce(gs in collectives.GRAD_SYNC_MODES,
                    "BuildStrategy.gradient_sync must be one of %s, "
                    "got %r", collectives.GRAD_SYNC_MODES, gs)
            if gs in collectives.SHARDED_MODES:
                enforce(self._build_strategy.reduce_strategy ==
                        BuildStrategy.ReduceStrategy.AllReduce,
                        "gradient_sync=%r IS the explicit ZeRO "
                        "sharding; combine it with "
                        "reduce_strategy=AllReduce (Reduce would "
                        "shard the parameters a second time)", gs)
                # accumulator slots become 1/n shards (block shapes +
                # scope values) BEFORE the executor snapshots the
                # persistable carry; q8 param gather also needs master
                # shards and param-side residuals
                collectives.ensure_sharded_state(
                    self.program, scope or global_scope(), self._mesh,
                    param_gather=self._build_strategy.param_gather)
                if gs == "sharded_update_q8":
                    collectives.ensure_residual_vars(
                        self.program, scope or global_scope())
            elif gs == "q8":
                # error-feedback residual slots must exist (block var +
                # scope zeros) BEFORE the executor snapshots the
                # persistable carry for this step
                collectives.ensure_residual_vars(
                    self.program, scope or global_scope())
            if getattr(self, "_verified_version", None) != \
                    self.program._version:
                # debug/verify mode (FLAGS_verify_rewrites): statically
                # verify the composed program once per version, right
                # after the sharded-state/residual conversions rewrote
                # its declarations. The memo is only booked when a
                # verify actually RAN (maybe_verify returns None when
                # the flag is off), so flipping the flag on mid-run
                # still verifies the current version.
                from .analysis import maybe_verify_rewrite
                if maybe_verify_rewrite(self.program,
                                        "compiled_program_run",
                                        gradient_sync=gs) is not None:
                    self._verified_version = self.program._version

    def run(self, exe, feed, fetch_list, scope, return_numpy,
            use_program_cache=True, validate_feed=True, donate=True,
            *, phases):
        """Called by ``Executor.run``: hands itself over as the ``dist``
        of the executor's one body (``Executor._run_impl``), which
        prepares this strategy's state (``_prepare_run``) and traces
        and dispatches under its mesh. ``phases``: the executor's
        clock of this entry-point call (executor._EntryPhases).
        ``use_program_cache`` is accepted for parity, as in
        ``Executor.run``."""
        from .core.scope import global_scope
        return exe._run_impl(self.program, feed or {}, fetch_list or [],
                             scope or global_scope(), return_numpy,
                             phases, dist=self, donate=donate,
                             validate_feed=validate_feed)
