"""Pipeline parallelism: GPipe microbatch schedule over a ``pp`` mesh
axis.

Not present in the 2019 reference (Fluid 1.4 predates its
PipelineTrainer) — a TPU-first capability completing the parallelism
matrix (dp x tp x sp x pp): layer stages are sharded over the ``pp``
axis, activations flow stage-to-stage with ``lax.ppermute`` (one ICI
hop per tick), and ``lax.scan`` drives the M + P - 1 tick schedule so
XLA sees ONE compiled loop, not unrolled Python. Autodiff works
through the whole schedule (scan/ppermute/dynamic-slice all have
transposes), so ``jax.grad`` of a pipelined loss yields exactly the
1F1B-equivalent backward without hand-written scheduling.

As of PR 19 the scheduler itself lives in ``engine.pipeline`` — the
schedule tables, the functional forward scan, the stage stacking, and
the microbatch validation are the SAME code the StepEngine traces when
a ``PipelinePlan`` rides a build strategy (gpipe AND 1F1B, forward and
backward, composed with guard/collectives/sharded-update inside the
one step trace). This module keeps the global-view ``gpipe_apply``
entry for user shard_map code: the explicit pp-mesh path (one stage
per device, ppermute transfers) plus the sequential reference
semantics when no pp axis is in scope.

The bubble fraction is (P-1)/(M+P-1) — callers pick n_micro >> pp for
efficiency; correctness holds for any M >= 1.
"""

from __future__ import annotations

import jax
from jax import lax
from jax.sharding import PartitionSpec

# the scheduler plane is shared with the engine: these are the exact
# callables PipelinePlan traces inside build_step
from ..engine.pipeline import (gpipe_apply_inner, schedule_forward,
                               stack_stage_params,
                               validate_microbatches)
from . import mesh as mesh_lib

__all__ = ["gpipe_apply", "gpipe_apply_inner", "schedule_forward",
           "stack_stage_params", "validate_microbatches"]


def gpipe_apply(stage_fn, stacked_params, x, *, mesh=None, axis="pp",
                n_micro=None):
    """Global-view entry. stacked_params: pytree whose leaves have a
    leading stage axis [P, ...] (sharded over the pp mesh axis by the
    shard_map in_specs). x [B, ...]: the global batch; it is split
    into n_micro microbatches along axis 0 (B % n_micro == 0).
    Returns stage_fn applied through all P stages, [B, ...]."""
    from jax import shard_map

    mesh = mesh or mesh_lib.current_mesh()
    n_params = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    B = x.shape[0]
    # validate BEFORE the mesh branch: the same call must behave
    # identically on one device and on a pod
    M = n_micro if n_micro is not None else n_params
    validate_microbatches(B, M)
    if mesh is None or axis not in mesh.axis_names \
            or mesh.shape[axis] == 1:
        # no pipeline axis in scope: the engine's functional scheduler
        # over the SAME microbatches the meshed path uses — so a
        # stage_fn with cross-row coupling (batch statistics) cannot
        # silently diverge between one device and a pod
        xm = x.reshape((M, B // M) + x.shape[1:])
        return schedule_forward(stage_fn, stacked_params,
                                xm).reshape((B,) + x.shape[1:])

    P = mesh.shape[axis]
    if n_params != P:
        raise ValueError(
            "stacked_params has %d stages but the %r mesh axis has "
            "%d devices — one stage per device (a [k*P] stack would "
            "silently drop stages)" % (n_params, axis, P))
    x_micro = x.reshape((M, B // M) + x.shape[1:])

    # params: leading [P] axis sharded over pp; activations replicated
    # (each shard runs the full microbatch stream)
    p_spec = jax.tree_util.tree_map(
        lambda _: PartitionSpec(axis), stacked_params)

    def body(params_shard, xm):
        params_local = jax.tree_util.tree_map(
            lambda a: a[0], params_shard)  # [1, ...] shard -> [...]
        out = gpipe_apply_inner(stage_fn, params_local, xm,
                                axis_name=axis, n_stages=P)
        # everyone returns their buffer; only the last stage's is
        # real. Rotate it to stage 0 so the out_specs slice (index 0
        # along a per-stage axis) carries the data.
        out = lax.ppermute(out, axis,
                           [(i, (i + 1) % P) for i in range(P)])
        return out[None]  # [1, M, b, ...] per stage

    f = shard_map(
        body, mesh=mesh,
        in_specs=(p_spec, PartitionSpec()),
        out_specs=PartitionSpec(axis),
        check_vma=False)
    out = f(stacked_params, x_micro)          # [P, M, b, ...]
    return out[0].reshape((B,) + x.shape[1:])
