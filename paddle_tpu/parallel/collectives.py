"""Explicit gradient-collective layer.

Until this module, every data-parallel gradient sync was an IMPLICIT
GSPMD all-reduce: the partitioner inserted a full-precision collective
wherever a batch-sharded gradient met a replicated parameter, and the
one part of the step dominating interconnect time could be neither
selected nor measured. This layer makes the sync first-class — three
selectable transports over the ``dp`` mesh axis, applied by the
executor as a rewrite of ``@GRAD`` values between the backward and
optimizer ops of the SAME traced step (XLA still fuses around them):

  - ``all_reduce_exact``       psum via shard_map — the explicit twin of
                               what GSPMD inserts implicitly.
  - ``reduce_scatter_gather``  the reduce-scatter + all-gather
                               decomposition of "Automatic Cross-Replica
                               Sharding of Weight Update"
                               (arXiv:2004.13336) — composes with the
                               ZeRO-style ``reduce_strategy=Reduce``
                               sharding ``compiler.py`` assigns, and is
                               bit-identical to the psum because both
                               reduce the same per-device partials in
                               rank order.
  - ``all_reduce_q8``          block-scaled int8 quantize →
                               reduce-scatter (all_to_all of int8 blocks
                               + f32 scales) → dequant/accumulate in
                               fp32 → requantize → all-gather, the
                               in-XLA quantized AllReduce of EQuARX
                               (arXiv:2506.17615), with a PERSISTENT
                               per-parameter error-feedback residual
                               (same lifecycle as the dgc U/V slots in
                               ``ops/optimizer_ops.py``) so compression
                               error is carried into the next step
                               instead of lost.

On top of the pointwise transports sit the ``sharded_update`` modes
(``ShardedUpdatePlan``): reduce-scatter the gradients and DON'T gather
them back — run the whole optimize section on 1/n flat shards over
1/n-sharded accumulator slots, then all-gather the fresh parameters
(optionally int8, with a second residual family and full-precision
master shards). See docs/gradient_sync.md §"Sharded weight update".

Formulation note: at trace level a gradient is one global value ``g``
(the full-batch gradient). The transports re-express the reduction over
per-device partials ``p_d = g/n`` — mathematically the identity for the
exact modes, but the collectives are REAL (psum / psum_scatter /
all_to_all / all_gather in the lowered HLO), so wire bytes, reduction
order, and quantization error are all faithfully modeled and
measurable. Known composition limit: on a real multi-device lowering
the partitioner may first materialize ``g`` replicated (its own
reduction) to satisfy shard_map's replicated in_specs, so the
END-TO-END wire bytes of a training step can exceed what the explicit
transport itself moves; the estimator below prices the transport
algorithms (what an HLO-native EQuARX-style pass moves), and the bench
rows report measured steps/s so the composition cost stays visible.
Consuming the pre-reduction partials (backward under shard_map) is the
follow-up that closes this gap. Error feedback follows the EF-SGD telescope: each device
compensates its contribution ``c = p + r`` before quantizing and carries
``r' = c - y/n`` forward, so ``sum_t y_t = sum_t g_t + n(r_0 - r_T)``
— the applied updates drift from the exact ones by a bounded amount
regardless of horizon.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec

from ..core.enforce import (InvalidArgumentError, UnimplementedError,
                            enforce)

# ZeRO-style sharded weight update (arXiv:2004.13336 proper): instead
# of all-gathering the reduced GRADIENT back to full size (rs_ag) so
# every replica applies the complete update over complete optimizer
# state, the ``sharded_update`` modes stop after the reduce-scatter,
# run regularizer/clip/optimizer ops on the 1/n gradient shard over
# 1/n-sharded accumulator slots, and all-gather the fresh PARAMETERS.
# ``sharded_update_q8`` rides the scatter leg on int8 blocks with the
# same per-param error-feedback residuals q8 uses; the gather leg can
# independently quantize (BuildStrategy.param_gather="q8", the EQuARX
# both-directions recipe, arXiv:2506.17615) with a SECOND persistable
# residual family on the param side plus a full-precision master shard
# so quantization error never compounds into the master weights.
SHARDED_MODES = ("sharded_update", "sharded_update_q8")
GRAD_SYNC_MODES = ("exact", "rs_ag", "q8") + SHARDED_MODES
PARAM_GATHER_MODES = ("fp32", "q8")

# EQuARX-style block scaling: one f32 scale per 256 int8 elements keeps
# the scale overhead at 4/256 = 1.6% of payload.
DEFAULT_BLOCK_SIZE = 256

# Persistable error-feedback slot per parameter (created by
# ensure_residual_vars, threaded through the executor's persistable
# carry exactly like optimizer accumulators).
RESIDUAL_SUFFIX = ".q8_ef_residual"

# Sharded-update state families (ensure_sharded_state): the param-side
# error-feedback residual of the quantized all-gather, and the
# full-precision master shard the update applies to when the gathered
# params are quantized approximations.
PARAM_RESIDUAL_SUFFIX = ".q8_pg_residual"
MASTER_SHARD_SUFFIX = ".zero_master_shard"

# Input slots whose vars must stay replicated scalars even when their
# shape happens to match the parameter's (scalar params): never
# converted into shard-shaped accumulator slots.
_NON_SLOT_INPUTS = ("LearningRate", "Beta1Pow", "Beta2Pow",
                    "ShouldApply", "CurrentStep")

_QMAX = 127.0


def residual_name(param_name: str) -> str:
    return param_name + RESIDUAL_SUFFIX


def param_residual_name(param_name: str) -> str:
    return param_name + PARAM_RESIDUAL_SUFFIX


def master_shard_name(param_name: str) -> str:
    return param_name + MASTER_SHARD_SUFFIX


def axis_size(mesh, axis: str = "dp") -> int:
    if mesh is None:
        return 1
    return int(mesh.shape.get(axis, 1))


def model_axes(mesh, sync_axis: str = "dp") -> Tuple[str, ...]:
    """The mesh's MODEL-parallel axes: every axis other than the
    gradient-sync axis with extent > 1 (sp/tp/ep/pp). These shard
    activations and expert weights inside the forward/backward; the
    gradient-sync layer operates along ``sync_axis`` only."""
    if mesh is None:
        return ()
    return tuple(a for a in mesh.axis_names
                 if a != sync_axis and mesh.shape[a] > 1)


def finish_model_partials(g, mesh, sync_axis: str = "dp"):
    """Pin a parameter gradient replicated over the mesh BEFORE it
    enters the dp gradient-sync bracket.

    Under a dp×sp (or ×tp/×ep) mesh the backward produces each weight
    gradient as PARTIAL sums distributed over the model axes (every sp
    shard contributes its sequence chunk's term). The dp transports'
    shard_map in_specs are replicated, so GSPMD must finish that
    partial reduction first — this constraint makes the seam explicit:
    the model-axis all-reduce lands HERE, once, immediately before the
    dp collective, instead of wherever the partitioner's propagation
    happens to put it (and the fusion-boundary audit sees one stable
    boundary). A no-op on pure-dp meshes."""
    if not model_axes(mesh, sync_axis):
        return g
    return jax.lax.with_sharding_constraint(
        g, NamedSharding(mesh, PartitionSpec()))


def _numel(shape) -> int:
    return int(np.prod(shape)) if len(shape) else 1


def block_geometry(numel: int, world: int,
                   block_size: int = DEFAULT_BLOCK_SIZE
                   ) -> Tuple[int, int, int]:
    """(block, n_blocks, padded_len) for quantizing ``numel`` elements
    over ``world`` devices. Small tensors shrink the block (instead of
    padding a 64-element bias out to world*block elements) and n_blocks
    is rounded up to a multiple of ``world`` so the reduce-scatter deals
    whole blocks to every device."""
    world = max(1, int(world))
    bs = max(1, min(int(block_size), -(-numel // world)))
    nblk = -(-numel // bs)
    nblk = -(-nblk // world) * world
    return bs, nblk, nblk * bs


def quantize_q8(blocks):
    """Per-block symmetric int8: blocks [nblk, bs] f32 -> (q int8,
    scale f32 [nblk]). scale = blockmax/127 (1.0 for all-zero blocks so
    dequant is exactly 0); |dequant - x| <= scale/2 per element."""
    amax = jnp.max(jnp.abs(blocks), axis=1)
    scale = jnp.where(amax > 0, amax / _QMAX, 1.0)
    q = jnp.clip(jnp.round(blocks / scale[:, None]), -_QMAX, _QMAX)
    return q.astype(jnp.int8), scale


def dequantize_q8(q, scale):
    return q.astype(jnp.float32) * scale[:, None]


# ---------------------------------------------------------------------------
# host-side row codec (the sparse wire format)
# ---------------------------------------------------------------------------

# Embedding rows below this width ship exact fp32: at dim < 16 the
# 4-byte scale overhead erodes the int8 win (dim 8: 12/32 = 0.375x vs
# the 0.35x wire-bytes bar) and tiny rows are latency- not
# bandwidth-bound anyway.
SPARSE_Q8_MIN_DIM = 16


def quantize_rows_q8(rows: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """NumPy twin of ``quantize_q8`` for the HOST sparse path
    (PUSH_SPARSE/PREFETCH payloads move through the RPC plane, never
    XLA): each embedding row is one quantization block — ``rows``
    [n, dim] f32 -> (q int8 [n, dim], scale f32 [n]). Same format and
    semantics as ``quantize_q8`` with ``block_size = dim`` (scale =
    rowmax/127, 1.0 for all-zero rows, |dequant - x| <= scale/2), so
    device- and wire-quantization error models match."""
    rows = np.ascontiguousarray(rows, np.float32)
    amax = np.max(np.abs(rows), axis=1)
    scale = np.where(amax > 0, amax / _QMAX, 1.0).astype(np.float32)
    q = np.clip(np.rint(rows / scale[:, None]), -_QMAX, _QMAX)
    return q.astype(np.int8), scale


def dequantize_rows_q8(q: np.ndarray, scale: np.ndarray) -> np.ndarray:
    return q.astype(np.float32) * np.asarray(
        scale, np.float32)[:, None]


def sparse_wire_bytes(n_rows: int, dim: int, q8: bool,
                      ids_bytes: bool = True) -> int:
    """Payload bytes a sparse push/pull of ``n_rows`` moves: int64 ids
    (optional) + either f32 rows or int8 rows with one f32 scale each.
    Serialization headers excluded — this prices the algorithm, the
    bench rows report measured socket bytes."""
    ids = 8 * n_rows if ids_bytes else 0
    if q8:
        return ids + n_rows * (dim + 4)
    return ids + n_rows * dim * 4


def _pad_flat(x, padded_len: int):
    flat = x.reshape(-1)
    return jnp.pad(flat, (0, padded_len - flat.shape[0]))


# ---------------------------------------------------------------------------
# the three transports
# ---------------------------------------------------------------------------

def all_reduce_exact(g, mesh, axis: str = "dp"):
    """Explicit psum of the per-device partials g/n via shard_map."""
    n = axis_size(mesh, axis)
    if n <= 1:
        return g

    def local(x):
        return lax.psum(x / n, axis)

    return shard_map(local, mesh=mesh, in_specs=PartitionSpec(),
                     out_specs=PartitionSpec(), check_vma=False)(g)


def reduce_scatter_gather(g, mesh, axis: str = "dp"):
    """arXiv:2004.13336 decomposition: psum_scatter the partials, then
    all_gather the reduced shards. Rank-order reduction makes it
    bit-identical to ``all_reduce_exact`` (fp32 reduce order fixed)."""
    n = axis_size(mesh, axis)
    if n <= 1:
        return g
    numel = _numel(g.shape)
    padded = -(-numel // n) * n

    def local(x):
        flat = _pad_flat(x / n, padded)
        shard = lax.psum_scatter(flat.reshape(n, padded // n), axis,
                                 scatter_dimension=0, tiled=False)
        full = lax.all_gather(shard, axis, axis=0, tiled=True)
        return full[:numel].reshape(x.shape)

    return shard_map(local, mesh=mesh, in_specs=PartitionSpec(),
                     out_specs=PartitionSpec(), check_vma=False)(g)


def all_reduce_q8(g, residual, mesh=None, axis: str = "dp",
                  block_size: int = DEFAULT_BLOCK_SIZE):
    """Block-quantized all-reduce with error feedback.

    Per device: compensate ``c = g/n + residual``; quantize c into
    int8 blocks + f32 scales; all_to_all so each device holds every
    peer's copy of ITS block range (the reduce-scatter — int8 on the
    wire); dequant and accumulate the n partial slices in fp32 in rank
    order; requantize the reduced slice; all_gather (int8 on the wire
    again); dequant. Returns ``(synced, new_residual)`` where
    ``new_residual = c - synced/n`` carries exactly what this step
    failed to transmit. On a 1-device mesh the transport disappears but
    the quantize/dequant round-trip and residual semantics remain, so
    the mode means the same thing at every scale."""
    n = axis_size(mesh, axis)
    out_dtype = jnp.asarray(g).dtype
    numel = _numel(np.shape(g))
    bs, nblk, padded = block_geometry(numel, n, block_size)

    def _qdq(c):
        q, s = quantize_q8(_pad_flat(c, padded).reshape(nblk, bs))
        return dequantize_q8(q, s).reshape(padded)[:numel] \
            .reshape(np.shape(c))

    if n <= 1:
        c = jnp.asarray(g).astype(jnp.float32) + residual
        y = _qdq(c)
        return y.astype(out_dtype), c - y

    def local(x, r):
        c = x.astype(jnp.float32) / n + r
        q, s = quantize_q8(_pad_flat(c, padded).reshape(nblk, bs))
        # reduce-scatter phase: device d ships block-range j of its
        # (q, s) to device j and receives every peer's range d
        q_t = lax.all_to_all(q.reshape(n, nblk // n, bs), axis,
                             split_axis=0, concat_axis=0, tiled=False)
        s_t = lax.all_to_all(s.reshape(n, nblk // n), axis,
                             split_axis=0, concat_axis=0, tiled=False)
        # dequant/accumulate in fp32, rank order (deterministic)
        part = q_t.astype(jnp.float32) * s_t[:, :, None]
        reduced = jnp.sum(part, axis=0)  # [nblk//n, bs]
        # all-gather phase: requantize the reduced shard so the gather
        # also moves int8 + scales, not fp32
        q2, s2 = quantize_q8(reduced)
        q2_all = lax.all_gather(q2, axis, axis=0, tiled=True)
        s2_all = lax.all_gather(s2, axis, axis=0, tiled=True)
        y = dequantize_q8(q2_all, s2_all).reshape(padded)[:numel] \
            .reshape(x.shape)
        return y.astype(out_dtype), c - y / n

    return shard_map(local, mesh=mesh,
                     in_specs=(PartitionSpec(), PartitionSpec()),
                     out_specs=(PartitionSpec(), PartitionSpec()),
                     check_vma=False)(g, residual)


# ---------------------------------------------------------------------------
# sharded-update transports (arXiv:2004.13336): scatter grads, gather
# params. Each returns a GLOBAL flat [padded] array whose device layout
# is 1/n per replica over the dp axis — at trace level the global
# contents are the full padded tensor (so downstream global math, norms
# included, stays ordinary jax), while the per-chip footprint and the
# wire bytes are genuinely 1/n.
# ---------------------------------------------------------------------------

def reduce_scatter_shard(g, mesh, axis: str = "dp",
                         block_size: int = DEFAULT_BLOCK_SIZE):
    """Reduce-scatter the per-device partials ``g/n`` and STOP: returns
    the reduced gradient as a flat ``[padded]`` array sharded 1/n over
    ``axis`` (block_geometry padding so the same layout serves the q8
    variant and the shard-shaped accumulator slots). Rank-order
    psum_scatter — bit-identical content to ``all_reduce_exact``."""
    n = axis_size(mesh, axis)
    numel = _numel(np.shape(g))
    _bs, _nblk, padded = block_geometry(numel, n, block_size)
    if n <= 1:
        return _pad_flat(g, padded)

    def local(x):
        flat = _pad_flat(x / n, padded)
        return lax.psum_scatter(flat.reshape(n, padded // n), axis,
                                scatter_dimension=0, tiled=False)

    return shard_map(local, mesh=mesh, in_specs=PartitionSpec(),
                     out_specs=PartitionSpec(axis),
                     check_vma=False)(g)


def reduce_scatter_shard_q8(g, residual, mesh, axis: str = "dp",
                            block_size: int = DEFAULT_BLOCK_SIZE):
    """int8 reduce-scatter with error feedback: compensate
    ``c = g/n + r``, quantize into blocks, all_to_all the int8 blocks +
    f32 scales (each device receives every peer's copy of ITS block
    range), dequant/accumulate in fp32 rank order. Returns
    ``(grad_shard [padded] f32 sharded over axis, new_residual)`` where
    ``new_residual = c - qdq(c)`` is exactly what this device failed to
    ship — the same EF telescope as ``all_reduce_q8``, one quantization
    leg instead of two. On one device the wire disappears but the
    quantize/round-trip and residual semantics remain."""
    n = axis_size(mesh, axis)
    shape = np.shape(g)
    numel = _numel(shape)
    bs, nblk, padded = block_geometry(numel, n, block_size)

    if n <= 1:
        c = jnp.asarray(g).astype(jnp.float32) + residual
        q, s = quantize_q8(_pad_flat(c, padded).reshape(nblk, bs))
        sent = dequantize_q8(q, s).reshape(padded)
        return sent, c - sent[:numel].reshape(shape)

    def local(x, r):
        c = x.astype(jnp.float32) / n + r
        q, s = quantize_q8(_pad_flat(c, padded).reshape(nblk, bs))
        sent = dequantize_q8(q, s).reshape(padded)
        q_t = lax.all_to_all(q.reshape(n, nblk // n, bs), axis,
                             split_axis=0, concat_axis=0, tiled=False)
        s_t = lax.all_to_all(s.reshape(n, nblk // n), axis,
                             split_axis=0, concat_axis=0, tiled=False)
        reduced = jnp.sum(q_t.astype(jnp.float32) * s_t[:, :, None],
                          axis=0)  # [nblk//n, bs], rank order
        return reduced.reshape(-1), c - sent[:numel].reshape(x.shape)

    return shard_map(local, mesh=mesh,
                     in_specs=(PartitionSpec(), PartitionSpec()),
                     out_specs=(PartitionSpec(axis), PartitionSpec()),
                     check_vma=False)(g, residual)


def all_gather_params(p_shard, mesh, axis: str = "dp"):
    """fp32 all-gather of the freshly-updated param shards back to the
    full flat ``[padded]`` (replicated). Bit-exact: gather(slice(x))
    round-trips every element untouched."""
    n = axis_size(mesh, axis)
    if n <= 1:
        return p_shard

    def local(s):
        return lax.all_gather(s, axis, axis=0, tiled=True)

    return shard_map(local, mesh=mesh, in_specs=PartitionSpec(axis),
                     out_specs=PartitionSpec(),
                     check_vma=False)(p_shard)


def all_gather_params_q8(p_shard, residual, mesh, axis: str = "dp", *,
                         bs: int, nblk: int):
    """Quantized param gather with its OWN error feedback (EQuARX's
    second direction): compensate ``c = shard + r_p``, quantize the
    local block range, all-gather int8 + f32 scales, dequant. Returns
    ``(full_flat [padded] replicated, new_residual [padded] sharded)``
    with ``new_residual = c - qdq(c)``. The master shard (what the
    optimizer updates) never passes through the quantizer, so the error
    is bounded per step and the residual carries what each gather
    failed to express into the next one."""
    n = axis_size(mesh, axis)

    if n <= 1:
        c = p_shard + residual
        q, sc = quantize_q8(c.reshape(nblk, bs))
        y = dequantize_q8(q, sc).reshape(-1)
        return y, c - y

    def local(s, r):
        c = s + r
        q, sc = quantize_q8(c.reshape(nblk // n, bs))
        sent = dequantize_q8(q, sc).reshape(-1)
        q_all = lax.all_gather(q, axis, axis=0, tiled=True)
        sc_all = lax.all_gather(sc, axis, axis=0, tiled=True)
        return dequantize_q8(q_all, sc_all).reshape(-1), c - sent

    return shard_map(local, mesh=mesh,
                     in_specs=(PartitionSpec(axis), PartitionSpec(axis)),
                     out_specs=(PartitionSpec(), PartitionSpec(axis)),
                     check_vma=False)(p_shard, residual)


# ---------------------------------------------------------------------------
# bytes-on-wire estimator
# ---------------------------------------------------------------------------

def bytes_on_wire(shape, mode: Optional[str], world: int,
                  block_size: int = DEFAULT_BLOCK_SIZE,
                  dtype_bytes: int = 4,
                  param_gather: str = "fp32") -> int:
    """Estimated per-device wire bytes for the sync TRANSPORT of one
    gradient of ``shape`` over ``world`` devices, using the standard
    ring costs: all-reduce moves 2*(n-1)/n of the payload; the rs+ag
    decomposition moves the same total; q8 moves int8 blocks + f32
    scales through both phases. ``mode=None`` (implicit GSPMD) costs
    what the exact collective costs — the compiler inserts the same
    all-reduce. The sharded_update modes price their two HALF-trips
    separately: the reduce-scatter moves (n-1)/n of the (padded)
    payload ONCE (fp32, or int8 blocks + f32 scales under
    sharded_update_q8), and the param all-gather moves (n-1)/n once
    more, fp32 or int8+scales per ``param_gather``. This prices the
    algorithm, not the full lowered step (see the module docstring's
    composition note)."""
    world = int(world)
    if world <= 1:
        return 0
    numel = _numel(tuple(shape))
    ring = 2.0 * (world - 1) / world
    if mode in (None, "", "exact", "rs_ag"):
        return int(round(ring * numel * dtype_bytes))
    if mode == "q8":
        bs, nblk, padded = block_geometry(numel, world, block_size)
        return int(round(ring * (padded + 4 * nblk)))
    if mode in SHARDED_MODES:
        enforce(param_gather in PARAM_GATHER_MODES,
                "param_gather must be one of %s, got %r",
                PARAM_GATHER_MODES, param_gather)
        bs, nblk, padded = block_geometry(numel, world, block_size)
        half = (world - 1) / world
        q8_leg = half * (padded + 4 * nblk)
        fp_leg = half * padded * dtype_bytes
        scatter = q8_leg if mode == "sharded_update_q8" else fp_leg
        gather = q8_leg if param_gather == "q8" else fp_leg
        return int(round(scatter + gather))
    raise InvalidArgumentError(
        "unknown gradient_sync mode %r (one of %s)"
        % (mode, (None,) + GRAD_SYNC_MODES))


def _sparse_grad_params(block) -> set:
    """Parameter names whose gradient arrives as SparseRows (produced
    by a lookup_table_grad op, nn_ops.py): the sync layer leaves those
    on the implicit path, so residual slots and byte estimates must
    not count them."""
    from ..framework import grad_var_name, Parameter
    sparse_grads = set()
    for op in block.ops:
        if op.type == "lookup_table_grad":
            sparse_grads.update(op.output_arg_names)
    return {p.name for p in block.vars.values()
            if isinstance(p, Parameter)
            and grad_var_name(p.name) in sparse_grads}


def grad_bytes_per_step(program, mode: Optional[str], world: int,
                        block_size: int = DEFAULT_BLOCK_SIZE,
                        param_gather: str = "fp32") -> int:
    """Total estimated gradient-sync wire bytes for one train step of
    ``program`` (sum over its dense-synced trainable parameters)."""
    from ..framework import Parameter
    block = program.global_block()
    sparse = _sparse_grad_params(block)
    total = 0
    for p in block.vars.values():
        if isinstance(p, Parameter) and getattr(p, "trainable", True) \
                and p.name not in sparse:
            total += bytes_on_wire(p.shape, mode, world, block_size,
                                   param_gather=param_gather)
    return total


# ---------------------------------------------------------------------------
# executor integration: the @GRAD rewrite plan
# ---------------------------------------------------------------------------

class GradSyncPlan:
    """Where and how to rewrite gradient values inside one traced step:
    at op index ``boundary`` (the first optimize-role op that consumes
    a parameter gradient — i.e. after ALL backward accumulation, before
    regularizers/clipping/updates read the grads), replace each
    ``param@GRAD`` env entry with its synced value."""

    # pointwise rewrite plans have no closing hook; the executor probes
    # this uniformly (ShardedUpdatePlan sets a real index)
    end_boundary = None

    def __init__(self, mode, mesh, axis, boundary, entries, block_size):
        self.mode = mode
        self.mesh = mesh
        self.axis = axis
        self.boundary = boundary
        self.entries = entries  # [(param, grad_key, residual_key)]
        self.block_size = block_size

    def apply(self, env: Dict):
        from ..core.selected_rows import SparseRows
        for _pname, gkey, rkey in self.entries:
            v = env.get(gkey)
            if v is None or isinstance(v, SparseRows):
                # sparse embedding grads stay on the implicit path (the
                # same posture dgc takes: compressing an already-sparse
                # grad is redundant)
                continue
            if not jnp.issubdtype(jnp.asarray(v).dtype, jnp.floating):
                continue
            # dp×sp/tp composition: the model-axis partial sums finish
            # here, so the transport below sees the SAME full-batch
            # gradient it sees on a pure-dp mesh (and q8's residual
            # telescope stays a dp-axis-only story)
            v = finish_model_partials(v, self.mesh, self.axis)
            if self.mode == "exact":
                env[gkey] = all_reduce_exact(v, self.mesh, self.axis)
            elif self.mode == "rs_ag":
                env[gkey] = reduce_scatter_gather(v, self.mesh,
                                                  self.axis)
            else:  # q8
                r = env.get(rkey)
                if r is None:
                    r = jnp.zeros(np.shape(v), jnp.float32)
                y, r_new = all_reduce_q8(v, r, self.mesh, self.axis,
                                         self.block_size)
                env[gkey] = y
                env[rkey] = r_new


class _ShardEntry:
    """Per-parameter record of the sharded bracket: geometry, the
    shard-shaped accumulator slots, and the names of the sharded-state
    families (grad residual / param residual / master shard)."""

    __slots__ = ("pname", "gkey", "shape", "numel", "bs", "nblk",
                 "padded", "slots", "grad_res_key", "param_res_key",
                 "master_key")

    def __init__(self, pname, shape, bs, nblk, padded, slots):
        from ..framework import grad_var_name
        self.pname = pname
        self.gkey = grad_var_name(pname)
        self.shape = tuple(shape)
        self.numel = _numel(self.shape)
        self.bs, self.nblk, self.padded = bs, nblk, padded
        self.slots = list(slots)
        self.grad_res_key = residual_name(pname)
        self.param_res_key = param_residual_name(pname)
        self.master_key = master_shard_name(pname)


def sharded_entries(block, world: int,
                    block_size: int = DEFAULT_BLOCK_SIZE,
                    reject_dgc: bool = True):
    """(boundary, end_boundary, entries) of the shard→update→gather
    bracket for a block. ``boundary`` is the first non-vjp op consuming
    a dense trainable parameter gradient (regularizers carry backward
    role in this codebase, so the pointwise plans' optimize-role rule
    would open the bracket too late); ``end_boundary`` is one past the
    last op that writes a bracketed parameter. Slot vars are the
    persistable param-shaped inputs/outputs of the update ops (adam
    m/v, momentum velocities, grad-accumulation Acc, AMP master copies
    — anything shaped like the param that the update carries), found by
    scanning ops that either write the param or consume its gradient;
    LR/beta-pow/counter scalars are excluded by slot name."""
    from ..framework import Parameter, grad_var_name
    sparse = _sparse_grad_params(block)
    params = {p.name: p for p in block.vars.values()
              if isinstance(p, Parameter)
              and getattr(p, "trainable", True)
              and p.name not in sparse}
    if not params:
        return None, None, []
    g2p = {grad_var_name(n): n for n in params}
    boundary = None
    end = None
    slot_map = {n: [] for n in params}
    wrote_param = set()
    for i, op in enumerate(block.ops):
        if op.type in ("vjp", "vjp2"):
            continue
        ins = op.input_arg_names
        outs = op.output_arg_names
        consumed = [g2p[n] for n in ins if n in g2p]
        wrote = [n for n in outs if n in params]
        if boundary is None and consumed:
            boundary = i
        if op.attrs.get("op_role") != "optimize":
            continue
        if op.type == "dgc" and reject_dgc:
            # only the sharded transports reject dgc; measurement
            # callers (slot_bytes_per_chip) scan any program
            raise UnimplementedError(
                "sharded_update does not compose with dgc: its top-k "
                "threshold needs the full |v| tensor on every replica; "
                "use gradient_sync='q8' with DGCMomentumOptimizer")
        owner = wrote[0] if wrote else (consumed[0] if consumed else
                                        None)
        if owner is None:
            continue
        if wrote:
            end = i + 1
            wrote_param.update(wrote)
        pshape = tuple(params[owner].shape)
        pnumel = _numel(pshape)
        skip = {owner}
        for slot_name in _NON_SLOT_INPUTS:
            skip.update(op.inputs.get(slot_name, ()))
        for n in list(ins) + list(outs):
            if n in skip or n in g2p:
                continue
            v = block.vars.get(n)
            if v is None or not v.persistable \
                    or isinstance(v, Parameter):
                continue
            geom = getattr(v, "_shard_geometry", None)
            if tuple(v.shape) == pshape or \
                    (geom is not None and geom[0] == pnumel):
                if n not in slot_map[owner]:
                    slot_map[owner].append(n)
    if boundary is None or end is None:
        return None, None, []
    entries = []
    for pname in sorted(wrote_param):
        p = params[pname]
        numel = _numel(tuple(p.shape))
        bs, nblk, padded = block_geometry(numel, world, block_size)
        entries.append(_ShardEntry(pname, p.shape, bs, nblk, padded,
                                   slot_map[pname]))
    return boundary, end, entries


class ShardedUpdatePlan:
    """The shard→update→gather bracket around the optimize-role ops.

    ``apply`` (at ``boundary``): reduce-scatter each dense parameter
    gradient to a flat ``[padded]`` shard (fp32 bit-exact, or int8
    blocks with grad-side error feedback under sharded_update_q8) and
    swap the param env entry to its flat shard — the master shard when
    the param gather quantizes, a free local slice of the full param
    otherwise. Every op inside the bracket (regularizer, clip,
    accumulation, update) then runs on 1/n-laid-out flats; global
    reductions (norm clip, lamb trust ratios) still see the full
    global value, with GSPMD reducing the sharded operand.

    ``finish`` (at ``end_boundary``): carry the updated shard into the
    master slot, all-gather the fresh params (fp32, or int8 + scales
    with the param-side residual), and restore the param env entry to
    full shape for everything downstream (EMA/averaging ops, the next
    step's forward). When the anomaly guard's flag is in the env, a
    gated (bad) step select-restores the gathered params and the
    param-side residuals, so a skipped step leaves shards, residuals,
    and params bit-identical."""

    def __init__(self, mode, param_gather, mesh, axis, boundary,
                 end_boundary, entries, block_size):
        self.mode = mode
        self.quant_grads = mode == "sharded_update_q8"
        self.param_gather = param_gather
        self.mesh = mesh
        self.axis = axis
        self.boundary = boundary
        self.end_boundary = end_boundary
        self.entries = entries
        self.block_size = block_size

    def _shard_layout(self, flat):
        if axis_size(self.mesh, self.axis) > 1:
            return jax.lax.with_sharding_constraint(
                flat, NamedSharding(self.mesh,
                                    PartitionSpec(self.axis)))
        return flat

    def apply(self, env: Dict):
        from ..core.selected_rows import SparseRows
        for e in self.entries:
            g = env.get(e.gkey)
            p_full = env.get(e.pname)
            if g is None or p_full is None \
                    or isinstance(g, SparseRows):
                continue
            if not jnp.issubdtype(jnp.asarray(g).dtype, jnp.floating):
                continue
            # model-axis partial sums must complete before the shard
            # bracket opens (see GradSyncPlan.apply)
            g = finish_model_partials(g, self.mesh, self.axis)
            if self.quant_grads:
                r = env.get(e.grad_res_key)
                if r is None:
                    r = jnp.zeros(e.shape, jnp.float32)
                gs, r_new = reduce_scatter_shard_q8(
                    g, r, self.mesh, self.axis, self.block_size)
                env[e.grad_res_key] = r_new
            else:
                gs = reduce_scatter_shard(g, self.mesh, self.axis,
                                          self.block_size)
            env[e.gkey] = gs
            env[("sharded_full", e.pname)] = p_full
            master = env.get(e.master_key) \
                if self.param_gather == "q8" else None
            if master is not None:
                env[e.pname] = master
            else:
                env[e.pname] = self._shard_layout(
                    _pad_flat(p_full, e.padded))

    def finish(self, env: Dict):
        from ..resilience.guard import FLAG_KEY
        flag = env.get(FLAG_KEY)
        for e in self.entries:
            key = ("sharded_full", e.pname)
            if key not in env:
                continue
            old_full = env.pop(key)
            shard = env[e.pname]
            if self.param_gather == "q8":
                # the exact master carries forward; gate protection is
                # inherited from the update op's own select
                env[e.master_key] = shard
                rp = env.get(e.param_res_key)
                if rp is None:
                    rp = self._shard_layout(
                        jnp.zeros((e.padded,), jnp.float32))
                full_flat, rp_new = all_gather_params_q8(
                    shard, rp, self.mesh, self.axis,
                    bs=e.bs, nblk=e.nblk)
                if flag is not None:
                    rp_new = jnp.where(flag, rp_new, rp)
                env[e.param_res_key] = rp_new
            else:
                full_flat = all_gather_params(shard, self.mesh,
                                              self.axis)
            full = full_flat[:e.numel].reshape(e.shape).astype(
                jnp.asarray(old_full).dtype)
            if flag is not None:
                full = jnp.where(flag, full, old_full)
            env[e.pname] = full
            # the full gradient ceases to exist after the scatter
            # (that IS the ZeRO memory win) — drop the flat shard so a
            # downstream read/fetch fails loudly instead of silently
            # seeing a [padded] 1/n slice where every other mode
            # yields the full synced gradient
            env.pop(e.gkey, None)


def make_plan(block, mode: Optional[str], mesh, axis: str = "dp",
              block_size: int = DEFAULT_BLOCK_SIZE,
              param_gather: str = "fp32"):
    """Build the rewrite plan for a block, or None when the mode is
    unset or the block has no optimizer consuming parameter grads
    (inference/forward-only programs sync nothing)."""
    if not mode:
        return None
    enforce(mode in GRAD_SYNC_MODES,
            "BuildStrategy.gradient_sync must be one of %s, got %r",
            GRAD_SYNC_MODES, mode)
    if mode in SHARDED_MODES:
        enforce(mesh is not None,
                "sharded_update needs a device mesh (run through "
                "CompiledProgram.with_data_parallel)")
        enforce(param_gather in PARAM_GATHER_MODES,
                "BuildStrategy.param_gather must be one of %s, got %r",
                PARAM_GATHER_MODES, param_gather)
        world = axis_size(mesh, axis)
        boundary, end, entries = sharded_entries(block, world,
                                                 block_size)
        if boundary is None or not entries:
            return None
        return ShardedUpdatePlan(mode, param_gather, mesh, axis,
                                 boundary, end, entries, block_size)
    from ..framework import Parameter, grad_var_name
    sparse = _sparse_grad_params(block)
    params = [p for p in block.vars.values()
              if isinstance(p, Parameter)
              and getattr(p, "trainable", True)
              and p.name not in sparse]
    if not params:
        return None
    grad_keys = {grad_var_name(p.name) for p in params}
    boundary = None
    for i, op in enumerate(block.ops):
        if op.attrs.get("op_role") == "optimize" and \
                any(n in grad_keys for n in op.input_arg_names):
            boundary = i
            break
    if boundary is None:
        return None
    entries = [(p.name, grad_var_name(p.name), residual_name(p.name))
               for p in sorted(params, key=lambda p: p.name)]
    return GradSyncPlan(mode, mesh, axis, boundary, entries, block_size)


def _scope_uid(scope) -> int:
    """Monotonic scope identity for memo keys. NEVER id(scope): a GC'd
    scope's address is reused by fresh scopes, and a recycled id with a
    matching program version silently skips state creation for the new
    scope (the residual-memo bug this replaced)."""
    return getattr(scope, "_uid", None) or id(scope)


def ensure_residual_vars(program, scope):
    """Create the persistable error-feedback residual var for every
    dense-synced trainable parameter (idempotent) and zero-fill it in
    ``scope`` so the executor's persistable carry picks it up from the
    first traced step — the same lifecycle as the dgc U/V accumulator
    slots. Memoized per (program version, scope uid) so the per-step
    dispatch path does not rescan the block."""
    from ..framework import Parameter
    memo = (program._version, _scope_uid(scope))
    if getattr(program, "_q8_residual_memo", None) == memo:
        return
    block = program.global_block()
    sparse = _sparse_grad_params(block)
    for p in list(block.vars.values()):
        if not isinstance(p, Parameter) or \
                not getattr(p, "trainable", True) or p.name in sparse:
            continue
        rname = residual_name(p.name)
        if rname not in block.vars:
            block.create_var(name=rname, shape=tuple(p.shape),
                             dtype="float32", persistable=True,
                             stop_gradient=True)
        if not scope.has_var(rname) or scope.find_var(rname) is None:
            scope.set_var(rname,
                          jnp.zeros(tuple(p.shape), jnp.float32))
    program._q8_residual_memo = (program._version, _scope_uid(scope))


# ---------------------------------------------------------------------------
# sharded-update state lifecycle
# ---------------------------------------------------------------------------

def _place_shard(arr: np.ndarray, mesh, axis: str):
    """Device-place a flat [padded] host array 1/n over the axis (or
    just on-device for a 1-wide axis)."""
    if mesh is not None and axis_size(mesh, axis) > 1:
        return jax.device_put(
            arr, NamedSharding(mesh, PartitionSpec(axis)))
    return jnp.asarray(arr)


def _to_padded_flat(value, padded: int) -> np.ndarray:
    arr = np.asarray(jax.device_get(value))
    out = np.zeros((padded,), arr.dtype)
    out[:arr.size] = arr.reshape(-1)
    return out


def ensure_sharded_state(program, scope, mesh, axis: str = "dp",
                         param_gather: str = "fp32",
                         block_size: int = DEFAULT_BLOCK_SIZE):
    """Convert ``program``'s optimizer accumulator slots to the sharded
    layout and make sure ``scope`` carries them (plus, under
    ``param_gather='q8'``, the master shards seeded from the current
    params and the zeroed param-side residuals).

    Idempotent and value-preserving: a full-shape slot value already in
    the scope (startup-program zeros, or a replicated-era training
    state) is pad-flattened into the ``[padded]`` shard layout; an
    already-converted value is left alone. Block declarations are
    reshaped to ``(padded,)``, annotated with ``sharding=P(axis)`` (so
    the executor's persist placement and jit out_shardings pin the 1/n
    layout) and stamped with ``_shard_geometry=(numel, padded)`` (so
    checkpoint restore recognizes the layout — io._check_and_set).
    Memoized per (program version, scope uid, world, param_gather) so
    the per-step dispatch path does not rescan the block. Run the
    startup program BEFORE the first sharded step; re-running it
    afterwards resets the slots to full-shape zeros behind the memo's
    back (the same lifecycle contract as the q8 residuals)."""
    enforce(param_gather in PARAM_GATHER_MODES,
            "param_gather must be one of %s, got %r",
            PARAM_GATHER_MODES, param_gather)
    world = axis_size(mesh, axis)
    memo = (program._version, _scope_uid(scope), world, param_gather,
            block_size)
    if getattr(program, "_sharded_state_memo", None) == memo:
        return
    block = program.global_block()
    boundary, _end, entries = sharded_entries(block, world, block_size)
    if boundary is None or not entries:
        program._sharded_state_memo = memo
        return
    changed = False
    for e in entries:
        geom = (e.numel, e.padded)
        names = list(e.slots)
        if param_gather == "q8":
            for extra in (e.master_key, e.param_res_key):
                if extra not in block.vars:
                    block.create_var(name=extra, shape=(e.padded,),
                                     dtype="float32", persistable=True,
                                     stop_gradient=True)
                    changed = True
            names += [e.master_key, e.param_res_key]
        for name in names:
            v = block.vars[name]
            if tuple(v.shape) != (e.padded,):
                v.shape = (e.padded,)
                changed = True
            if getattr(v, "_shard_geometry", None) != geom:
                v._shard_geometry = geom
                v.sharding = PartitionSpec(axis)
                changed = True
        for name in e.slots:
            if not scope.has_var(name):
                continue
            val = scope.find_var(name)
            if val is None or tuple(np.shape(val)) == (e.padded,):
                continue
            vnumel = int(np.prod(np.shape(val))) if np.shape(val) \
                else 1
            # a full-shape value (startup zeros / replicated-era
            # training state) has the param's numel; anything else flat
            # is a shard padded for a DIFFERENT world size — padding it
            # again would corrupt or crash deep in numpy, so be loud
            enforce(vnumel == e.numel,
                    "optimizer slot %r holds a [%d] shard but this "
                    "mesh's layout wants [%d] (param numel %d): the "
                    "scope was converted under a different device "
                    "count — sharded_update state must be restored and "
                    "run under the same device count it was trained "
                    "with", name, vnumel, e.padded, e.numel)
            scope.set_var(name, _place_shard(
                _to_padded_flat(val, e.padded), mesh, axis))
        if param_gather == "q8":
            # the master/residual families only ever exist in the
            # [padded] layout (created here or checkpoint-restored), so
            # a present-but-wrong-shape value is sharded state from a
            # DIFFERENT device count — reseeding the master from the
            # current param would bake the quantized gather image into
            # the exact masters and zeroing the residual would drop the
            # EF history, so be as loud as the slot conversion above
            for fam in (e.master_key, e.param_res_key):
                fval = scope.find_var(fam) if scope.has_var(fam) \
                    else None
                if fval is not None \
                        and tuple(np.shape(fval)) != (e.padded,):
                    fnumel = int(np.prod(np.shape(fval))) \
                        if np.shape(fval) else 1
                    enforce(False,
                            "sharded state %r holds a [%d] shard but "
                            "this mesh's layout wants [%d]: the scope "
                            "was converted under a different device "
                            "count — sharded_update state must be "
                            "restored and run under the same device "
                            "count it was trained with",
                            fam, fnumel, e.padded)
            pval = scope.find_var(e.pname) \
                if scope.has_var(e.pname) else None
            mval = scope.find_var(e.master_key) \
                if scope.has_var(e.master_key) else None
            if mval is None and pval is not None:
                # seed the master from the CURRENT full param — the
                # full var becomes the quantized gather's output from
                # the next step on, the master stays exact
                scope.set_var(e.master_key, _place_shard(
                    _to_padded_flat(pval, e.padded).astype(np.float32),
                    mesh, axis))
            rval = scope.find_var(e.param_res_key) \
                if scope.has_var(e.param_res_key) else None
            if rval is None:
                scope.set_var(e.param_res_key, _place_shard(
                    np.zeros((e.padded,), np.float32), mesh, axis))
    if changed:
        program._bump()
    program._sharded_state_memo = (program._version, _scope_uid(scope),
                                   world, param_gather, block_size)


def reject_stale_sharded_layout(block):
    """Refuse to trace update ops over shard-laid-out slots without a
    ShardedUpdatePlan.

    ``ensure_sharded_state`` rewrites a program's accumulator slot
    DECLARATIONS to the flat ``[padded]`` layout; that program's
    optimize-role ops only make sense inside the shard→update→gather
    bracket. Running it through a non-sharded path (plain ``exe.run``,
    a CompiledProgram without a sharded ``gradient_sync``,
    ``run_repeated``/``run_pipelined`` on the raw program) would crash
    deep in the update lowering with a bare shape mismatch — or worse,
    broadcast a ``[padded]`` slot against a full-shape grad. Detect it
    at trace time and say what happened. A ``clone(for_test=True)``
    program passes: its optimizer ops are pruned, and forward ops never
    touch slot vars."""
    for op in block.ops:
        if op.attrs.get("op_role") != "optimize":
            continue
        for n in list(op.input_arg_names) + list(op.output_arg_names):
            v = block.vars.get(n)
            if v is not None and \
                    getattr(v, "_shard_geometry", None) is not None:
                raise InvalidArgumentError(
                    "op %r reads optimizer slot %r which is in the "
                    "1/n sharded layout (converted by "
                    "gradient_sync='sharded_update'): this program "
                    "must keep running through the sharded "
                    "CompiledProgram that converted it — a plain run "
                    "would corrupt the shards" % (op.type, n))


def slot_bytes_per_chip(program, scope) -> int:
    """Measured per-chip bytes of the optimizer's per-parameter carry:
    accumulator slots plus (when present) master shards and param-side
    residuals, summed over the scope's live values. A value with a
    sharding contributes its per-device shard size (replicated values
    count in full — every chip holds them); host arrays count in full.
    This is the number the sharded_update memory claim is about: under
    a dp=n mesh it scales ~1/n of the replicated total."""
    block = program.global_block()
    _b, _e, entries = sharded_entries(block, 1, reject_dgc=False)
    total = 0
    seen = set()
    for e in entries:
        names = list(e.slots)
        for extra in (e.master_key, e.param_res_key):
            if extra in block.vars:
                names.append(extra)
        for name in names:
            if name in seen:
                continue
            seen.add(name)
            val = scope.find_var(name) if scope.has_var(name) else None
            if val is None:
                continue
            sh = getattr(val, "sharding", None)
            if sh is not None and hasattr(sh, "shard_shape"):
                shard = sh.shard_shape(tuple(val.shape))
                total += int(np.prod(shard)) * val.dtype.itemsize
            else:
                total += int(np.asarray(val).nbytes)
    return total
