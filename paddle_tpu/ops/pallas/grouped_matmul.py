"""Grouped matrix product: the rows of ``lhs``, sorted by group, each
times its own group's matrix.

``grouped_matmul(lhs [M, K], rhs [E, K, N], group_sizes [E]) -> [M, N]``
with ``out[r] = lhs[r] @ rhs[g(r)]`` where rows ``0..group_sizes[0]-1``
belong to group 0 and so on. The sizes need not fill ``lhs``: rows
past their sum are slack, come out as exact zeros whatever they hold
and cost the kernels nothing (their grid ends at the last real row's
tile; the sizes ride as a scalar-prefetched operand). This is the
product an expert layer makes over one chunk of the rows routed to the
experts it holds (parallel/moe.py ``held_experts_ffn``), which walks
its sorted rows in chunks so that what XLA does between the products
is as short as the step's load too.

``grouped_matmul_pullback`` is the product's backward pass with the
matrices' gradient ADDED to a float32 sum the caller carries: a layer
that makes the product a chunk at a time sums every row's part in
float32 and rounds once, as one call over all rows does inside the
kernel. ``grouped_matmul`` differentiates through it.

On the TPU the lowering is the Mosaic kernel family that ships with
jax (``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` for the
forward product and the gradient of ``lhs``, ``tgmm`` with
``existing_out`` for the sum of ``rhs``'s gradient), wrapped here for
three reasons: its tiling default (128 cubed) is a test size, its own
``custom_vjp`` leaves the slack rows of every product uninitialised
and rounds the matrices' gradient a call, and a lowering has to be
counted. Off the TPU (CPU tests, ``interpret_mode()``) it is
``jax.lax.ragged_dot`` and, for the matrices' gradient,
``ragged_dot_general`` over the ragged contraction.

Each product lowered bumps ``moe_lowering.gmm_pallas`` or
``moe_lowering.ragged_dot`` at trace time, as ``sdpa_lowering.*`` is
counted (ops/pallas/attention.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .common import count_lowering, interpret_mode

# Row tile of the TPU kernels: a group that starts or ends inside a
# tile pays for the whole tile, so the tile is what the padding is
# counted in (``tile_rounded_rows``; telemetry()["moe"]
# rows_computed_total).
TILE_M = 128


def _tiling(k, n):
    """(tm, tk, tn) of a product [m, k] x [k, n]. k and n whole up to
    2048 x 1024 elements a tile (4 MB of bf16, double-buffered inside
    the 16 MB of scoped VMEM): with one k- and one n-tile the
    consecutive row tiles of a group present the same ``rhs`` block, so
    a group's matrix is fetched once."""
    tk = min(k, 2048)
    return TILE_M, tk, min(n, (2048 * 1024) // tk)


def tile_rounded_rows(group_sizes, tile=TILE_M):
    """Rows the kernel computes for these sizes: each non-empty group's
    span from the start of its first row's tile to the end of its last
    row's (a tile shared by two groups is visited once for each)."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    spans = (ends + tile - 1) // tile * tile - starts // tile * tile
    return jnp.sum(jnp.where(group_sizes > 0, spans, 0))


def _megablox():
    """The module of the raw kernels: the package's own ``gmm`` name
    is its custom_vjp function, which shadows the module."""
    import importlib
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _zero_slack(x, group_sizes):
    rows = lax.broadcasted_iota(jnp.int32, (x.shape[0], 1), 0)
    return jnp.where(rows < jnp.sum(group_sizes), x, jnp.zeros((), x.dtype))


def _gmm(lhs, rhs, group_sizes, transpose_rhs=False):
    """lhs [M, K] times each row's own rhs[g] ([K, N], or [N, K] with
    ``transpose_rhs``), the slack rows zeroed."""
    if interpret_mode():
        out = lax.ragged_dot(
            lhs, rhs.swapaxes(1, 2) if transpose_rhs else rhs,
            group_sizes, preferred_element_type=jnp.float32)
    else:
        k = lhs.shape[1]
        n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
        out = _megablox().gmm(lhs, rhs, group_sizes, lhs.dtype,
                              _tiling(k, n), transpose_rhs=transpose_rhs)
    return _zero_slack(out.astype(lhs.dtype), group_sizes)


def _tgmm_add(lhs, g, group_sizes, acc):
    """acc [E, K, N] float32 plus, for each group, the sum over its
    rows of lhs[r]^T g[r]."""
    if interpret_mode():
        over_rows = lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=(([0], [0]), ([], [])),
            lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
        return acc + lax.ragged_dot_general(
            lhs, g, group_sizes, over_rows,
            preferred_element_type=jnp.float32)
    k, n = lhs.shape[1], g.shape[1]
    return _megablox().tgmm(
        lhs.swapaxes(0, 1), g, group_sizes, jnp.float32,
        (2 * TILE_M, min(k, 512), min(n, 1024)),
        num_actual_groups=acc.shape[0], existing_out=acc)


def _count(products=1):
    count_lowering("moe_lowering.ragged_dot" if interpret_mode()
                   else "moe_lowering.gmm_pallas", products)


def grouped_matmul_pullback(lhs, rhs, group_sizes, g, d_rhs_sum):
    """The backward pass of ``grouped_matmul(lhs, rhs, group_sizes)``
    for the output's cotangent ``g`` [M, N]: (``d_lhs`` [M, K] in
    ``lhs``'s type, slack rows zero; ``d_rhs_sum`` [E, K, N] float32
    plus this call's rows' part of ``rhs``'s gradient)."""
    group_sizes = group_sizes.astype(jnp.int32)
    g = g.astype(lhs.dtype)
    _count(2)
    return (_gmm(g, rhs, group_sizes, transpose_rhs=True),
            _tgmm_add(lhs, g, group_sizes, d_rhs_sum))


@jax.custom_vjp
def grouped_matmul(lhs, rhs, group_sizes):
    """See the module's docstring. ``group_sizes`` int32, its sum at
    most ``lhs.shape[0]``; ``lhs`` and ``rhs`` of one dtype."""
    _count()
    return _gmm(lhs, rhs, group_sizes.astype(jnp.int32))


def _grouped_matmul_fwd(lhs, rhs, group_sizes):
    return grouped_matmul(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _grouped_matmul_bwd(res, g):
    lhs, rhs, group_sizes = res
    d_lhs, d_rhs = grouped_matmul_pullback(
        lhs, rhs, group_sizes, g, jnp.zeros(rhs.shape, jnp.float32))
    return d_lhs, d_rhs.astype(rhs.dtype), None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)
