"""Grouped matrix product: the rows of ``lhs``, sorted by group, each
times its own group's matrix.

``grouped_matmul(lhs [M, K], rhs [E, K, N], group_sizes [E]) -> [M, N]``
with ``out[r] = lhs[r] @ rhs[g(r)]`` where rows ``0..group_sizes[0]-1``
belong to group 0 and so on. The sizes need not fill ``lhs``: rows
past their sum are the slack of a static buffer, come out as exact
zeros and cost nothing (the kernel's grid ends at the last real row's
tile; the sizes ride as a scalar-prefetched operand). This is the
product an expert layer makes over the tokens routed to the experts it
holds (parallel/moe.py ``held_experts_ffn``).

On the TPU the lowering is the Mosaic kernel family that ships with
jax (``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` for the
forward product and the gradient of ``lhs``, ``tgmm`` for the gradient
of ``rhs``), wrapped here for three reasons: its tiling default
(128 cubed) is a test size, its own ``custom_vjp`` leaves the slack
rows of every product uninitialised, and a lowering has to be counted.
Off the TPU (CPU tests, ``interpret_mode()``) it is
``jax.lax.ragged_dot``, which differentiates by itself.

Each lowering bumps ``moe_lowering.gmm_pallas`` or
``moe_lowering.ragged_dot`` at trace time, as ``sdpa_lowering.*`` is
counted (ops/pallas/attention.py).
"""

from __future__ import annotations

import functools

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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm_tpu(lhs, rhs, group_sizes, transpose_rhs=False):
    mb = _megablox()
    k = lhs.shape[1]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    out = mb.gmm(lhs, rhs, group_sizes, lhs.dtype, _tiling(k, n),
                 transpose_rhs=transpose_rhs)
    return _zero_slack(out, group_sizes)


def _gmm_tpu_fwd(lhs, rhs, group_sizes, transpose_rhs):
    return (_gmm_tpu(lhs, rhs, group_sizes, transpose_rhs),
            (lhs, rhs, group_sizes))


def _gmm_tpu_bwd(transpose_rhs, res, g):
    mb = _megablox()
    lhs, rhs, group_sizes = res
    g = g.astype(lhs.dtype)
    d_lhs = _gmm_tpu(g, rhs, group_sizes, not transpose_rhs)
    # [E, k, n] = sum over each group's rows of lhs[r]^T g[r]
    k, n = lhs.shape[1], g.shape[1]
    d_rhs = mb.tgmm(lhs.swapaxes(0, 1), g, group_sizes, rhs.dtype,
                    (2 * TILE_M, min(k, 512), min(n, 1024)),
                    num_actual_groups=rhs.shape[0])
    if transpose_rhs:
        d_rhs = d_rhs.swapaxes(1, 2)
    return d_lhs, d_rhs, None


_gmm_tpu.defvjp(_gmm_tpu_fwd, _gmm_tpu_bwd)


def grouped_matmul(lhs, rhs, group_sizes):
    """See the module's docstring. ``group_sizes`` int32, its sum at
    most ``lhs.shape[0]``; ``lhs`` and ``rhs`` of one dtype."""
    group_sizes = group_sizes.astype(jnp.int32)
    if interpret_mode():
        count_lowering("moe_lowering.ragged_dot")
        out = lax.ragged_dot(lhs, rhs, group_sizes,
                             preferred_element_type=jnp.float32)
        return _zero_slack(out.astype(lhs.dtype), group_sizes)
    count_lowering("moe_lowering.gmm_pallas")
    return _gmm_tpu(lhs, rhs, group_sizes, False)
