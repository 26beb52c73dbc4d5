"""ops/pallas/grouped_matmul.py against a loop over the groups: values
and both gradients, empty groups and a slack tail included; on the
CPU's lowering (``lax.ragged_dot``) and on the TPU's wrapper with the
Mosaic kernels run by the Pallas interpreter."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import profiler
from paddle_tpu.ops.pallas import grouped_matmul as G

# rows to each of 6 groups: two empty (one leading the buffer's tail),
# boundaries inside tiles, 90 rows of slack after the last
SIZES = [130, 0, 7, 256, 0, 29]
M, K, N = 512, 64, 256


def loop(lhs, rhs, sizes):
    out, at = np.zeros((lhs.shape[0], rhs.shape[2]), np.float64), 0
    for g, n in enumerate(sizes):
        out[at:at + n] = lhs[at:at + n].astype(np.float64) \
            @ rhs[g].astype(np.float64)
        at += n
    return out


def operands(seed=0):
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.randn(M, K), jnp.float32),
            jnp.asarray(r.randn(len(SIZES), K, N) / K ** 0.5,
                        jnp.float32),
            jnp.asarray(SIZES, jnp.int32),
            jnp.asarray(r.randn(M, N), jnp.float32))


def check(fn):
    lhs, rhs, sizes, t = operands()
    n = sum(SIZES)
    got = np.asarray(jax.jit(fn)(lhs, rhs, sizes))
    np.testing.assert_allclose(got, loop(np.asarray(lhs),
                                         np.asarray(rhs), SIZES),
                               rtol=1e-5, atol=1e-5)
    assert (got[n:] == 0).all()                 # the slack: exact zeros
    d_lhs, d_rhs = jax.jit(jax.grad(
        lambda a, b: jnp.sum(fn(a, b, sizes) * t), (0, 1)))(lhs, rhs)
    tn, ln = np.asarray(t, np.float64), np.asarray(lhs, np.float64)
    want_rhs = np.zeros(rhs.shape)
    want_lhs = np.zeros(lhs.shape)
    at = 0
    for g, s in enumerate(SIZES):
        want_rhs[g] = ln[at:at + s].T @ tn[at:at + s]
        want_lhs[at:at + s] = tn[at:at + s] @ np.asarray(rhs[g]).T
        at += s
    np.testing.assert_allclose(d_lhs, want_lhs, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(d_rhs, want_rhs, rtol=1e-5, atol=1e-4)
    assert (np.asarray(d_rhs)[[1, 4]] == 0).all()   # the empty groups


def test_cpu_lowering_matches_a_loop_over_the_groups():
    before = profiler.counter_values().get("moe_lowering.ragged_dot", 0)
    check(G.grouped_matmul)
    assert profiler.counter_values()["moe_lowering.ragged_dot"] > before


def test_tpu_wrapper_matches_a_loop_over_the_groups(monkeypatch):
    """``_gmm_tpu``'s own custom_vjp (which product is transposed,
    tgmm's operand order, the slack zeroed) with megablox's kernels
    interpreted: the chip's path, minus Mosaic."""
    mb = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")

    class Interpreted:
        @staticmethod
        def gmm(*a, **kw):
            return mb.gmm(*a, interpret=True, **kw)

        @staticmethod
        def tgmm(*a, **kw):
            return mb.tgmm(*a, interpret=True, **kw)

    monkeypatch.setattr(G, "_megablox", lambda: Interpreted)
    check(lambda a, b, s: G._gmm_tpu(a, b, s, False))


def test_tile_rounded_rows_by_hand():
    # tiles of 128: group 0 rows 0..129 -> tiles 0..1 (256); group 2
    # rows 130..136 -> tile 1 again (128); group 3 rows 137..392 ->
    # tiles 1..3 (384); group 5 rows 393..421 -> tile 3 again (128)
    assert int(G.tile_rounded_rows(jnp.asarray(SIZES))) == 896
    assert int(G.tile_rounded_rows(jnp.asarray([0, 0, 0]))) == 0
    assert int(G.tile_rounded_rows(jnp.asarray([128, 128]))) == 256


@pytest.mark.parametrize("k,n", [(2048, 1024), (1024, 2048)])
def test_tiling_of_the_cells_products(k, n):
    """One k-tile and one n-tile at the cell's widths, inside the 16 MB
    of scoped VMEM double-buffered (bf16)."""
    tm, tk, tn = G._tiling(k, n)
    assert (tm, tk, tn) == (128, k, n)
    assert 2 * 2 * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn \
        < 16 << 20
