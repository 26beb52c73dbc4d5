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
    """The TPU lowering of the product and its pullback (which product
    is transposed, tgmm's operand order and ``existing_out``, the slack
    zeroed) with megablox's kernels interpreted: the chip's path,
    minus Mosaic."""
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
    monkeypatch.setattr(G, "interpret_mode", lambda: False)
    check(G.grouped_matmul)
    check_pullback_adds()


def check_pullback_adds():
    """The matrices' gradient is ADDED to the float32 sum handed in:
    two halves of the rows, one after the other, give the whole."""
    lhs, rhs, sizes, t = operands(1)
    half = M // 2
    ends = np.cumsum(SIZES)
    starts = ends - SIZES
    acc = jnp.zeros(rhs.shape, jnp.float32)
    d_lhs = []
    for lo in (0, half):
        part = jnp.asarray(np.clip(ends, lo, lo + half)
                           - np.clip(starts, lo, lo + half), jnp.int32)
        d, acc = jax.jit(G.grouped_matmul_pullback)(
            lhs[lo:lo + half], rhs, part, t[lo:lo + half], acc)
        d_lhs.append(d)
    whole = jax.jit(G.grouped_matmul_pullback)(
        lhs, rhs, sizes, t, jnp.zeros(rhs.shape, jnp.float32))
    np.testing.assert_allclose(jnp.concatenate(d_lhs), whole[0],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(acc, whole[1], rtol=1e-5, atol=1e-4)
    assert acc.dtype == jnp.float32


def test_cpu_pullback_adds_to_the_sum_handed_in():
    check_pullback_adds()


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
