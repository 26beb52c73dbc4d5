"""The attention op with a sliding ``window`` and grouped queries (kv
heads fewer than q heads) against a plain masked softmax written out
here: values and gradients, through XLA's chain (``_sdpa_reference``)
and through the blocked flash kernels in interpret mode, with the
window smaller than, equal to and larger than the sequence; and the
blocked kernels against ``_sdpa_reference`` in every schedule their
VMEM model can choose (K and V resident or streamed in major blocks,
one fused backward kernel or the dq + dk/dv pair)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import ops
from paddle_tpu.ops.pallas import attention as A

OP = ops.get("scaled_dot_product_attention")
PATHS = {"xla": OP.fn, "blocked": OP.variants["pallas"]}


def plain(q, k, v, scale, window):
    """softmax over the keys row i may read (j <= i, and i - j < window
    where there is one), the kv heads repeated for their group."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) * scale
    i = jnp.arange(q.shape[2])[:, None]
    j = jnp.arange(k.shape[2])[None, :]
    keep = j <= i
    if window:
        keep = keep & (i - j < window)
    w = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v,
                      precision=jax.lax.Precision.HIGHEST)


# (S, q heads, kv heads, window). 2048 with a window of 300 makes the
# band shorter than the grid's k axis (3 of 4 k-blocks, 5 of 8
# q-blocks): the clamped index maps and the skipped steps run. 8 over
# 1 is the benchmark cell's group (four q heads to a cell, two cells
# to a kv head).
CASES = [(2048, 4, 1, 300), (1024, 4, 2, 1024), (1024, 2, 2, 4096),
         (1024, 8, 1, 0), (1024, 4, 4, 700)]


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("s,h,hkv,window", CASES)
def test_values_and_gradients(path, s, h, hkv, window):
    r = np.random.RandomState(s + h + window)
    dh = 16
    q = jnp.asarray(r.randn(1, h, s, dh), jnp.float32)
    k = jnp.asarray(r.randn(1, hkv, s, dh), jnp.float32)
    v = jnp.asarray(r.randn(1, hkv, s, dh), jnp.float32)
    t = jnp.asarray(r.randn(1, h, s, dh), jnp.float32)
    scale = dh ** -0.5

    def op(q_, k_, v_):
        return PATHS[path](q_, k_, v_, None, scale=scale, causal=True,
                           window=window)

    want = plain(q, k, v, scale, window)
    got = jax.jit(op)(q, k, v)
    assert got.shape == want.shape
    # float32 on both sides: what is left is the order of the sums
    np.testing.assert_allclose(got, want, rtol=5e-5, atol=1e-5)
    loss = lambda f: lambda *a: jnp.sum(f(*a) * t)      # noqa: E731
    gw = jax.grad(loss(lambda *a: plain(*a, scale, window)),
                  (0, 1, 2))(q, k, v)
    gg = jax.jit(jax.grad(loss(op), (0, 1, 2)))(q, k, v)
    for a, b, name in zip(gg, gw, "qkv"):
        assert a.shape == b.shape, name     # dk, dv at the KV heads
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-5,
                                   err_msg="d" + name)


def test_band_of_blocks():
    """Which k-blocks a q-block reads, by hand at the sliding layers'
    window (2048) over 8192 positions, at tiles of 256 x 512, and
    which of them lie wholly inside the band and run without a
    mask."""
    k_lo, k_hi, j_lo, j_hi = A._band(256, 512, 32, 16, True, 2048)
    # q-block 20 holds rows 5120..5375: keys 3073..5375, blocks 6..10
    assert (int(k_lo(20)), int(k_hi(20))) == (6, 10)
    assert (int(k_lo(0)), int(k_hi(0))) == (0, 0)
    # k-block 6 holds keys 3072..3583, read by rows 3072..5630
    assert (int(j_lo(6)), int(j_hi(6))) == (12, 21)
    assert A._n_steps(256, 512, 16, 2048) == 6      # the most any reads
    assert A._n_steps(512, 256, 32, 2048) == 11
    assert A._n_steps(256, 512, 16, 0) == 16        # full: every block
    for j in range(32):
        assert int(k_hi(j)) - int(k_lo(j)) + 1 <= 6
    for kk in range(16):
        assert int(j_hi(kk)) - int(j_lo(kk)) + 1 <= 11
    # of q-block 20's blocks 6..10, block 6 holds key 3073 (the edge
    # of row 5120's window: row 5375 reads from 3328 on) and block 10
    # the diagonal; 7..9 are inside. Every tile, by every score:
    k_first, k_end, j_first, j_end = A._inside(256, 512, True, 2048)
    assert (int(k_first(20)), int(k_end(20))) == (7, 10)
    assert (int(j_first(6)), int(j_end(6))) == (14, 20)
    rows, cols = np.arange(8192)[:, None], np.arange(8192)[None, :]
    keep = (cols <= rows) & (rows - cols < 2048)
    for j in range(32):
        for kk in range(16):
            tile = keep[j * 256:(j + 1) * 256, kk * 512:(kk + 1) * 512]
            read = int(k_lo(j)) <= kk <= int(k_hi(j))
            assert read == (int(j_lo(kk)) <= j <= int(j_hi(kk)))
            assert read or not tile.any()
            inside = read and int(k_first(j)) <= kk < int(k_end(j))
            assert inside == (int(j_first(kk)) <= j < int(j_end(kk))
                              and read)
            assert inside == bool(tile.all()), (j, kk)


def test_refusals_and_envelope():
    q = jnp.zeros((1, 6, 8, 4))
    kv = jnp.zeros((1, 4, 8, 4))
    with pytest.raises(ValueError, match="query heads"):
        OP.fn(q, kv, kv, None, causal=True)
    with pytest.raises(ValueError, match="causal"):
        OP.fn(q, q, q, None, window=4)
    # a site with no dropout takes the blocked kernels past the 1k
    # envelope only, in tiles of whole 128-lane groups (a q-block's
    # statistics travel a row of lanes)
    assert A._blocked_applicable(8192, 8192)
    assert A._blocked_applicable(1024, 1024)
    assert not A._blocked_applicable(512, 512)
    assert not A._blocked_applicable(1000, 1000)
    assert A._blocked_applicable(640, 1280)         # 128 x 5, 256 x 5
    assert A._blocked_tiles(1, 640, 1280) == (1, 128, 256)


# -- every schedule the VMEM model can choose --------------------------------

S3 = 1536       # three q-blocks of 512 (six of 256) and three k-blocks


def _budget_for(schedule, h, hkv, dqk, dv):
    """A VMEM budget under which the model takes ``schedule`` at the
    test's shape: the site's own needs, read from the model."""
    G, blk_q, blk_k = A._blocked_tiles(h // hkv, S3, S3)
    gk = G if h == hkv else 1
    need = lambda kernel, major: A._blocked_bytes(      # noqa: E731
        kernel, G, gk, blk_q, blk_k, major, S3, dqk, dv, 4)
    if schedule == "resident_split":
        return need("fused", S3) - 1
    return need("fwd", S3) - 1                          # streamed_split


@pytest.mark.parametrize("schedule", ["resident_fused", "resident_split",
                                      "streamed_split"])
@pytest.mark.parametrize("dqk,dv", [(128, 128), (192, 128)])
@pytest.mark.parametrize("h,hkv", [(1, 1), (8, 1)])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 700),
                                           (False, 0)])
def test_every_schedule_matches_the_reference(monkeypatch, fresh_traces,
                                              schedule, dqk, dv, h, hkv,
                                              causal, window):
    """Output and the three gradients against ``_sdpa_reference`` at
    the model's own tiles (512 x 512, or four heads' 256 x 512 where
    eight share a kv head) over 1,536 positions, so that tiles inside
    the band, tiles an edge crosses (the diagonal; the trailing edge
    of a window of 700, which falls inside a block) and tiles outside
    it all occur, in each schedule the VMEM model can choose, steered
    by its budget alone."""
    from paddle_tpu import profiler
    if schedule != "resident_fused":    # which the shape takes by itself
        monkeypatch.setattr(A, "_BLOCKED_VMEM_BUDGET",
                            _budget_for(schedule, h, hkv, dqk, dv))
    sched = A._blocked_schedule(h, hkv, S3, S3, dqk, dv, 4)
    assert (sched.kv_resident, sched.fused) == {
        "resident_fused": (True, True), "resident_split": (True, False),
        "streamed_split": (False, False)}[schedule]
    assert S3 // sched.blk_q >= 3 and S3 // sched.blk_k >= 3
    r = np.random.RandomState(h + dqk + window)
    mk = lambda heads, d: jnp.asarray(                  # noqa: E731
        r.randn(1, heads, S3, d), jnp.float32)
    q, k, v, t = mk(h, dqk), mk(hkv, dqk), mk(hkv, dv), mk(h, dv)
    kw = dict(scale=dqk ** -0.5, causal=causal, window=window)
    ref = lambda *a: A._sdpa_reference(*a, None, **kw)   # noqa: E731
    pal = lambda *a: A.sdpa_pallas(*a, None, is_test=True,  # noqa: E731
                                   **kw)
    before = profiler.counter_values()
    got = jax.jit(pal)(q, k, v)
    np.testing.assert_allclose(got, ref(q, k, v), rtol=5e-5, atol=1e-5)
    loss = lambda f: lambda *a: jnp.sum(f(*a) * t)      # noqa: E731
    gw = jax.grad(loss(ref), (0, 1, 2))(q, k, v)
    gg = jax.jit(jax.grad(loss(pal), (0, 1, 2)))(q, k, v)
    for a, b, name in zip(gg, gw, "qkv"):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-5,
                                   err_msg="d" + name)
    # the counters name the schedule taken, the other listed with 0
    after = profiler.counter_values()
    moved = {n: after[n] - before.get(n, 0.0) for n in (
        "flash_schedule.kv_resident", "flash_schedule.kv_streamed",
        "flash_backward.fused", "flash_backward.split")}
    assert (moved["flash_schedule.kv_resident"] > 0) == sched.kv_resident
    assert (moved["flash_schedule.kv_streamed"] > 0) \
        == (not sched.kv_resident)
    assert (moved["flash_backward.fused"] > 0) == sched.fused
    assert (moved["flash_backward.split"] > 0) == (not sched.fused)

