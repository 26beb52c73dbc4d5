"""Scoped-VMEM footprint audit for the pallas kernel library.

CPU-testable analog of the TPU compiler's scoped-VMEM check (16 MB on
v5e): an early on-chip run rejected the fused vocab-xent
kernel with "Scoped allocation with size 32.00M ... exceeded scoped
vmem limit by 16.00M" — its full-length ``[N, 1]`` f32 stats/outputs
are lane-padded 128x by the (8, 128) VMEM tile. That failure class is
pure geometry (block shapes x tiling x grid revisit pattern), so it is
checkable without a chip: this test intercepts each kernel's
``pl.pallas_call``, replays its geometry at the flagship benchmark
shape (transformer-base: batch 64, S=256, d_model 512, vocab 30k),
and asserts the modeled footprint fits the v5e scoped limit.

Footprint model (validated against the observed OOM, which it
reproduces at 33.6 MB for the old layout):
  - blocks are tiled to (sublane, 128) lanes with the dtype-dependent
    sublane multiple (f32 8, bf16 16, int8 32);
  - streamed input/output blocks are double-buffered (x2);
  - an OUTPUT whose index map comes BACK to a block after leaving it
    cannot be flushed incrementally — charge every distinct block
    (x2), which for a revisited full sweep is the whole padded array.
    A block revisited only on consecutive grid steps (an accumulator
    over the innermost axis: the attention backward's dk/dv over
    q-blocks) is written back when the index moves on: one block;
  - scratch is resident at full padded size (x1).

Reference analog: the jit/ kernel layer's "prove it at the target
shape" discipline (operators/jit/README.en.md) — this is the memory
half of that proof, run in CI on every change to ops/pallas/.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu import ops

V5E_SCOPED_VMEM = 16 << 20

# flagship shapes: transformer-base NMT (BASELINE.json config 3)
_B, _S, _D, _H, _V = 64, 256, 512, 8, 30000
_N = _B * _S

_SUBLANE = {4: 8, 2: 16, 1: 32}


def _padded_bytes(shape, dtype):
    itemsize = np.dtype(dtype).itemsize
    if len(shape) == 0:
        return itemsize
    dims = list(shape)
    dims[-1] = -(-dims[-1] // 128) * 128
    if len(dims) >= 2:
        m = _SUBLANE.get(itemsize, 8)
        dims[-2] = -(-dims[-2] // m) * m
    n = 1
    for d in dims:
        n *= int(d)
    return n * itemsize


def _grid_points(grid):
    pts = [()]
    for g in grid:
        pts = [p + (i,) for p in pts for i in range(int(g))]
    return pts


def _block_cost(spec, arr_shape, dtype, grid, is_output):
    """Modeled VMEM bytes for one operand's blocks."""
    shape = getattr(spec, "block_shape", None) or arr_shape
    one = _padded_bytes(shape, dtype)
    if is_output and grid:
        seen, last = set(), None
        for p in _grid_points(grid):     # row-major: last axis fastest
            cur = spec.index_map(*p)
            if cur != last and cur in seen:
                # came back to a block it had left: every distinct
                # block stays resident
                return one * 2 * len({spec.index_map(*g) for g in
                                      _grid_points(grid)})
            seen.add(cur)
            last = cur
    return one * 2  # streamed + double-buffered


class _Recorded(Exception):
    pass


def _capture_calls(fn):
    """Run fn with pl.pallas_call patched to record geometry; fake
    outputs (zeros) keep multi-call kernels (fwd+bwd) traceable
    without executing anything. Under ``jax.disable_jit``: a kernel
    wrapper that is itself jitted (the attention 1k pair) would
    otherwise keep the trace made with the fake in its cache, and hand
    zeros to the next real call of the same signature."""
    calls = []
    real = pl.pallas_call

    def fake(kernel, *, out_shape, grid=None, in_specs=None,
             out_specs=None, scratch_shapes=(), **kw):
        def runner(*args):
            calls.append(dict(out_shape=out_shape, grid=grid or (),
                              in_specs=in_specs or [],
                              out_specs=out_specs,
                              scratch_shapes=scratch_shapes,
                              args=[(a.shape, a.dtype) for a in args]))
            outs = jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), out_shape)
            return outs
        return runner

    pl.pallas_call = fake
    try:
        with jax.disable_jit():
            fn()
    finally:
        pl.pallas_call = real
    assert calls, "kernel never reached pl.pallas_call"
    return calls


def _footprint(call):
    grid = call["grid"]
    total = 0
    detail = {}
    in_specs = call["in_specs"]
    for spec, (shape, dtype) in zip(in_specs, call["args"]):
        total += _block_cost(spec, shape, dtype, grid, is_output=False)
    out_specs = call["out_specs"]
    out_shapes = jax.tree_util.tree_leaves(call["out_shape"])
    out_spec_list = (list(out_specs)
                     if isinstance(out_specs, (tuple, list))
                     else [out_specs] * len(out_shapes))
    for spec, s in zip(out_spec_list, out_shapes):
        total += _block_cost(spec, s.shape, s.dtype, grid,
                             is_output=True)
    for sc in call["scratch_shapes"]:
        shape = getattr(sc, "shape", None)
        if shape is not None:
            total += _padded_bytes(shape, getattr(sc, "dtype",
                                                  "float32"))
    detail["total"] = total
    return total


def _assert_fits(calls, label):
    for k, call in enumerate(calls):
        total = _footprint(call)
        assert total <= V5E_SCOPED_VMEM, (
            "%s call %d modeled VMEM %.1f MB exceeds the v5e scoped "
            "limit (%.0f MB): grid=%s blocks=%s"
            % (label, k, total / 2**20, V5E_SCOPED_VMEM / 2**20,
               call["grid"],
               [getattr(s, "block_shape", None)
                for s in call["in_specs"]]))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fused_xent_flagship_fits_vmem(dtype):
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.rand(_N, _D).astype(dtype))
    w = jnp.asarray((rs.rand(_D, _V) * 0.02).astype(dtype))
    lab = jnp.asarray(rs.randint(0, _V, (_N, 1)).astype("int64"))
    var = ops.get("fused_linear_xent").variants["pallas"]
    calls = _capture_calls(
        functools.partial(var, x, w, lab, epsilon=0.1))
    _assert_fits(calls, "fused_linear_xent[%s]" % dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_attention_flagship_fits_vmem(dtype):
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.rand(_B, _H, _S, _D // _H).astype(dtype))
    k = jnp.asarray(rs.rand(_B, _H, _S, _D // _H).astype(dtype))
    v = jnp.asarray(rs.rand(_B, _H, _S, _D // _H).astype(dtype))
    var = ops.get("scaled_dot_product_attention").variants["pallas"]

    def fwd_bwd():
        def loss(q_, k_, v_):
            return jnp.sum(var(q_, k_, v_, None, causal=True))
        jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    calls = _capture_calls(fwd_bwd)
    _assert_fits(calls, "scaled_dot_product_attention[%s]" % dtype)


def _1k_temp_bytes(call):
    """In-kernel score temporaries of the single-k-block attention
    kernels (ADVICE r4: streamed blocks alone under-count them): ONE
    head's [blk_q, Sk] at a time, the loop over the cell's heads being
    unrolled. q block = in_specs[1] (1, blk_q, G*Dh); k block =
    (1, Sk, G*Dh). Bytes/element: attention._1K_TEMP_BYTES (twice for
    float32 operands), and the s + b float32 addend where there is a
    bias."""
    from paddle_tpu.ops.pallas import attention as A
    blocks = [getattr(s, "block_shape", None) for s in call["in_specs"]]
    if len(blocks) < 3 or blocks[1] is None or len(blocks[1]) != 3:
        return 0
    blk_q, Sk = int(blocks[1][1]), int(blocks[2][1])
    has_bias = any(b is not None and len(b) == 4 for b in blocks)
    wide = np.dtype(call["args"][1][1]).itemsize > 2
    return blk_q * Sk * (A._1K_TEMP_BYTES * (2 if wide else 1)
                         + (4 if has_bias else 0))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rate", [0.0, 0.1])
@pytest.mark.parametrize("bias_kind", [None, "batch", "head"])
@pytest.mark.parametrize("Sq,H", [(256, _H), (512, 12), (512, _H)])
def test_attention_1k_corner_fits_vmem(dtype, rate, bias_kind, Sq, H):
    """The largest score tile of _1k_applicable, 256 x 512, as the
    largest single-k-block geometries FLAGS_sdpa_auto_flash dispatches
    by default present it, on the pair's own [B,S,H*Dh] layout:
    Sq=256/Sk=512 (one q-block), and Sq=Sk=512 at BERT-base's H=12
    and at H=8 (two q-blocks, so the backward also holds its dk/dv
    accumulators: scratch, charged by _footprint). Charges the
    lane-dense streamed blocks as the kernels declare them AND the
    in-kernel score temporaries, whatever G the model chose."""
    from paddle_tpu.ops.pallas import attention as A
    Sk, Dh = 512, 64
    assert A._1k_applicable(Sq, Sk)
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.rand(4, Sq, H * Dh).astype(dtype))
    k = jnp.asarray(rs.rand(4, Sk, H * Dh).astype(dtype))
    v = jnp.asarray(rs.rand(4, Sk, H * Dh).astype(dtype))
    var = ops.get("scaled_dot_product_attention").variants["pallas"]
    rng = jax.random.PRNGKey(0) if rate else None
    bias = None
    if bias_kind:
        bias = jnp.asarray(rs.rand(
            4, H if bias_kind == "head" else 1, Sq, Sk).astype("float32"))

    def fwd_bwd():
        def loss(q_, k_, v_):
            return jnp.sum(var(q_, k_, v_, bias, dropout_rate=rate,
                               causal=False, num_heads=H, rng=rng))
        jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    orig = A.interpret_mode
    A.interpret_mode = lambda: False  # force the TPU kernel path
    try:
        calls = _capture_calls(fwd_bwd)
    finally:
        A.interpret_mode = orig
    if Sq > A._1k_blk_q(Sq):
        assert len(calls[-1]["scratch_shapes"]) == 2   # dk, dv sums
    for n, call in enumerate(calls):
        width = call["in_specs"][1].block_shape[2]
        assert width == H * Dh or width % 128 == 0     # lane-dense
        assert call["grid"] == (4, H * Dh // width,
                                Sq // A._1k_blk_q(Sq))
        total = _footprint(call) + _1k_temp_bytes(call)
        assert total <= V5E_SCOPED_VMEM, (
            "1k[%s,rate=%s] call %d modeled VMEM %.1f MB exceeds the "
            "v5e scoped limit" % (dtype, rate, n, total / 2**20))


def test_1k_headline_geometry_pinned():
    """The two benchmark geometries' heads per cell, forward AND
    backward (bf16, dropout, a bf16 bias per batch row): the
    transformer's H=8 at Sq=Sk=256 takes the whole 512 lanes in one
    cell (G=8, chip-measured since round 4), BERT-base's H=12 at
    Sq=Sk=512 six heads (384 lanes). Any VMEM-model change that
    silently moves either fails loudly here instead."""
    from paddle_tpu.ops.pallas import attention as A
    assert A._1k_fwd_G(8, 2, 0.1, 256, 256, 64, 2) == 8
    assert A._1k_bwd_G(8, 2, 256, 256, 64, 2) == 8
    g = A._1k_bwd_G(12, 2, 512, 512, 64, 2)
    assert g == A._1k_fwd_G(12, 2, 0.1, 512, 512, 64, 2) == 6
    # without dropout the forward holds half the streams
    assert A._1k_fwd_G(12, 2, 0.0, 512, 512, 64, 2) >= g
    # a cell is lane-dense or the whole width, and divides the heads
    for H, Dh in ((8, 64), (12, 64), (6, 16), (5, 64), (16, 128),
                  (12, 32)):
        g = A._1k_bwd_G(H, 4, 512, 512, Dh, 4, True)
        assert H % g == 0 and (g == H or g * Dh % 128 == 0), (H, Dh, g)


# the blocked kernels' sites: (heads, kv heads, S, qk width, v width,
# window) of kanana2_s8k_scan's and kimi_linear_s8k_scan's latent
# attention, trinity_mini_s8k_scan's full and sliding layers, and a
# 32k-key corner; and the schedule _blocked_schedule reads off each:
# (G, blk_q, blk_k, kv_resident, fused)
_BLOCKED_SITES = {
    "mla_8k": ((32, 32, 8192, 192, 128, 0), (1, 512, 512, True, True)),
    "gqa_8k": ((32, 4, 8192, 128, 128, 0), (4, 256, 512, True, True)),
    "gqa_8k_window": ((32, 4, 8192, 128, 128, 2048),
                      (4, 256, 512, True, True)),
    "mla_32k": ((32, 32, 32768, 192, 128, 0),
                (1, 512, 512, True, False)),
}


@pytest.mark.parametrize("site", sorted(_BLOCKED_SITES))
def test_blocked_schedule_pinned_and_fits(site):
    """The blocked flash kernels' VMEM model at the three 8k cells'
    sites and at 32k keys: the schedule each shape takes is pinned
    (all three cells keep K and V of a kv head for its whole q sweep
    and run ONE backward kernel; at 32k keys K, V, dK, dV and the
    float32 sums no longer fit together and the backward splits), and
    the kernels' declared blocks and scratch, replayed by this file's
    own tiling rules with the model's count of a step's score
    temporaries, stay under what the kernels ask Mosaic for and under
    what the model itself charged."""
    from paddle_tpu.ops.pallas import attention as A
    (h, hkv, s, dqk, dv, window), want = _BLOCKED_SITES[site]
    sched = A._blocked_schedule(h, hkv, s, s, dqk, dv, 2)
    assert (sched.G, sched.blk_q, sched.blk_k, sched.kv_resident,
            sched.fused) == want
    assert sched.k_major == sched.q_major == s
    assert A._BLOCKED_VMEM_BUDGET < A._BLOCKED_VMEM_LIMIT <= 128 << 20
    # one kv head and the query heads that share it (two heads where
    # each has its own): the blocks are a cell's, whatever the count
    cut = h // hkv if h > hkv else 2
    q = jnp.zeros((1, cut, s, dqk), jnp.bfloat16)
    k = jnp.zeros((1, max(1, cut * hkv // h), s, dqk), jnp.bfloat16)
    v = jnp.zeros(k.shape[:3] + (dv,), jnp.bfloat16)
    seed = jnp.zeros((2,), jnp.float32)

    def fwd_bwd():
        out, pull = jax.vjp(
            lambda a, b, c: A._sdpa_flash(a, b, c, None, seed,
                                          dqk ** -0.5, 0.0, True,
                                          window), q, k, v)
        pull(out)

    orig = A.interpret_mode
    A.interpret_mode = lambda: False
    try:
        calls = _capture_calls(fwd_bwd)
    finally:
        A.interpret_mode = orig
    kernels = ["fwd", "fused"] if sched.fused else ["fwd", "dq", "dkv"]
    assert len(calls) == len(kernels)
    for kernel, call in zip(kernels, calls):
        temps = sched.G * sched.blk_q * sched.blk_k * (
            A._BLOCKED_FWD_TEMP_BYTES if kernel == "fwd"
            else A._BLOCKED_BWD_TEMP_BYTES)
        # less the seed's pair of words, which _footprint charges as a
        # double-buffered tile and which lives in SMEM
        total = _footprint(call) + temps - 1024
        modeled = A._blocked_bytes(
            kernel, sched.G, sched.gk, sched.blk_q, sched.blk_k, s, s,
            dqk, dv, 2)
        assert total <= modeled <= A._BLOCKED_VMEM_BUDGET, (
            "%s %s: replayed %.1f MB, modeled %.1f MB"
            % (site, kernel, total / 2**20, modeled / 2**20))


def test_layer_norm_flagship_fits_vmem():
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.rand(_N, _D).astype("float32"))
    scale = jnp.asarray(rs.rand(_D).astype("float32"))
    bias = jnp.asarray(rs.rand(_D).astype("float32"))
    var = ops.get("layer_norm").variants["pallas"]
    calls = _capture_calls(
        functools.partial(var, x, scale, bias, begin_norm_axis=1))
    _assert_fits(calls, "layer_norm")


# tier-1 wall-time headroom (ISSUE 15): ~10 s VMEM-fit sweep of the
# flagship shape; the smaller fits + the pallas train smoke stay
@pytest.mark.slow
def test_softmax_xent_flagship_fits_vmem():
    rs = np.random.RandomState(0)
    logits = jnp.asarray(rs.rand(_N, _V).astype("float32"))
    lab = jnp.asarray(rs.randint(0, _V, (_N, 1)).astype("int64"))
    var = ops.get("softmax_with_cross_entropy").variants["pallas"]
    calls = _capture_calls(functools.partial(var, logits, lab))
    _assert_fits(calls, "softmax_with_cross_entropy")


def test_fused_adam_flagship_fits_vmem():
    rs = np.random.RandomState(0)
    shape = (_D, 4 * _D)
    feed = dict(
        param=jnp.asarray(rs.rand(*shape).astype("float32")),
        grad=jnp.asarray(rs.rand(*shape).astype("float32")),
        m1=jnp.asarray(rs.rand(*shape).astype("float32")),
        m2=jnp.asarray(rs.rand(*shape).astype("float32")))
    var = ops.get("adam").variants["pallas"]
    lr = jnp.asarray([1e-3], jnp.float32)
    b1p = jnp.asarray([0.9], jnp.float32)
    b2p = jnp.asarray([0.999], jnp.float32)
    calls = _capture_calls(functools.partial(
        var, feed["param"], feed["grad"], feed["m1"], feed["m2"],
        lr, b1p, b2p))
    _assert_fits(calls, "adam")


def test_model_reproduces_round4_oom():
    """The footprint model must FLAG the exact geometry the chip
    rejected (the old [N,1] layout): two (N,1) f32 outputs revisited
    across a (nvj, ni) grid -> whole padded arrays resident."""
    bn, ni, nvj = 512, _N // 512, 15
    call = dict(
        out_shape=(jax.ShapeDtypeStruct((_N, 1), jnp.float32),
                   jax.ShapeDtypeStruct((_N, 1), jnp.float32)),
        grid=(nvj, ni),
        in_specs=[],
        out_specs=(pl.BlockSpec((bn, 1), lambda j, i: (i, 0)),
                   pl.BlockSpec((bn, 1), lambda j, i: (i, 0))),
        scratch_shapes=(),
        args=[])
    total = _footprint(call)
    # observed: "Scoped allocation with size 32.00M ... limit 16.00M"
    assert total > V5E_SCOPED_VMEM, (
        "model failed to flag the round-4 OOM geometry (%.1f MB)"
        % (total / 2**20))
