"""Every registered Pallas variant, COMPILED by Mosaic on the chip at
the shapes transformer-base presents (B=64, H=8, S=256, Dh=64,
N=B*S=16384 rows, d_model 512, vocab 30,000), BERT-base's S=512
attention (b=56, h=12: two q-blocks to a cell; the flash 1k pair on
[b, s, h * Dh] as the models hand it over), S=1024 for the blocked
flash path and the per-hop shapes for ops/pallas/ring.py — each against
its jnp reference at the tolerance tests/test_pallas_kernels.py uses
for that kernel (bf16 inputs: bf16 tolerance).

The CPU suite only ever runs these kernels with ``interpret=True`` and
cannot reach the in-kernel PRNG at all; these checks need the real
backend and skip anywhere else:

    PADDLE_TPU_CHIP_TESTS=1 python -m pytest tests/test_chip_kernels.py -m chip
"""

import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import ops
from paddle_tpu.ops.pallas import attention as A
from paddle_tpu.ops.pallas import ring as R

pytestmark = [
    pytest.mark.chip,
    pytest.mark.skipif(jax.default_backend() != "tpu",
                       reason="needs a TPU: Mosaic compiles only there"),
]

B, H, S, DH = 64, 8, 256, 64       # transformer-base attention
BERT = (56, 12, 512)               # bert_base_s512_scan's b, h, S
N, D, V = B * S, 512, 30000        # rows, d_model, vocab
F32 = dict(rtol=5e-5, atol=1e-5)
F32_GRAD = dict(rtol=5e-4, atol=5e-5)
BF16 = dict(rtol=2e-2, atol=2e-2)


def _exact_if_f32(dtype):
    """f32 comparisons want exact f32 matmuls in kernel and reference
    alike; bf16 runs as training does — default MXU precision (Mosaic
    refuses fp32 contract precision on bf16 operands)."""
    if jnp.dtype(dtype) == jnp.float32:
        return jax.default_matmul_precision("highest")
    return contextlib.nullcontext()


def _close(got, want, rtol, atol, atol_of_max=False):
    """``atol_of_max``: atol is a share of each array's largest
    magnitude — for gradients, whose small entries are differences of
    large terms."""
    got = jax.tree_util.tree_leaves(got)
    want = jax.tree_util.tree_leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        w = np.asarray(w, np.float32)
        np.testing.assert_allclose(
            np.asarray(g, np.float32), w, rtol=rtol,
            atol=atol * (np.abs(w).max() if atol_of_max else 1.0))


def _qkv(seed, b, h, sq, sk, dtype, heads_last=False):
    """[b, h, s, DH], or [b, s, h * DH] with ``heads_last``."""
    r = np.random.RandomState(seed)
    mk = lambda s: jnp.asarray(  # noqa: E731
        r.randn(*((b, s, h * DH) if heads_last else (b, h, s, DH)))
        .astype(np.float32) * 0.5, dtype)
    return mk(sq), mk(sk), mk(sk)


def _pad_bias(seed, b, sq, sk):
    """The models' additive pad mask: 0 / -1e9 per key, [b, 1, sq, sk]."""
    r = np.random.RandomState(seed)
    keep = r.rand(b, 1, 1, sk) > 0.15
    keep[..., 0] = True
    return jnp.broadcast_to(
        jnp.asarray(np.where(keep, 0.0, -1e9).astype(np.float32)),
        (b, 1, sq, sk))


def _sdpa_fwd_and_grads(q, k, v, bias, causal, fwd_tol, grad_tol,
                        num_heads=0):
    scale = DH ** -0.5
    kw = dict(scale=scale, causal=causal, num_heads=num_heads)

    def ref(q_, k_, v_):
        return A._sdpa_reference(q_, k_, v_, bias, **kw)

    def pal(q_, k_, v_):
        return A.sdpa_pallas(q_, k_, v_, bias, is_test=True, **kw)

    _close(jax.jit(pal)(q, k, v), jax.jit(ref)(q, k, v), **fwd_tol)
    loss = lambda f: lambda *a: jnp.sum(  # noqa: E731
        jnp.square(f(*a).astype(jnp.float32)))
    gp = jax.jit(jax.grad(loss(pal), (0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss(ref), (0, 1, 2)))(q, k, v)
    _close(gp, gr, **grad_tol)


# -- the default path: single-k-block flash pair ---------------------------

@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("geom", [(B, H, S), BERT])
@pytest.mark.parametrize("dtype,fwd_tol,grad_tol", [
    (jnp.bfloat16, BF16, dict(rtol=5e-2, atol=5e-2)),
    (jnp.float32, F32, F32_GRAD)])
def test_flash_1k_matches_reference(dtype, fwd_tol, grad_tol, geom,
                                    causal):
    """The pair where the models call it: q, k, v [b, s, h * DH]."""
    b, h, s = geom
    assert A._1k_applicable(s, s)
    if dtype == jnp.float32:
        b = 8
    q, k, v = _qkv(0, b, h, s, s, dtype, heads_last=True)
    with _exact_if_f32(dtype):
        _sdpa_fwd_and_grads(q, k, v, _pad_bias(1, b, s, s), causal,
                            fwd_tol, grad_tol, h)
        _sdpa_fwd_and_grads(q, k, v, None, causal, fwd_tol, grad_tol, h)


@pytest.mark.parametrize("geom", [(B, H, S), BERT])
def test_flash_1k_behind_a_rank4_caller(geom):
    """A caller with heads leading is adapted by the lowering's own
    transposes and runs the same pair; a per-head bias with it."""
    b, h, s = geom
    q, k, v = _qkv(12, b, h, s, s, jnp.bfloat16)
    r = np.random.RandomState(13)
    keep = r.rand(1, h, s, s) > 0.15
    keep[..., 0] = True
    per_head = jnp.asarray(np.where(keep, 0.0, -1e9).astype(np.float32))
    grad_tol = dict(rtol=5e-2, atol=5e-2)
    _sdpa_fwd_and_grads(q, k, v, _pad_bias(14, b, s, s), True, BF16,
                        grad_tol)
    _sdpa_fwd_and_grads(q[:4], k[:4], v[:4], per_head, False, BF16,
                        grad_tol)


def _dropout_checks(sq, sk, b, h=H, bias=None, heads_last=False):
    """What can be said about in-kernel dropout without the mask:
    deterministic in the seed, different across seeds, grid cells,
    heads and q-blocks, the kept share near 1-rate, and the backward
    regenerating exactly the forward's mask — out is linear in V,
    out = A(mask) V, so dV must be A(mask)^T dOut with the SAME mask.
    ``heads_last``: the 1k pair's layout, [b, s, h * DH]."""
    rate, scale = 0.1, DH ** -0.5
    q, k, v = _qkv(2, b, h, sq, sk, jnp.bfloat16, heads_last)
    seed = jnp.asarray([1234, 0], jnp.float32)

    def fwd(q_, k_, v_, s=seed):
        return A._sdpa_flash(q_, k_, v_, bias, s, scale, rate, False, 0,
                             h if heads_last else 0)

    out = jax.jit(fwd)(q, k, v)
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())
    _close(jax.jit(fwd)(q, k, v), out, rtol=0, atol=0)
    other = jax.jit(lambda *a: fwd(
        *a, s=jnp.asarray([99, 0], jnp.float32)))(q, k, v)
    assert not np.array_equal(np.asarray(out, np.float32),
                              np.asarray(other, np.float32))

    # uniform probabilities over ones: each output is kept/(sk*(1-rate))
    zeros = jnp.zeros_like(q)
    ones = jnp.ones_like(v)
    share = np.asarray(jax.jit(fwd)(zeros, jnp.zeros_like(k), ones),
                       np.float32) * (1.0 - rate)
    # one lane of each head: [b, h, sq]
    share = share[..., ::DH].transpose(0, 2, 1) if heads_last \
        else share[..., 0]
    assert abs(share.mean() - (1.0 - rate)) < 5e-3, share.mean()
    assert share.std() > 0.0
    cells = share.reshape(-1, sq)
    assert not np.array_equal(cells[0], cells[-1])
    assert not np.array_equal(cells[0], cells[1])      # two heads
    if sq > 256:                   # q-blocks of one cell draw apart
        assert not np.array_equal(cells[0][:256], cells[0][256:512])

    # adjoint identity without cancellation: with cotangent A v',
    # <dV, v'> = <A^T A v', v'> = |A v'|^2
    r = np.random.RandomState(3)
    v2 = jnp.asarray(r.randn(*v.shape).astype(np.float32), jnp.bfloat16)
    out2 = jax.jit(fwd)(q, k, v2)
    _, pull = jax.vjp(fwd, q, k, v)
    dq, dk, dv = jax.jit(pull)(out2)
    for d in (dq, dk, dv):
        assert bool(jnp.isfinite(d.astype(jnp.float32)).all())
    lhs = float(jnp.vdot(dv.astype(jnp.float32), v2.astype(jnp.float32)))
    rhs = float(jnp.sum(jnp.square(out2.astype(jnp.float32))))
    assert rhs > 0 and abs(lhs - rhs) <= 2e-2 * rhs, (lhs, rhs)


def test_flash_1k_dropout_prng():
    """The exact configuration the model compiles 18 times: bf16,
    b64 h8 S=256 on [b, s, 512], dropout 0.1, in-kernel pltpu PRNG:
    one seed a cell, its eight heads drawing one after another."""
    _dropout_checks(S, S, B, heads_last=True)


def test_flash_1k_dropout_prng_bert_s512():
    """BERT-base's 12 sites: bf16, b56 h12 S=512, the pad bias, two
    q-blocks to a cell — the adjoint identity holds only if the
    backward regenerates the forward's mask in EACH q-block (same G,
    same blk_q, same (cell, q-block) seed)."""
    b, h, s = BERT
    assert A._1k_applicable(s, s) and s > A._1k_blk_q(s)
    _dropout_checks(s, s, b, h, _pad_bias(11, b, s, s), heads_last=True)


# -- blocked online-softmax path (S=1024) ----------------------------------

@pytest.mark.parametrize("dtype,fwd_tol,grad_tol", [
    (jnp.bfloat16, BF16, dict(rtol=5e-2, atol=5e-2)),
    (jnp.float32, F32, F32_GRAD)])
def test_flash_blocked_matches_reference(dtype, fwd_tol, grad_tol):
    s = 1024
    assert not A._1k_applicable(s, s)
    b = 16 if dtype == jnp.bfloat16 else 2
    q, k, v = _qkv(4, b, H, s, s, dtype)
    with _exact_if_f32(dtype):
        _sdpa_fwd_and_grads(q, k, v, _pad_bias(5, b, s, s), True,
                            fwd_tol, grad_tol)
        _sdpa_fwd_and_grads(q, k, v, None, False, fwd_tol, grad_tol)


def test_flash_blocked_dropout_prng():
    _dropout_checks(1024, 1024, 4)


# -- blocked path, sliding window and grouped queries (S=8192) --------------

@pytest.mark.parametrize("window", [2048, 0])
def test_flash_blocked_window_gqa_matches_reference(window):
    """trinity_mini_s8k_scan's site (32 q heads over 4 kv heads of 128,
    S=8192, bf16, causal, a 2048 window in the sliding layers) cut to
    ONE kv head and its 8 q heads, so that the reference's
    [1,8,8192,8192] float32 scores fit beside it. The gradient's
    tolerance is a share of each array's largest entry: dk and dv sum
    8 heads x up to 8192 rows of bf16 products, and the reference
    keeps its probabilities in bf16 (row 0, which reads one key, has
    a dq of exactly nought in the kernel and of 1.5% of the largest
    entry in the reference: my chip run, PR 28, call 3)."""
    dh, s = 128, 8192
    r = np.random.RandomState(20 + window)
    mk = lambda h: jnp.asarray(                      # noqa: E731
        r.randn(1, h, s, dh).astype(np.float32) * 0.5, jnp.bfloat16)
    q, k, v = mk(8), mk(1), mk(1)
    assert A._blocked_applicable(s, s) \
        and not A._takes_1k(8, 1, s, s, window)
    kw = dict(scale=dh ** -0.5, causal=True, window=window)
    ref = lambda *a: A._sdpa_reference(*a, None, **kw)   # noqa: E731
    pal = lambda *a: A.sdpa_pallas(*a, None, is_test=True,  # noqa: E731
                                   **kw)
    _close(jax.jit(pal)(q, k, v), jax.jit(ref)(q, k, v), **BF16)
    loss = lambda f: lambda *a: jnp.sum(             # noqa: E731
        jnp.square(f(*a).astype(jnp.float32)))
    _close(jax.jit(jax.grad(loss(pal), (0, 1, 2)))(q, k, v),
           jax.jit(jax.grad(loss(ref), (0, 1, 2)))(q, k, v),
           rtol=5e-2, atol=3e-2, atol_of_max=True)


# -- blocked path, keys wider than values (S=8192) --------------------------

def test_flash_blocked_mla_widths_matches_reference():
    """kimi_linear_s8k_scan's latent-attention site (32 heads, queries
    and keys 192 wide -- 128 lanes a head's own, 64 shared by every
    head --, values 128 wide, S=8192, bf16, causal, no window) cut to 4
    heads so that the reference's [1,4,8192,8192] float32 scores fit
    beside it. The same tolerances as the windowed GQA site above."""
    s = 8192
    r = np.random.RandomState(31)
    mk = lambda h, d: jnp.asarray(                    # noqa: E731
        r.randn(1, h, s, d).astype(np.float32) * 0.5, jnp.bfloat16)
    q, own, v = mk(4, 192), mk(4, 128), mk(4, 128)
    k = jnp.concatenate([own, jnp.broadcast_to(mk(1, 64),
                                               (1, 4, s, 64))], -1)
    assert A._blocked_applicable(s, s) and not A._same_widths(k, v)
    kw = dict(scale=192 ** -0.5, causal=True)
    ref = lambda *a: A._sdpa_reference(*a, None, **kw)   # noqa: E731
    pal = lambda *a: A.sdpa_pallas(*a, None, is_test=True,  # noqa: E731
                                   **kw)
    out = jax.jit(pal)(q, k, v)
    assert out.shape == (1, 4, s, 128)
    _close(out, jax.jit(ref)(q, k, v), **BF16)
    loss = lambda f: lambda *a: jnp.sum(             # noqa: E731
        jnp.square(f(*a).astype(jnp.float32)))
    _close(jax.jit(jax.grad(loss(pal), (0, 1, 2)))(q, k, v),
           jax.jit(jax.grad(loss(ref), (0, 1, 2)))(q, k, v),
           rtol=5e-2, atol=3e-2, atol_of_max=True)


def test_kda_kernels_match_the_xla_form():
    """kimi_linear_s8k_scan's KDA site (32 heads of 128, S=8192, q, k,
    v in bf16, the log decay in float32 with the model's spread of
    gates, so some channels pass the floor inside a chunk): the Mosaic
    kernels against the XLA chunked form, output, floor hits and all
    five gradients, at the operands' own tolerance."""
    from paddle_tpu.ops import kda_ops as K
    b, s, h, d = 1, 8192, 32, 128
    r = np.random.RandomState(33)
    mk = lambda *sh: jnp.asarray(r.randn(*sh), jnp.bfloat16)  # noqa: E731
    q, k, v, ct = (mk(b, s, h * d) for _ in range(4))
    a = np.repeat(np.exp(r.uniform(0, 2.77, h)), d)
    step = np.exp(r.uniform(np.log(1e-3), np.log(0.1), h * d))
    x = r.randn(b, s, h * d) * 0.5 + np.log(np.expm1(step))
    g = jnp.asarray(-a * np.log1p(np.exp(x)), jnp.float32)
    beta = jnp.asarray(1 / (1 + np.exp(-r.randn(b, s, h))), jnp.bfloat16)
    args, scale = (q, k, v, g, beta), d ** -0.5
    assert K.lowering(q, v, beta) == "pallas_chunked"

    def site(*a):
        (out, low), pull = jax.vjp(lambda *x: K.kda_chunked(*x, scale),
                                   *a)
        return out, low, pull((ct, jnp.zeros_like(low)))
    out, low, grads = jax.jit(site)(*args)
    want, low_x = K._kda_forward(*args, scale)
    grads_x = K._kda_backward(*args, ct, scale)
    assert float(low) == float(low_x) > 0
    _close(out, want, rtol=2e-2, atol=2e-2, atol_of_max=True)
    _close(grads, grads_x, rtol=5e-2, atol=2e-2, atol_of_max=True)


def test_ssd_kernels_match_the_xla_form():
    """nemotron3_nano_s8k_scan's SSD site (64 heads of 64 over 8 groups
    of B / C at state 128, chunks of 128, S=8192, x, B, C in bf16, the
    step and its bias float32 at the model's start: A = 1..64 and steps
    0.001..0.1, so the strong heads pass the floor inside a chunk): the
    Mosaic kernels of ops/pallas/ssd.py against the XLA chunked form,
    output, floor hits and all seven gradients, at the KDA core's
    tolerances."""
    from paddle_tpu.models import nemotron_h as NH
    from paddle_tpu.ops import ssd_ops as S
    b, s, h, p, g, n = 1, 8192, 64, 64, 8, 128
    r = np.random.RandomState(39)
    mk = lambda *sh: jnp.asarray(r.randn(*sh) * 0.5,  # noqa: E731
                                 jnp.bfloat16)
    x, ct, bm, cm = (mk(b, s, w) for w in (h * p, h * p, g * n, g * n))
    a_log, dt_bias = (jnp.asarray(t)
                      for t in NH.ssd_start(NH.NemotronHConfig()))
    dt = jnp.asarray(r.randn(b, s, h) * 0.5, jnp.float32)
    d = jnp.asarray(r.uniform(0.5, 1.5, h), jnp.float32)
    args = (x, dt, a_log, bm, cm, d, dt_bias)
    assert S.lowering(x, a_log, bm, g, 128) == "pallas_chunked"

    def site(*a):
        (out, low), pull = jax.vjp(lambda *t: S.ssd_chunked(*t, g, 128),
                                   *a)
        return out, low, pull((ct, jnp.zeros_like(low)))
    out, low, grads = jax.jit(site)(*args)
    want, low_x = S._ssd_forward(*args, g, 128)
    grads_x = S._ssd_backward(*args, ct, g, 128)
    # the two cumulative sums may round an element at -80 apart
    assert abs(float(low) - float(low_x)) <= 1e-4 * float(low_x)
    assert float(low_x) > 0
    _close(out, want, rtol=2e-2, atol=2e-2, atol_of_max=True)
    _close(grads, grads_x, rtol=5e-2, atol=2e-2, atol_of_max=True)


@pytest.mark.parametrize("op", ["short_conv", "gated_rms_norm"])
def test_kda_small_kernels_match_plain_autodiff(op):
    """kimi_linear_s8k_scan's short convolution (4 taps) and gated norm
    (32 heads of 128) at ``[1,8192,4096]`` in bf16: the Mosaic kernels
    of ops/pallas/kda_small.py through the op, against ``jax.vjp`` of
    the op's definition, the output and every gradient."""
    from paddle_tpu.ops import kda_ops as K
    r = np.random.RandomState(37)
    mk = lambda *sh: jnp.asarray(r.randn(*sh), jnp.bfloat16)  # noqa: E731
    x, dy = mk(1, 8192, 4096), mk(1, 8192, 4096)
    if op == "short_conv":
        fn, definition, args = K.short_conv, K.short_conv_definition, \
            (x, mk(4096, 4) * 0.3)
        assert K._conv_lowering(*args) == "pallas"
    else:
        fn = lambda *a: K.gated_rms_norm(*a, epsilon=1e-5)  # noqa: E731
        definition = lambda *a: K.gated_rms_norm_definition(  # noqa: E731
            *a, 1e-5)
        args = (x, mk(1, 8192, 4096),
                jnp.asarray(r.uniform(0.5, 1.5, (128,)), jnp.bfloat16))
        assert K._norm_lowering(x, args[2]) == "pallas"

    def site(f):
        def run(*a):
            out, pull = jax.vjp(f, *a)
            return (out,) + pull(dy)
        return jax.jit(run)(*args)
    # one bf16 ulp of an element; the sums over 8,192 positions (dw,
    # dscale) in another order
    _close(site(fn), site(definition), rtol=2.0 ** -7, atol=1e-3,
           atol_of_max=True)


# -- grouped matrix product (the held experts' three products) -------------

@pytest.mark.parametrize("k,n", [(2048, 1024), (1024, 2048)])
def test_grouped_matmul_matches_ragged_dot(k, n):
    """ops/pallas/grouped_matmul.py at the cell's widths: 8 groups in a
    buffer of 8192 rows, one group empty, one boundary inside a tile,
    slack after the last; the Mosaic kernels (megablox gmm / tgmm)
    against lax.ragged_dot, values and both gradients, the slack and
    the empty group's gradient exact zeros."""
    from paddle_tpu.ops.pallas import grouped_matmul as G
    sizes = [700, 0, 129, 1024, 37, 512, 900, 450]
    m, e = 8192, len(sizes)
    r = np.random.RandomState(k)
    lhs = jnp.asarray(r.randn(m, k).astype(np.float32) * 0.5,
                      jnp.bfloat16)
    rhs = jnp.asarray(r.randn(e, k, n).astype(np.float32) * k ** -0.5,
                      jnp.bfloat16)
    t = jnp.asarray(r.randn(m, n).astype(np.float32), jnp.bfloat16)
    gs = jnp.asarray(sizes, jnp.int32)

    def ref(a, b):
        out = jax.lax.ragged_dot(a, b, gs,
                                 preferred_element_type=jnp.float32)
        return G._zero_slack(out.astype(a.dtype), gs)

    pal = lambda a, b: G.grouped_matmul(a, b, gs)    # noqa: E731
    got = jax.jit(pal)(lhs, rhs)
    _close(got, jax.jit(ref)(lhs, rhs), **BF16)
    assert not np.asarray(got[sum(sizes):], np.float32).any()
    loss = lambda f: lambda a, b: jnp.sum(           # noqa: E731
        (f(a, b) * t).astype(jnp.float32))
    gp = jax.jit(jax.grad(loss(pal), (0, 1)))(lhs, rhs)
    gr = jax.jit(jax.grad(loss(ref), (0, 1)))(lhs, rhs)
    # ragged_dot leaves the slack rows of ITS lhs gradient as it found
    # them (NaN and stale numbers: my chip run, PR 28, call 3): the
    # real rows are compared, the wrapper's slack is exact zeros
    live = sum(sizes)
    _close((gp[0][:live], gp[1]), (gr[0][:live], gr[1]), rtol=5e-2,
           atol=1e-2, atol_of_max=True)
    assert not np.asarray(gp[0][live:], np.float32).any()
    assert not np.asarray(gp[1][1], np.float32).any()


# -- ring attention per-hop kernels ----------------------------------------

@pytest.mark.parametrize("dtype,tol", [
    (jnp.bfloat16, dict(rtol=5e-2, atol=5e-2)),
    (jnp.float32, dict(rtol=5e-4, atol=2e-4))])
@pytest.mark.parametrize("causal", [False, True])
def test_ring_hop_matches_reference(dtype, tol, causal):
    """One hop of S=1024 over sp=4: 256x256 blocks with global offsets
    (q shard 2, k shard 1 — fully visible under the causal mask — and
    the diagonal hop)."""
    b, sq = 2, 256
    assert R.applicable(b, H, sq, sq, DH, jnp.dtype(dtype).itemsize)
    q, k, v = _qkv(6, b, H, sq, sq, dtype)
    scale = DH ** -0.5
    hops = ((2 * sq, sq), (sq, sq))
    with _exact_if_f32(dtype):
        for q_off, k_off in hops:
            _ring_hop(q, k, v, q_off, k_off, scale, causal, dtype, tol)


def _ring_hop(q, k, v, q_off, k_off, scale, causal, dtype, tol):
    """One hop equals plain attention over that block pair: partials
    against jnp, gradients against jnp's vjp fed the same lse/delta."""
    b, sq = q.shape[0], q.shape[2]

    def ref_parts(q_, k_, v_):
        s = jnp.einsum("bhqd,bhkd->bhqk", q_.astype(jnp.float32),
                       k_.astype(jnp.float32)) * scale
        if causal:
            qp = q_off + jnp.arange(sq)[:, None]
            kp = k_off + jnp.arange(sq)[None, :]
            s = jnp.where(kp <= qp, s, -1e30)
        m = jnp.max(s, -1)
        p = jnp.exp(s - m[..., None])
        return (jnp.einsum("bhqk,bhkd->bhqd", p,
                           v_.astype(jnp.float32)), m,
                jnp.sum(p, -1))

    pv, m, l = jax.jit(
        lambda *a: R.fwd_block(*a, q_off, k_off, scale, causal))(
            q, k, v)
    pv_r, m_r, l_r = jax.jit(ref_parts)(q, k, v)
    _close((pv, m, l), (pv_r, m_r, l_r), **tol)

    def out_of(q_, k_, v_):
        pv_, _m, l_ = ref_parts(q_, k_, v_)
        return pv_ / l_[..., None]

    r = np.random.RandomState(7)
    do = jnp.asarray(r.randn(b, H, sq, DH).astype(np.float32), dtype)
    out, pull = jax.vjp(out_of, q, k, v)
    want = pull(do.astype(jnp.float32))
    lse = m_r + jnp.log(l_r)
    delta = jnp.sum(do.astype(jnp.float32) * out, -1)
    got = jax.jit(lambda *a: R.bwd_block(
        *a, q_off, k_off, scale, causal))(q, k, v, do, lse, delta)
    _close(got, [w.astype(jnp.float32) for w in want], **tol)


@pytest.mark.skipif(jax.device_count() < 4, reason="needs four chips")
@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_flash_over_four_chips(causal):
    """The hop kernels inside the real ring: S=1024 over an sp=4 mesh,
    ppermute over ICI, against full attention."""
    from paddle_tpu.parallel import mesh as mesh_lib
    from paddle_tpu.parallel.ring_attention import ring_attention
    from paddle_tpu.parallel.ulysses import _full_attention

    mesh = mesh_lib.make_mesh({"sp": 4}, jax.devices()[:4])
    q, k, v = _qkv(8, 2, H, 1024, 1024, jnp.float32)
    scale = DH ** -0.5
    loss = lambda f: lambda *a: jnp.sum(jnp.square(f(*a)))  # noqa: E731
    with _exact_if_f32(jnp.float32):
        want = _full_attention(q, k, v, scale, causal)
        got = ring_attention(q, k, v, mesh=mesh, scale=scale,
                             causal=causal, use_flash=True)
        _close(got, want, **F32_GRAD)
        gw = jax.grad(loss(lambda *a: _full_attention(
            *a, scale, causal)), (0, 1, 2))(q, k, v)
        gg = jax.grad(loss(lambda *a: ring_attention(
            *a, mesh=mesh, scale=scale, causal=causal, use_flash=True)),
            (0, 1, 2))(q, k, v)
        _close(gg, gw, rtol=2e-3, atol=2e-4)


@pytest.mark.skipif(jax.device_count() < 4, reason="needs four chips")
def test_flash_dropout_over_dp_mesh_equals_one_chip():
    """Under a dp=4 mesh the kernel runs per shard (shard_map) with
    its dropout cells numbered where one chip numbers them: the same
    masks, so outputs and gradients equal the one-chip call's."""
    from paddle_tpu.parallel import mesh as mesh_lib

    q, k, v = _qkv(9, B, H, S, S, jnp.bfloat16, heads_last=True)
    bias = _pad_bias(10, B, 1, S)
    key = jax.random.key(5)

    def loss(q_, k_, v_):
        out = A.sdpa_pallas(q_, k_, v_, bias, scale=DH ** -0.5,
                            dropout_rate=0.1, num_heads=H, rng=key)
        return jnp.sum(jnp.square(out.astype(jnp.float32))), out

    fn = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)
    want = jax.jit(fn)(q, k, v)
    mesh = mesh_lib.make_mesh({"dp": 4}, jax.devices()[:4])
    with mesh_lib.mesh_guard(mesh):
        got = jax.jit(fn)(q, k, v)
    _close(got[0][1], want[0][1], rtol=0, atol=0)      # out, bitwise
    _close(got[1], want[1], rtol=0, atol=0)            # dq, dk, dv
    _close(got[0][0], want[0][0], rtol=1e-5, atol=0)   # the reduction


# -- the other registered variants -----------------------------------------

def _cmp_variant(op_type, args, kwargs, **tol):
    opdef = ops.get(op_type)
    _close(jax.jit(lambda *a: opdef.variants["pallas"](*a, **kwargs))(
        *args), jax.jit(lambda *a: opdef.fn(*a, **kwargs))(*args), **tol)


@pytest.mark.parametrize("dtype,tol,gtol", [
    (jnp.bfloat16, BF16, dict(rtol=5e-2, atol=5e-3, atol_of_max=True)),
    (jnp.float32, dict(rtol=1e-4, atol=1e-5),
     dict(rtol=1e-3, atol=1e-5, atol_of_max=True))])
def test_layer_norm_variant(dtype, tol, gtol):
    r = np.random.RandomState(10)
    x = jnp.asarray(r.randn(B, S, D).astype(np.float32), dtype)
    scale = jnp.asarray(r.rand(D).astype(np.float32) + 0.5)
    bias = jnp.asarray(r.randn(D).astype(np.float32))
    kw = {"epsilon": 1e-5, "begin_norm_axis": 2}
    _cmp_variant("layer_norm", (x, scale, bias), kw, **tol)
    opdef = ops.get("layer_norm")
    loss = lambda f: lambda *a: jnp.sum(  # noqa: E731
        jnp.square(f(*a, **kw)[0].astype(jnp.float32)))
    _close(jax.jit(jax.grad(loss(opdef.variants["pallas"]), (0, 1, 2)))(
        x, scale, bias),
        jax.jit(jax.grad(loss(opdef.fn), (0, 1, 2)))(x, scale, bias),
        **gtol)


def test_softmax_xent_variant():
    r = np.random.RandomState(11)
    logits = jnp.asarray(r.randn(N, V).astype(np.float32))
    label = jnp.asarray(r.randint(0, V, (N, 1)).astype(np.int32))
    _cmp_variant("softmax_with_cross_entropy", (logits, label), {},
                 rtol=1e-5, atol=1e-6)
    opdef = ops.get("softmax_with_cross_entropy")
    gp = jax.jit(jax.grad(lambda lg: jnp.sum(
        opdef.variants["pallas"](lg, label)[1])))(logits)
    gr = jax.jit(jax.grad(lambda lg: jnp.sum(
        opdef.fn(lg, label)[1])))(logits)
    _close(gp, gr, rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("dtype,tol,gtol", [
    (jnp.bfloat16, dict(rtol=5e-2, atol=5e-2),
     dict(rtol=5e-2, atol=5e-2)),
    (jnp.float32, dict(rtol=2e-5, atol=2e-5),
     dict(rtol=2e-4, atol=2e-5))])
def test_fused_linear_xent_variant(dtype, tol, gtol):
    r = np.random.RandomState(12)
    x = jnp.asarray(r.randn(N, D).astype(np.float32) * 0.5, dtype)
    w = jnp.asarray(r.randn(D, V).astype(np.float32) * 0.05, dtype)
    lab = jnp.asarray(r.randint(0, V, (N, 1)).astype(np.int32))
    kw = {"epsilon": 0.1}
    opdef = ops.get("fused_linear_xent")
    loss = lambda f: lambda a, b: jnp.mean(f(a, b, lab, **kw))  # noqa
    with _exact_if_f32(dtype):
        _cmp_variant("fused_linear_xent", (x, w, lab), kw, **tol)
        _close(jax.jit(jax.grad(loss(opdef.variants["pallas"]),
                                (0, 1)))(x, w),
               jax.jit(jax.grad(loss(opdef.fn), (0, 1)))(x, w), **gtol)


@pytest.mark.parametrize("shape", [(V, D), (D,), (37, 13)])
def test_fused_adam_variant(shape):
    r = np.random.RandomState(13)
    mk = lambda s=1.0: jnp.asarray(  # noqa: E731
        r.randn(*shape).astype(np.float32) * s)
    args = (mk(), mk(), mk(0.1), jnp.abs(mk(0.1)), jnp.float32(0.9),
            jnp.float32(0.999), jnp.float32(1e-3))
    _cmp_variant("adam", args,
                 {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8},
                 rtol=1e-6, atol=1e-7)
