"""Pallas kernel variants vs reference lowerings (the operators/jit
test pattern, jit/test.cc: every hand-written kernel must match its
refer impl; run in interpret mode on CPU, compiled on TPU)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import ops
from paddle_tpu.core.flags import FLAGS

import paddle_tpu as fluid
from paddle_tpu import layers


def _cmp(op_type, args, kwargs, rtol=2e-5, atol=2e-6):
    opdef = ops.get(op_type)
    ref = opdef.fn(*args, **kwargs)
    pal = opdef.variants["pallas"](*args, **kwargs)
    ref_flat = jax.tree_util.tree_leaves(ref)
    pal_flat = jax.tree_util.tree_leaves(pal)
    assert len(ref_flat) == len(pal_flat)
    for r, p in zip(ref_flat, pal_flat):
        np.testing.assert_allclose(np.asarray(p), np.asarray(r),
                                   rtol=rtol, atol=atol)


def test_sdpa_matches_reference():
    r = np.random.RandomState(0)
    B, H, Sq, Sk, Dh = 2, 4, 16, 24, 8
    q = jnp.asarray(r.randn(B, H, Sq, Dh).astype(np.float32))
    k = jnp.asarray(r.randn(B, H, Sk, Dh).astype(np.float32))
    v = jnp.asarray(r.randn(B, H, Sk, Dh).astype(np.float32))
    bias = jnp.asarray(
        np.where(r.rand(B, 1, Sq, Sk) > 0.2, 0.0, -1e9)
        .astype(np.float32))
    _cmp("scaled_dot_product_attention", (q, k, v, bias),
         {"scale": Dh ** -0.5})
    _cmp("scaled_dot_product_attention", (q, k, v, None),
         {"scale": Dh ** -0.5})


def test_sdpa_gradients_match():
    r = np.random.RandomState(1)
    B, H, S, Dh = 1, 2, 8, 4
    q = jnp.asarray(r.randn(B, H, S, Dh).astype(np.float32))
    k = jnp.asarray(r.randn(B, H, S, Dh).astype(np.float32))
    v = jnp.asarray(r.randn(B, H, S, Dh).astype(np.float32))
    opdef = ops.get("scaled_dot_product_attention")

    def loss_ref(q_, k_, v_):
        return jnp.sum(jnp.square(opdef.fn(q_, k_, v_, None,
                                           scale=0.5)))

    def loss_pal(q_, k_, v_):
        return jnp.sum(jnp.square(
            opdef.variants["pallas"](q_, k_, v_, None, scale=0.5)))

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    gp = jax.grad(loss_pal, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gp):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-5, atol=2e-6)


def test_sdpa_causal_matches_reference():
    r = np.random.RandomState(7)
    B, H, S, Dh = 1, 2, 64, 16
    q = jnp.asarray(r.randn(B, H, S, Dh).astype(np.float32))
    k = jnp.asarray(r.randn(B, H, S, Dh).astype(np.float32))
    v = jnp.asarray(r.randn(B, H, S, Dh).astype(np.float32))
    bias = jnp.asarray(
        np.where(r.rand(B, 1, 1, S) > 0.15, 0.0, -1e9)
        .astype(np.float32))
    bias = jnp.broadcast_to(bias, (B, 1, S, S))
    _cmp("scaled_dot_product_attention", (q, k, v, bias),
         {"scale": Dh ** -0.5, "causal": True})
    _cmp("scaled_dot_product_attention", (q, k, v, None),
         {"scale": Dh ** -0.5, "causal": True})


def test_sdpa_flash_blocked_multi_q_causal():
    """Multiple q-blocks AND k-blocks with causal masking — the
    longseq bench geometry (S=1024): exercises the dk/dv kernel's
    q-block accumulation and the causal block-skip logic, fwd +
    grads."""
    r = np.random.RandomState(9)
    B, H, S, Dh = 1, 2, 1024, 32
    q = jnp.asarray(r.randn(B, H, S, Dh).astype(np.float32))
    k = jnp.asarray(r.randn(B, H, S, Dh).astype(np.float32))
    v = jnp.asarray(r.randn(B, H, S, Dh).astype(np.float32))
    opdef = ops.get("scaled_dot_product_attention")
    _cmp("scaled_dot_product_attention", (q, k, v, None),
         {"scale": Dh ** -0.5, "causal": True}, rtol=5e-5, atol=1e-5)

    def loss(fn):
        return lambda q_, k_, v_: jnp.sum(jnp.square(
            fn(q_, k_, v_, None, scale=Dh ** -0.5, causal=True)))

    gr = jax.grad(loss(opdef.fn), (0, 1, 2))(q, k, v)
    gp = jax.grad(loss(opdef.variants["pallas"]), (0, 1, 2))(q, k, v)
    for a, b in zip(gr, gp):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-4, atol=5e-5)


def test_sdpa_flash_blocked_shapes():
    """Shapes that force multiple k-blocks through the online-softmax
    path (Sk > blk_k), fwd + grads — the flash recurrence itself."""
    r = np.random.RandomState(8)
    B, H, Sq, Sk, Dh = 1, 1, 256, 1024, 32
    q = jnp.asarray(r.randn(B, H, Sq, Dh).astype(np.float32))
    k = jnp.asarray(r.randn(B, H, Sk, Dh).astype(np.float32))
    v = jnp.asarray(r.randn(B, H, Sk, Dh).astype(np.float32))
    bias = jnp.asarray(
        np.where(r.rand(B, 1, 1, Sk) > 0.1, 0.0, -1e9)
        .astype(np.float32))
    bias = jnp.broadcast_to(bias, (B, 1, Sq, Sk))
    opdef = ops.get("scaled_dot_product_attention")
    _cmp("scaled_dot_product_attention", (q, k, v, bias),
         {"scale": Dh ** -0.5}, rtol=5e-5, atol=1e-5)

    def loss(fn):
        return lambda q_, k_, v_: jnp.sum(jnp.square(
            fn(q_, k_, v_, bias, scale=Dh ** -0.5)))

    gr = jax.grad(loss(opdef.fn), (0, 1, 2))(q, k, v)
    gp = jax.grad(loss(opdef.variants["pallas"]), (0, 1, 2))(q, k, v)
    for a, b in zip(gr, gp):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-4, atol=5e-5)


def test_layer_norm_matches_reference():
    r = np.random.RandomState(2)
    x = jnp.asarray(r.randn(6, 4, 32).astype(np.float32))
    scale = jnp.asarray(r.rand(4 * 32).astype(np.float32) + 0.5)
    bias = jnp.asarray(r.randn(4 * 32).astype(np.float32))
    _cmp("layer_norm", (x, scale, bias),
         {"epsilon": 1e-5, "begin_norm_axis": 1}, rtol=1e-4)
    x2 = jnp.asarray(r.randn(3, 8, 64).astype(np.float32))
    s2 = jnp.asarray(r.rand(64).astype(np.float32) + 0.5)
    _cmp("layer_norm", (x2, s2, None),
         {"epsilon": 1e-5, "begin_norm_axis": 2}, rtol=1e-4)


def test_softmax_xent_matches_reference():
    r = np.random.RandomState(3)
    logits = jnp.asarray(r.randn(32, 10).astype(np.float32))
    label = jnp.asarray(r.randint(0, 10, (32, 1)).astype(np.int64))
    _cmp("softmax_with_cross_entropy", (logits, label), {}, rtol=1e-5)
    # gradient parity
    opdef = ops.get("softmax_with_cross_entropy")
    gr = jax.grad(lambda lg: jnp.sum(opdef.fn(lg, label)[1]))(logits)
    gp = jax.grad(lambda lg: jnp.sum(
        opdef.variants["pallas"](lg, label)[1]))(logits)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                               rtol=2e-5, atol=1e-6)


def test_fused_adam_matches_reference():
    r = np.random.RandomState(4)
    shape = (37, 13)  # deliberately lane-unaligned
    p = jnp.asarray(r.randn(*shape).astype(np.float32))
    g = jnp.asarray(r.randn(*shape).astype(np.float32))
    m1 = jnp.asarray(r.randn(*shape).astype(np.float32) * 0.1)
    m2 = jnp.asarray(np.abs(r.randn(*shape)).astype(np.float32) * 0.1)
    args = (p, g, m1, m2, jnp.float32(0.9), jnp.float32(0.999),
            jnp.float32(1e-3))
    _cmp("adam", args, {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8},
         rtol=1e-6)


# tier-1 headroom (PR 17): ~26 s train-through-library twin -> slow;
# the pallas kernel surface stays via the sdpa flash/blocked tests
# and the smaller train smokes in this file
@pytest.mark.slow
def test_transformer_trains_with_pallas_library():
    """End-to-end: transformer eval/train step under
    FLAGS_op_library=pallas matches the default path."""
    from paddle_tpu.models import transformer as T

    def run(lib):
        fluid.framework._reset_default_programs()
        cfg = T.TransformerConfig(src_vocab=50, tgt_vocab=50,
                                  max_len=16, d_model=32, d_ffn=64,
                                  n_head=4, n_layer=1, dropout=0.0)
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 11
        with fluid.program_guard(main, startup):
            avg_cost, token_num, logits = T.transformer(cfg,
                                                        is_test=False)
            fluid.optimizer.SGD(0.1).minimize(avg_cost)
        exe = fluid.Executor()
        scope = fluid.Scope()
        feed = T.make_fake_batch(cfg, 4)
        with fluid.scope_guard(scope):
            exe.run(startup)
            old = FLAGS.op_library
            FLAGS.op_library = lib
            try:
                losses = []
                for _ in range(3):
                    (lv,) = exe.run(main, feed=feed,
                                    fetch_list=[avg_cost])
                    losses.append(float(lv))
            finally:
                FLAGS.op_library = old
        return losses

    base = run("")
    pal = run("pallas")
    np.testing.assert_allclose(pal, base, rtol=5e-4, atol=1e-5)


def test_sdpa_per_head_bias_matches_reference(rng):
    """A per-HEAD bias [B, H, Sq, Sk] must work on the pallas path and
    match the base lowering (the two library paths used to diverge:
    pallas only accepted [B, 1, Sq, Sk])."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import attention as A

    B, H, Sq, Sk, Dh = 1, 2, 128, 128, 64
    q = jnp.asarray(rng.randn(B, H, Sq, Dh).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(B, H, Sk, Dh).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(B, H, Sk, Dh).astype(np.float32)) * 0.3
    # distinct mask per head
    bias = np.zeros((B, H, Sq, Sk), np.float32)
    bias[:, 0, :, Sk // 2:] = -1e9
    bias[:, 1, :, :Sk // 4] = -1e9
    bias = jnp.asarray(bias)

    want = A._sdpa_reference(q, k, v, bias, scale=0.5)
    got = A.sdpa_pallas(q, k, v, bias, scale=0.5, is_test=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-3, rtol=2e-3)

    # gradients agree too (dq/dk/dv recompute path reads the bias)
    def ref_loss(a, b, c):
        return jnp.sum(A._sdpa_reference(a, b, c, bias,
                                         scale=0.5) ** 2)

    def pl_loss(a, b, c):
        return jnp.sum(A.sdpa_pallas(a, b, c, bias, scale=0.5,
                                     is_test=True) ** 2)

    gw = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    gg = jax.grad(pl_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gg, gw):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-3, rtol=5e-3)


def test_fused_linear_xent_matches_reference():
    """Streaming fused projection+xent kernel vs the composite lowering
    — forward and both gradients, hard labels and label smoothing,
    including a non-128-multiple vocab (masked padded tail)."""
    r = np.random.RandomState(5)
    N, D, V = 48, 16, 300
    x = jnp.asarray(r.randn(N, D).astype(np.float32)) * 0.5
    w = jnp.asarray(r.randn(D, V).astype(np.float32)) * 0.2
    lab = jnp.asarray(r.randint(0, V, size=(N, 1)).astype(np.int64))
    g = jnp.asarray(r.rand(N, 1).astype(np.float32))
    opdef = ops.get("fused_linear_xent")
    for eps in (0.0, 0.1):
        _cmp("fused_linear_xent", (x, w, lab), {"epsilon": eps},
             rtol=2e-5, atol=2e-5)
        dref = jax.grad(lambda a, b: jnp.sum(
            opdef.fn(a, b, lab, epsilon=eps) * g), argnums=(0, 1))(x, w)
        dpal = jax.grad(lambda a, b: jnp.sum(
            opdef.variants["pallas"](a, b, lab, epsilon=eps) * g),
            argnums=(0, 1))(x, w)
        for dr, dp in zip(dref, dpal):
            np.testing.assert_allclose(np.asarray(dp), np.asarray(dr),
                                       rtol=2e-4, atol=2e-5)


def test_fused_linear_xent_3d_and_bf16():
    """Leading dims flatten correctly; bf16 inputs keep f32 statistics
    (the AMP path: white-listed op, loss must stay finite/accurate)."""
    r = np.random.RandomState(6)
    B, S, D, V = 3, 8, 16, 130
    x = jnp.asarray(r.randn(B, S, D).astype(np.float32))
    w = jnp.asarray(r.randn(D, V).astype(np.float32)) * 0.3
    lab = jnp.asarray(r.randint(0, V, size=(B, S, 1)).astype(np.int64))
    opdef = ops.get("fused_linear_xent")
    ref = opdef.fn(x, w, lab, epsilon=0.1)
    pal = opdef.variants["pallas"](x, w, lab, epsilon=0.1)
    assert pal.shape == (B, S, 1) and pal.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    xb, wb = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    palb = opdef.variants["pallas"](xb, wb, lab, epsilon=0.1)
    assert palb.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(palb), np.asarray(ref),
                               rtol=0.05, atol=0.05)


def _qkv_bias(r, B, H, Sq, Sk, Dh, bias_kind, heads_last=False):
    """q, k, v as [B,H,S,Dh], or as [B,S,H*Dh] with ``heads_last``;
    the additive mask by kind: per key, per batch row, per head."""
    def mk(S):
        shape = (B, S, H * Dh) if heads_last else (B, H, S, Dh)
        return jnp.asarray(r.randn(*shape).astype(np.float32))

    q, k, v = mk(Sq), mk(Sk), mk(Sk)
    shape = {None: None, "key": (B, 1, 1, Sk), "batch": (B, 1, Sq, Sk),
             "head": (B, H, Sq, Sk)}[bias_kind]
    bias = None
    if shape is not None:
        keep = r.rand(*shape) > 0.2
        keep[..., 0] = True          # no row wholly masked
        bias = jnp.asarray(np.where(keep, 0.0, -1e9).astype(np.float32))
    return q, k, v, bias


def _pair_matches_reference(q, k, v, bias, **kw):
    """The pallas variant against the base lowering's reference: the
    output and the gradients of all three inputs."""
    _cmp("scaled_dot_product_attention", (q, k, v, bias), kw,
         rtol=5e-5, atol=1e-5)
    opdef = ops.get("scaled_dot_product_attention")

    def loss(fn):
        return lambda q_, k_, v_: jnp.sum(jnp.square(
            fn(q_, k_, v_, bias, **kw)))

    gr = jax.grad(loss(opdef.fn), (0, 1, 2))(q, k, v)
    gp = jax.grad(loss(opdef.variants["pallas"]), (0, 1, 2))(q, k, v)
    for a, b in zip(gr, gp):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-4, atol=5e-5)


@pytest.fixture
def heads_to_a_cell(monkeypatch):
    """Cap the 1k pair's heads per grid cell, so that a batch row
    spans several cells at test sizes (every size fits the VMEM model
    whole). The jitted wrappers choose G while they trace: their
    caches are dropped on both sides of the test."""
    from paddle_tpu.ops.pallas import attention as A

    def cap(n):
        monkeypatch.setattr(A, "_1K_MAX_G", n)
        jax.clear_caches()

    yield cap
    jax.clear_caches()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("bias_kind", [None, "batch", "head"])
@pytest.mark.parametrize("Sq,Sk", [(512, 512), (512, 256)])
def test_sdpa_flash_1k_q_blocked(Sq, Sk, bias_kind, causal):
    """The single-k-block pair with more than one q-block (BERT's
    S=512) behind a rank-4 caller, which the lowering adapts by a
    transpose: forward, and dq / dk / dv — dk and dv are SUMS over the
    q-blocks of a cell, which a wrong accumulator breaks. H=6 at
    Dh=16 is one cell of 96 lanes, the whole width."""
    from paddle_tpu.ops.pallas import attention as A

    B, H, Dh = 1, 6, 16
    assert A._1k_applicable(Sq, Sk) and Sq // A._1k_blk_q(Sq) == 2
    assert A._1k_bwd_G(H, 4, Sq, Sk, Dh, 4 if bias_kind else 0,
                       bias_kind == "head") == H
    q, k, v, bias = _qkv_bias(np.random.RandomState(21), B, H, Sq, Sk,
                              Dh, bias_kind)
    _pair_matches_reference(q, k, v, bias, scale=Dh ** -0.5,
                            causal=causal)


# Sq, Sk, H, Dh, bias, causal: the two models' sites (H=8 and H=12 at
# Dh=64; one, two and two-over-half-the-keys q-blocks), every kind of
# bias and the causal mask on each, and a head of one lane tile and of
# a quarter of one
_RANK3_CASES = [
    (256, 256, 8, 64, None, False),
    (256, 256, 8, 64, "key", True),
    (256, 256, 8, 64, "batch", False),
    (256, 256, 8, 64, "head", True),
    (256, 256, 12, 64, "batch", True),
    (512, 512, 12, 64, "key", False),
    (512, 512, 12, 64, None, True),
    (512, 512, 8, 64, "head", False),
    (512, 512, 8, 64, "batch", True),
    (512, 256, 12, 64, "batch", False),
    (512, 256, 8, 64, "key", True),
    (512, 256, 8, 64, "head", False),
    (256, 256, 2, 128, "key", True),
    (256, 256, 4, 32, "batch", False),
]


@pytest.mark.parametrize("Sq,Sk,H,Dh,bias_kind,causal", _RANK3_CASES)
def test_sdpa_flash_1k_heads_in_place(Sq, Sk, H, Dh, bias_kind, causal):
    """The pair on the projections' own layout, q [B,Sq,H*Dh] and k, v
    [B,Sk,H*Dh]: each head picked out of the lanes inside the kernel,
    against the reference's einsums over the same arrays."""
    q, k, v, bias = _qkv_bias(np.random.RandomState(31), 1, H, Sq, Sk,
                              Dh, bias_kind, heads_last=True)
    _pair_matches_reference(q, k, v, bias, scale=Dh ** -0.5,
                            causal=causal, num_heads=H)


@pytest.mark.parametrize("Sq,Sk,H,G,bias_kind,causal", [
    (256, 256, 8, 2, "batch", True),
    (512, 512, 12, 4, "key", False),
    (512, 256, 12, 6, "head", True),
])
def test_sdpa_flash_1k_cells_across_a_batch_row(heads_to_a_cell, Sq, Sk,
                                                H, G, bias_kind,
                                                causal):
    """Several cells to a batch row (what the VMEM model does to
    BERT's twelve heads): the lanes of cell c start at c*G*Dh, a
    per-batch bias is shared by the row's cells, a per-head one
    indexed b*H/G + c, and each cell sums its own dk / dv."""
    from paddle_tpu.ops.pallas import attention as A

    Dh = 64
    heads_to_a_cell(G)
    assert A._1k_bwd_G(H, 4, Sq, Sk, Dh, 4, bias_kind == "head") == G
    q, k, v, bias = _qkv_bias(np.random.RandomState(32), 2, H, Sq, Sk,
                              Dh, bias_kind, heads_last=True)
    _pair_matches_reference(q, k, v, bias, scale=Dh ** -0.5,
                            causal=causal, num_heads=H)


@pytest.mark.parametrize("rate", [0.0, 0.1])
@pytest.mark.parametrize("Sq,Sk,H,bias_kind,causal", [
    (256, 256, 8, "batch", False),
    (512, 256, 12, "head", True),
])
def test_sdpa_reference_rank3_equals_rank4(Sq, Sk, H, bias_kind, causal,
                                           rate):
    """The reference over [B,S,H*Dh] is the reference over the same
    data as [B,H,S,Dh]: the output to the last bit, with dropout too
    (the mask is drawn at [B,H,Sq,Sk] in both); the gradients to
    float32 rounding (their contractions run over the rows, which the
    two layouts hand the CPU's products in another order)."""
    from paddle_tpu.ops.pallas import attention as A

    Dh = 64
    q, k, v, bias = _qkv_bias(np.random.RandomState(33), 2, H, Sq, Sk,
                              Dh, bias_kind, heads_last=True)
    kw = dict(scale=Dh ** -0.5, causal=causal, dropout_rate=rate,
              rng=jax.random.key(3))

    def rank3(q_, k_, v_):
        return A._sdpa_reference(q_, k_, v_, bias, num_heads=H, **kw)

    def rank4(q_, k_, v_):
        return A._merge_heads(A._sdpa_reference(
            *(A._split_heads(x, Dh) for x in (q_, k_, v_)), bias, **kw))

    def loss(fn):
        return lambda *a: jnp.sum(jnp.square(fn(*a)))

    np.testing.assert_array_equal(np.asarray(rank3(q, k, v)),
                                  np.asarray(rank4(q, k, v)))
    for a, b in zip(jax.grad(loss(rank3), (0, 1, 2))(q, k, v),
                    jax.grad(loss(rank4), (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5,
            atol=1e-5 * float(jnp.abs(b).max()))


def test_sdpa_rank3_outside_the_envelope_and_gqa():
    """Rank 3 where the pair does not serve: S=1024 takes the blocked
    kernels behind the lowering's own head split, and fewer kv heads
    with a window the reference's grouped einsums; both against the
    rank-4 reference of the same data."""
    from paddle_tpu.ops.pallas import attention as A

    r = np.random.RandomState(34)
    H, Dh, S = 2, 32, 1024
    q, k, v, _ = _qkv_bias(r, 1, H, S, S, Dh, None, heads_last=True)
    want = A._merge_heads(A._sdpa_reference(
        *(A._split_heads(x, Dh) for x in (q, k, v)), None,
        scale=Dh ** -0.5, causal=True))
    got = A.sdpa_pallas(q, k, v, None, scale=Dh ** -0.5, causal=True,
                        is_test=True, num_heads=H)
    assert got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-5, atol=1e-5)
    H, hkv, S = 4, 2, 64
    q = jnp.asarray(r.randn(1, S, H * Dh).astype(np.float32))
    k, v = (jnp.asarray(r.randn(1, S, hkv * Dh).astype(np.float32))
            for _ in range(2))
    kw = dict(scale=Dh ** -0.5, causal=True, window=16)
    want = A._merge_heads(A._sdpa_reference(
        *(A._split_heads(x, Dh) for x in (q, k, v)), None, **kw))
    got = A.scaled_dot_product_attention(q, k, v, None, is_test=True,
                                         num_heads=H, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)
    with pytest.raises(ValueError, match="num_heads"):
        A.scaled_dot_product_attention(q, k, v, None, scale=1.0)


@pytest.mark.parametrize("S,dtype", [(256, "float32"),
                                     (512, "bfloat16"),
                                     (1024, "bfloat16")])
def test_attention_dropout_grouping_consistent(monkeypatch, S, dtype):
    """The dropout mask is seeded per grid CELL and q-block, so with
    dropout on the forward and every backward kernel must group
    (batch, head) rows into cells identically and block q alike —
    single-k-block (S=256: one q-block; S=512: two) and blocked
    (S=1024) paths alike. A fwd G=8 / bwd G=4 split regenerates
    different masks for heads the groupings assign to different cells:
    silently wrong gradients."""
    from test_pallas_vmem import _capture_calls

    from paddle_tpu.ops.pallas import attention as A

    monkeypatch.setattr(A, "interpret_mode", lambda: False)
    q = jnp.zeros((2, 8, S, 64), dtype)
    var = ops.get("scaled_dot_product_attention").variants["pallas"]

    def fwd_bwd():
        jax.grad(lambda q_, k_, v_: jnp.sum(var(
            q_, k_, v_, None, dropout_rate=0.1,
            rng=jax.random.key(0)).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, q, q)

    calls = _capture_calls(fwd_bwd)
    assert len(calls) >= 2                     # fwd + bwd kernel(s)
    # the q block every kernel streams: same rows, same blk_q
    assert len({tuple(c["in_specs"][1].block_shape)
                for c in calls}) == 1
    if A._1k_applicable(S, S):
        # (batch rows, cells of G heads, q-blocks), the pair's one grid
        assert {c["grid"] for c in calls} == {
            (2, calls[0]["grid"][1], S // A._1k_blk_q(S))}
    else:
        assert len({c["grid"][0] for c in calls}) == 1, \
            [c["grid"] for c in calls]


_ENVELOPE_CASES = [
    # Sq, Sk, dtype, rate, flag, dispatched
    (256, 256, "bfloat16", 0.1, True, True),
    (512, 512, "bfloat16", 0.1, True, True),      # BERT phase 2
    (512, 256, "bfloat16", 0.1, True, True),
    (256, 512, "bfloat16", 0.1, True, True),
    (1024, 1024, "bfloat16", 0.1, True, False),   # Sk > 512
    (384, 512, "bfloat16", 0.1, True, False),     # ragged q-blocks
    (520, 512, "bfloat16", 0.1, True, False),
    (256, 256, "float32", 0.1, True, False),      # f32: stays XLA
    (256, 256, "bfloat16", 0.0, True, False),     # no dropout
    (256, 256, "bfloat16", 0.1, False, False),    # flag off
]


def _run_base_sdpa(Sq, Sk, dtype, rate, flag, rank=4):
    """The base lowering over 4 heads of 64 in either entry layout."""
    from paddle_tpu.ops.pallas import attention as A

    prev = FLAGS.sdpa_auto_flash
    FLAGS.sdpa_auto_flash = flag
    try:
        # non-degenerate inputs: BOTH paths must run clean — a crash
        # in either is a real failure (ADVICE r4: a blanket except
        # here swallowed the dispatched path's errors too)
        q = jnp.full((2, 4, Sq, 64) if rank == 4 else (2, Sq, 256),
                     0.1, dtype)
        k = jnp.full((2, 4, Sk, 64) if rank == 4 else (2, Sk, 256),
                     0.1, dtype)
        return A.scaled_dot_product_attention(
            q, k, k, None, scale=0.125, dropout_rate=rate,
            num_heads=0 if rank == 4 else 4, rng=jax.random.key(0))
    finally:
        FLAGS.sdpa_auto_flash = prev


@pytest.mark.parametrize("rank", [4, 3])
@pytest.mark.parametrize("Sq,Sk,dtype,rate,flag,dispatched",
                         _ENVELOPE_CASES)
def test_sdpa_auto_flash_dispatch_envelope(monkeypatch, Sq, Sk, dtype,
                                           rate, flag, dispatched, rank):
    """FLAGS_sdpa_auto_flash routes the BASE lowering to the flash
    kernel exactly inside the chip-measured win envelope: TPU
    execution, <=2-byte dtype, dropout active, single-k-block shapes
    (Sk <= 512; Sq at most 256 or whole 256-row q-blocks). Everything
    else (f32, no dropout, longer keys, ragged q, interpret mode)
    keeps the XLA chain. The envelope is of the sequence lengths: the
    same in both entry layouts."""
    from paddle_tpu.ops.pallas import attention as A

    calls = []
    monkeypatch.setattr(A, "interpret_mode", lambda: False)
    monkeypatch.setattr(
        A, "sdpa_pallas",
        lambda q, k, v, b, **kw: calls.append("flash") or q)
    _run_base_sdpa(Sq, Sk, dtype, rate, flag, rank)
    assert calls == (["flash"] if dispatched else [])


def _lowerings_counted(fn):
    """{path: bumps} of the ``sdpa_lowering.*`` counters over fn()."""
    from paddle_tpu import profiler

    def read():
        return {k.split(".", 1)[1]: v
                for k, v in profiler.counter_values().items()
                if k.startswith("sdpa_lowering.")}

    before = read()
    fn()
    after = read()
    return {k: after[k] - before.get(k, 0.0) for k in after
            if after[k] != before.get(k, 0.0)}


@pytest.mark.parametrize("rank", [4, 3])
@pytest.mark.parametrize("Sq,Sk,dtype,rate,flag,dispatched",
                         _ENVELOPE_CASES)
def test_sdpa_lowering_counter_names_the_path(monkeypatch, Sq, Sk,
                                              dtype, rate, flag,
                                              dispatched, rank):
    """``sdpa_lowering.<path>`` counts each lowering under the path it
    took: the envelope's cases read flash_1k, the rest xla; and
    flash_1k_transposed beside flash_1k where the pair was reached
    from rank 4, so the lowering built the transposes."""
    from test_pallas_vmem import _capture_calls

    from paddle_tpu.ops.pallas import attention as A

    monkeypatch.setattr(A, "interpret_mode", lambda: False)

    def lower():
        _run_base_sdpa(Sq, Sk, dtype, rate, flag, rank)

    moved = _lowerings_counted(
        (lambda: _capture_calls(lower)) if dispatched else lower)
    want = {"xla": 1.0}
    if dispatched:
        want = {"flash_1k": 1.0}
        if rank == 4:
            want["flash_1k_transposed"] = 1.0
    assert moved == want


def test_sdpa_lowering_counter_other_paths(monkeypatch):
    """The paths the base op's envelope never takes: the pallas
    library's blocked kernels (S=1024), its reference fallback
    (dropout in interpret mode), and the sp route."""
    from paddle_tpu.ops.pallas import attention as A
    from paddle_tpu.parallel import ulysses

    moved = _lowerings_counted
    q = jnp.full((1, 2, 1024, 16), 0.1, jnp.float32)
    assert moved(lambda: A.sdpa_pallas(q, q, q, None, scale=0.25,
                                       is_test=True)) \
        == {"flash_blocked": 1.0}
    q3 = A._merge_heads(q)      # the blocked kernels behind rank 3
    assert moved(lambda: A.sdpa_pallas(q3, q3, q3, None, scale=0.25,
                                       is_test=True, num_heads=2)) \
        == {"flash_blocked": 1.0}
    s = q[:, :, :128]
    assert moved(lambda: A.sdpa_pallas(
        s, s, s, None, scale=0.25, dropout_rate=0.1,
        rng=jax.random.key(0))) == {"xla": 1.0}
    monkeypatch.setattr(ulysses, "sequence_parallel_attention",
                        lambda q_, k_, v_, **kw: q_)
    monkeypatch.setattr(FLAGS, "sp_attention", True)
    assert moved(lambda: A.scaled_dot_product_attention(
        s, s, s, None, scale=0.25, is_test=True)) == {"sp": 1.0}
    # the sp schedules read heads leading: a rank-3 caller is split
    # for them and their output merged back
    s3 = A._merge_heads(s) + jnp.arange(32.0)
    seen = []
    monkeypatch.setattr(
        ulysses, "sequence_parallel_attention",
        lambda q_, k_, v_, **kw: seen.append(q_.shape) or q_)
    out = {}
    assert moved(lambda: out.update(o=A.scaled_dot_product_attention(
        s3, s3, s3, None, scale=0.25, is_test=True, num_heads=2))) \
        == {"sp": 1.0}
    assert seen == [(1, 2, 128, 16)]
    np.testing.assert_array_equal(np.asarray(out["o"]), np.asarray(s3))


def test_sdpa_auto_flash_failure_propagates(monkeypatch):
    """Inside the envelope the kernel is THE lowering: when it fails
    (a Mosaic compile error on the chip) the op fails — nothing
    catches it and carries on with the jnp reference."""
    from paddle_tpu.ops.pallas import attention as A

    class KernelRefused(RuntimeError):
        pass

    def refuse(*a, **kw):
        raise KernelRefused("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(A, "interpret_mode", lambda: False)
    monkeypatch.setattr(A, "_flash_fwd_1k", refuse)
    q = jnp.full((2, 256, 256), 0.1, jnp.bfloat16)
    with pytest.raises(KernelRefused):
        A.scaled_dot_product_attention(
            q, q, q, None, scale=0.125, dropout_rate=0.1, num_heads=4,
            rng=jax.random.key(0))


@pytest.mark.parametrize("rank", [4, 3])
@pytest.mark.parametrize("axes", [{"dp": 4}, {"dp": 2, "tp": 2}])
def test_sdpa_pallas_under_mesh_runs_per_shard(axes, rank):
    """Under a multi-device mesh the kernel runs per shard (Mosaic
    kernels are not auto-partitioned): batch over dp, heads over tp
    (axis 1 of rank 4, contiguous lanes of rank 3's last axis), the
    pad bias following the batch — same values and gradients as the
    unsharded call."""
    from paddle_tpu.ops.pallas import attention as A
    from paddle_tpu.parallel import mesh as mesh_lib

    r = np.random.RandomState(14)
    B, H, S, Dh = 4, 4, 128, 64
    q, k, v, _ = _qkv_bias(r, B, H, S, S, Dh, None,
                           heads_last=rank == 3)
    bias = jnp.asarray(np.where(r.rand(B, 1, 1, S) > 0.2, 0.0, -1e9)
                       .astype(np.float32))

    def loss(q_, k_, v_):
        return jnp.sum(jnp.square(A.sdpa_pallas(
            q_, k_, v_, bias, scale=0.125, causal=True, is_test=True,
            num_heads=H if rank == 3 else 0)))

    want = jax.value_and_grad(loss, (0, 1, 2))(q, k, v)
    n = int(np.prod(list(axes.values())))
    with mesh_lib.mesh_guard(mesh_lib.make_mesh(axes,
                                                jax.devices()[:n])):
        got = jax.jit(jax.value_and_grad(loss, (0, 1, 2)))(q, k, v)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-5, atol=2e-5)
