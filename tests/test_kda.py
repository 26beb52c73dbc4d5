"""Kimi Delta Attention (ops/kda_ops.py): the chunked form the op
lowers to against the token-by-token recurrence that defines it --
forward and every input's gradient, at a length that is no multiple of
the chunk, across several chunks (the carried state), under gates so
strong that a decay factorised about the chunk's start would leave
float32 --, the two limits written out by hand, the short convolution's
causality, what the custom VJP keeps, and the op's counters; then the
chunked form's second lowering, the Mosaic kernels of
ops/pallas/kda.py, interpreted here: against the recurrence and the XLA
form, the rule that chooses between the two, and the module's
constants as a fault driver sets them."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import layers, profiler
from paddle_tpu.ops import kda_ops as K
from paddle_tpu.ops.pallas import kda as KP


def inputs(seed, b, s, h, dk, dv, g_scale=1.0):
    r = np.random.RandomState(seed)
    f = lambda *sh: jnp.asarray(r.randn(*sh), jnp.float32)  # noqa: E731
    g = -jnp.asarray(r.uniform(0.0, g_scale, (b, s, h * dk)),
                     jnp.float32)
    beta = jnp.asarray(r.uniform(0.1, 0.9, (b, s, h)), jnp.float32)
    return f(b, s, h * dk), f(b, s, h * dk), f(b, s, h * dv), g, beta


def unit(x):
    """The op's L2 norm over the last axis, in numpy."""
    return x / np.sqrt(np.sum(x * x, -1, keepdims=True) + 1e-6)


def both(args, ct, scale):
    def rec(*a):
        return K.kda_recurrence(*a, scale=scale)

    def chk(*a):
        return K.kda_chunked(*a, scale)[0]
    loss = lambda f: lambda *a: jnp.sum(f(*a) * ct)     # noqa: E731
    every = tuple(range(5))
    return (rec(*args), chk(*args),
            jax.grad(loss(rec), every)(*args),
            jax.jit(jax.grad(loss(chk), every))(*args))


@pytest.mark.parametrize("s,strong", [(150, False), (150, True),
                                      (64, True), (700, True)])
def test_chunked_form_is_the_recurrence(s, strong):
    """150 = two chunks and 22 tokens; 700 spans two blocks of the
    stateless part. ``strong``: A = 16 and a step near 5 for a run of
    70 tokens, so the in-chunk cumulative log decay reaches -3000 and
    ``exp(-G)`` about the chunk's start would be inf."""
    b, h, dk, dv = 2, 3, 8, 16
    q, k, v, g, beta = inputs(s, b, s, h, dk, dv, 3.0)
    if strong:
        g = g.at[:, 30:100].multiply(16.0 * 2.0)
        assert float(jnp.min(jnp.cumsum(g[:, :64], axis=1))) < -1000
    ct = jnp.asarray(np.random.RandomState(1).randn(b, s, h * dv),
                     jnp.float32)
    o_rec, o_chk, g_rec, g_chk = both((q, k, v, g, beta), ct, dk ** -0.5)
    assert o_chk.shape == (b, s, h * dv)
    np.testing.assert_allclose(o_chk, o_rec, rtol=2e-4, atol=2e-5)
    for name, a, w in zip(("q", "k", "v", "g", "beta"), g_chk, g_rec):
        assert bool(jnp.all(jnp.isfinite(a))), name
        np.testing.assert_allclose(a, w, rtol=2e-3, atol=2e-4,
                                   err_msg="d" + name)


def test_no_decay_is_the_delta_rule():
    """g = 0: S_t = (I - beta k k^T) S_{t-1} + beta k v^T, in numpy."""
    b, s, h, dk, dv = 1, 90, 2, 4, 4
    q, k, v, g, beta = inputs(5, b, s, h, dk, dv)
    got = np.asarray(K.kda_chunked(q, k, v, 0.0 * g, beta, 1.0)[0])
    qn, kn, vn = (np.asarray(x, np.float64).reshape(s, h, -1)
                  for x in (q, k, v))
    qn, kn = unit(qn), unit(kn)
    bn = np.asarray(beta, np.float64)[0]
    want = np.zeros((s, h, dv))
    for head in range(h):
        S = np.zeros((dk, dv))
        for t in range(s):
            kt = kn[t, head]
            S = S - bn[t, head] * np.outer(kt, kt @ S) \
                + bn[t, head] * np.outer(kt, vn[t, head])
            want[t, head] = S.T @ qn[t, head]
    np.testing.assert_allclose(got[0], want.reshape(s, -1), rtol=1e-3,
                               atol=1e-4)


def test_small_beta_is_decayed_linear_attention():
    """beta -> 0: the k k^T term is second order, so o / beta tends to
    sum_{j<=t} (q_t . (k_j * exp(G_t - G_j))) v_j with G the cumulative
    log decay over the whole row (q and k the normalised ones)."""
    b, s, h, dk, dv = 1, 100, 1, 4, 4
    q, k, v, g, _ = inputs(6, b, s, h, dk, dv)
    eps = 1e-4
    beta = jnp.full((b, s, h), eps, jnp.float32)
    got = np.asarray(K.kda_chunked(q, k, v, g, beta, 1.0)[0])[0] / eps
    qn, kn, vn, gn = (np.asarray(x, np.float64)[0] for x in (q, k, v, g))
    qn, kn = unit(qn), unit(kn)
    G = np.cumsum(gn, axis=0)
    want = np.zeros((s, dv))
    for t in range(s):
        w = np.einsum("c,jc->j", qn[t], kn[:t + 1]
                      * np.exp(G[t] - G[:t + 1]))
        want[t] = w @ vn[:t + 1]
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-3)


def test_custom_vjp_keeps_the_inputs_alone():
    args = inputs(7, 1, 130, 2, 4, 4)
    out, res = K._kda_fwd(*args, 0.5)
    assert len(res) == 5 and all(r is a for r, a in zip(res, args))
    from jax._src.ad_checkpoint import saved_residuals
    saved = saved_residuals(lambda *a: K.kda_chunked(*a, 0.5)[0],
                            *args)
    kept = sum(int(np.prod(aval.shape)) for aval, _ in saved)
    assert kept <= sum(int(a.size) for a in args), saved


def test_short_conv_is_causal_and_exact():
    op = fluid.ops.get("short_conv").fn
    r = np.random.RandomState(8)
    x = r.randn(2, 12, 6).astype(np.float32)
    w = r.randn(6, 4).astype(np.float32)
    y1 = np.asarray(op(jnp.asarray(x), jnp.asarray(w)))
    want = np.zeros_like(x)
    for t in range(12):
        for i in range(4):
            if t - 3 + i >= 0:
                want[:, t] += w[:, i] * x[:, t - 3 + i]
    np.testing.assert_allclose(y1, want / (1 + np.exp(-want)),
                               rtol=1e-5, atol=1e-6)
    x2 = x.copy()
    x2[:, 7] += 5.0                 # a future token for rows 0..6
    y2 = np.asarray(op(jnp.asarray(x2), jnp.asarray(w)))
    assert np.array_equal(y1[:, :7], y2[:, :7])
    assert not np.allclose(y1[:, 7:11], y2[:, 7:11])
    assert np.array_equal(y1[:, 11:], y2[:, 11:])   # four taps reach 10


def test_gate_and_gated_norm_by_hand():
    r = np.random.RandomState(9)
    x = r.randn(1, 5, 8).astype(np.float32)
    a_log = r.uniform(0, 2.7, (2,)).astype(np.float32)
    dt = r.randn(8).astype(np.float32)
    gate = fluid.ops.get("kda_gate").fn
    got = np.asarray(gate(jnp.asarray(x), jnp.asarray(a_log),
                          jnp.asarray(dt)))
    want = -np.repeat(np.exp(a_log), 4) * np.log1p(np.exp(x + dt))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert got.dtype == np.float32 and (got < 0).all()
    norm = fluid.ops.get("gated_rms_norm").fn
    o, g = r.randn(1, 5, 8).astype(np.float32), r.randn(1, 5, 8)
    w = r.uniform(0.5, 1.5, (4,)).astype(np.float32)
    got = np.asarray(norm(jnp.asarray(o), jnp.asarray(g, jnp.float32),
                          jnp.asarray(w), epsilon=1e-5))
    oh = o.reshape(1, 5, 2, 4)
    want = (oh / np.sqrt((oh ** 2).mean(-1, keepdims=True) + 1e-5)
            * w).reshape(1, 5, 8) / (1 + np.exp(-g))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def _small_op_case(op, seed, dtype, b, s, c, k=None, d=64):
    """(the op under test, its definition, the inputs, dy) in ``dtype``."""
    r = np.random.RandomState(seed)
    f = lambda *sh: jnp.asarray(r.randn(*sh), dtype)    # noqa: E731
    if op == "short_conv":
        return (fluid.ops.get(op).fn, K.short_conv_definition,
                (f(b, s, c), f(c, k)), f(b, s, c))
    eps = 1e-5
    return (lambda *a: fluid.ops.get(op).fn(*a, epsilon=eps),
            lambda *a: K.gated_rms_norm_definition(*a, eps),
            (f(b, s, c), f(b, s, c),
             jnp.asarray(r.uniform(0.5, 1.5, (d,)), dtype)), f(b, s, c))


def _matches_plain_autodiff(fn, definition, args, dy):
    """The output and every gradient against ``jax.vjp`` of the
    definition: float32 rounding at float32 inputs (an element, or for
    a sum the array's largest), one ulp of the result at bfloat16."""
    got_y, pull = jax.vjp(fn, *args)
    want_y, want_pull = jax.vjp(definition, *args)
    rtol = 1e-6 if dy.dtype == jnp.float32 else 2.0 ** -7
    for got, want in zip((got_y,) + pull(dy), (want_y,) + want_pull(dy)):
        assert got.dtype == want.dtype and got.shape == want.shape
        want = np.asarray(want, np.float32)
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   rtol=rtol,
                                   atol=1e-6 * np.abs(want).max())


@pytest.mark.parametrize("c", [128, 384])
@pytest.mark.parametrize("k", [2, 4])
@pytest.mark.parametrize("s", [5, 64, 200])
@pytest.mark.parametrize("b", [1, 2])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_short_conv_vjp_is_plain_autodiff(dtype, b, s, k, c):
    """y, dx and dw of the op's own VJP against autodiff of
    ``short_conv_definition``; 5 and 200 are no multiple of a tile's
    rows, and 5 rows with 4 taps are little more than the halo."""
    _matches_plain_autodiff(*_small_op_case(
        "short_conv", 31 + s + k, jnp.dtype(dtype), b, s, c, k))


@pytest.mark.parametrize("c", [128, 384])
@pytest.mark.parametrize("s", [5, 64, 200])
@pytest.mark.parametrize("b", [1, 2])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gated_rms_norm_vjp_is_plain_autodiff(dtype, b, s, c):
    """y, dx, dgate and dscale against autodiff of
    ``gated_rms_norm_definition``, two and six heads of 64 lanes."""
    _matches_plain_autodiff(*_small_op_case(
        "gated_rms_norm", 37 + s, jnp.dtype(dtype), b, s, c))


@pytest.mark.parametrize("op", ["short_conv", "gated_rms_norm"])
def test_small_ops_keep_their_inputs_alone(op):
    """What the pullback closes over is the op's inputs as they came
    (bfloat16 under AMP): no float32 array of x's size waits for the
    backward pass, as the definition's autodiff keeps several."""
    fn, definition, args, _ = _small_op_case(op, 41, jnp.bfloat16,
                                             2, 70, 128, 4)

    def kept(f):
        pull = jax.eval_shape(lambda *a: jax.vjp(f, *a)[1], *args)
        return sorted((v.shape, str(v.dtype))
                      for v in jax.tree_util.tree_leaves(pull))
    assert kept(fn) == sorted((a.shape, str(a.dtype)) for a in args)
    assert (args[0].shape, "float32") in kept(definition)


def test_the_op_trains_and_counts():
    """Through layers -> Program -> Executor: the loss falls, and
    telemetry()["kda"] counts the tokens, the chunks and the elements
    of the in-chunk cumulative log decay under -80 (two steps of one
    layer over 2 x 100 tokens: chunks of 64 and 36)."""
    b, s, h, d = 2, 100, 2, 4
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", shape=[s, h * d], dtype="float32")
        y = layers.data("y", shape=[s, h * d], dtype="float32")
        proj = lambda n: layers.short_conv(                 # noqa: E731
            layers.fc(x, h * d, num_flatten_dims=2, bias_attr=False),
            name="conv_" + n)
        # A = exp(3) and a step of softplus(2): -40 a token, the floor
        # after two tokens of every chunk
        start = lambda n, shape, value: layers.create_parameter(  # noqa: E731
            shape, "float32", name=n,
            default_initializer=fluid.initializer.Constant(value))
        g = layers.kda_gate(
            layers.fc(x, h * d, num_flatten_dims=2, bias_attr=False),
            start("gate.A_log", (h,), 3.0),
            start("gate.dt_bias", (h * d,), 2.0))
        beta = layers.sigmoid(layers.fc(x, h, num_flatten_dims=2))
        o = layers.kda_attention(proj("q"), proj("k"), proj("v"), g,
                                 beta, scale=d ** -0.5)
        loss = layers.reduce_mean(layers.square(o - y))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    before = profiler.counter_values().get("kda_lowering.xla_chunked",
                                           0.0)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    r = np.random.RandomState(3)
    feed = {"x": r.randn(b, s, h * d).astype(np.float32),
            "y": r.randn(b, s, h * d).astype(np.float32) * 0.1}
    with fluid.scope_guard(scope):
        exe.run(startup)
        assert exe.telemetry(scope=scope)["kda"]["tokens_total"] == 0
        losses = [float(exe.run(main, feed=feed, fetch_list=[loss])[0])
                  for _ in range(2)]
        gate = np.asarray(scope.find_var("gate.A_log"))
    assert losses[1] < losses[0]
    assert gate.shape == (h,) and np.abs(gate - 3.0).max() > 0  # it trains
    tel = exe.telemetry(scope=scope)["kda"]
    assert tel["tokens_total"] == 2 * b * s
    assert tel["chunks_total"] == 2 * b * 2
    # every element from a chunk's second or third token on, and none
    # of the 28 pad positions that fill the second chunk
    assert 0.95 < tel["decay_floor_hits_total"] \
        / (2 * b * s * h * d) < 0.99
    assert profiler.counter_values()["kda_lowering.xla_chunked"] > before


# -- the Mosaic kernels (ops/pallas/kda.py), interpreted -------------------

@pytest.fixture
def as_on_the_chip(monkeypatch):
    """``kda_chunked`` chooses the kernels where ``interpret_mode()`` is
    false; the kernels' own copy of it stays true, so they are
    interpreted."""
    monkeypatch.setattr(K, "interpret_mode", lambda: False)


@pytest.fixture
def small_tiles(monkeypatch):
    """ops/pallas/kda_small.py's tiles made small, so that a few
    thousand rows are several row tiles and 384 lanes several lane
    tiles. Its wrappers are jitted on the shapes alone: no trace made
    under other tiles may be met, here or afterwards."""
    from paddle_tpu.ops.pallas import kda_small as KS
    wrappers = (KS.conv_fwd, KS.conv_bwd, KS.norm_fwd, KS.norm_bwd)

    def tiles(lanes):
        monkeypatch.setattr(KS, "_TILE_LANES", lanes)
        monkeypatch.setattr(KS, "_TILE_ELEMENTS", 512 * lanes)
        for f in wrappers:
            f.clear_cache()
        return KS
    yield tiles
    monkeypatch.undo()
    for f in wrappers:
        f.clear_cache()


def kernel_inputs(seed, s, strong, dtype, h=2, d=128):
    q, k, v, g, beta = inputs(seed, 1, s, h, d, d, 3.0)
    if strong:
        g = g.at[:, 30:100].multiply(16.0 * 2.0)
        assert float(jnp.min(jnp.cumsum(g[:, :64], axis=1))) < -1000
    ct = jnp.asarray(np.random.RandomState(1).randn(1, s, h * d), dtype)
    return tuple(x.astype(dtype) for x in (q, k, v)) + (g, beta), ct


def rel(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return np.abs(got - want).max() / np.abs(want).max()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("strong", [False, True], ids=["weak", "strong"])
@pytest.mark.parametrize("s", [150, 256])
def test_kernels_are_the_recurrence(as_on_the_chip, s, strong, dtype):
    """Two heads of 128 lanes in one [B,S,H*D] array, 150 = two chunks
    and 22 tokens: the output and all five gradients through
    ``kda_chunked`` on the kernels, against the recurrence and against
    the XLA form; the floor hits equal the XLA form's."""
    args, ct = kernel_inputs(s, s, strong, dtype)
    scale = 128 ** -0.5
    assert K.lowering(args[0], args[2], args[4]) == "pallas_chunked"
    every = tuple(range(5))
    loss = lambda f: lambda *a: jnp.sum(                 # noqa: E731
        f(*a).astype(jnp.float32) * ct.astype(jnp.float32))
    out, low = K.kda_chunked(*args, scale)
    got = jax.grad(loss(lambda *a: K.kda_chunked(*a, scale)[0]),
                   every)(*args)
    o_xla, low_xla = K._kda_forward(*args, scale)
    g_xla = K._kda_backward(*args, ct, scale)
    o_rec = K.kda_recurrence(*args, scale=scale)
    g_rec = jax.grad(loss(lambda *a: K.kda_recurrence(*a, scale=scale)),
                     every)(*args)
    assert out.shape == (1, s, 256) and out.dtype == dtype
    assert float(low) == float(low_xla) > 0
    # float32: rounding alone; bfloat16: the operands' own 2^-8
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    assert rel(out, o_rec) < tol and rel(out, o_xla) < tol
    for name, a, x, w in zip("qkvgb", got, g_xla, g_rec):
        assert a.dtype == w.dtype and a.shape == w.shape, name
        assert bool(jnp.all(jnp.isfinite(a.astype(jnp.float32)))), name
        assert rel(a, w) < 3 * tol, "d%s against the recurrence" % name
        assert rel(a, x) < 3 * tol, "d%s against the XLA form" % name


def test_a_head_reads_its_own_lanes(as_on_the_chip):
    """Another head's q, k, v, g and beta change nothing in this
    head's output or gradients, bit for bit."""
    args, ct = kernel_inputs(11, 150, True, jnp.float32)
    other = [x.at[..., x.shape[-1] // 2:].multiply(-1.7) for x in args]
    other[3] = args[3].at[..., 128:].multiply(0.3)

    def run(a):
        out, pull = jax.vjp(lambda *x: K.kda_chunked(*x, 0.1)[0], *a)
        return (out,) + pull(ct)
    for a, b in zip(run(args), run(other)):
        half = a.shape[-1] // 2
        assert np.array_equal(a[..., :half], b[..., :half])
        assert not np.array_equal(a[..., half:], b[..., half:])


@pytest.mark.parametrize("setting", ["state_bf16", "chunk_8_sub_4"])
def test_kernels_read_the_modules_constants(monkeypatch, setting):
    """As tests/benchmark_suite/fault_driver_kimi_linear.py plants its
    ``kda_carry_bf16``: ``_STATE_DTYPE`` (and at toy size ``_CHUNK``,
    ``_SUB``) set on the module before a site is traced."""
    args, ct = kernel_inputs(12, 150, False, jnp.float32)
    sound = K.kda_recurrence(*args, scale=0.1)
    if setting == "state_bf16":
        monkeypatch.setattr(K, "interpret_mode", lambda: False)
        plain = K.kda_chunked(*args, 0.1)[0]
        monkeypatch.setattr(K, "_STATE_DTYPE", jnp.bfloat16)
        out = K.kda_chunked(*args, 0.1)[0]
        starts, _ = KP._forward(*args, 0.1, K._CHUNK, K._SUB,
                                jnp.dtype(K._STATE_DTYPE), 0.0, False)
        assert starts.dtype == jnp.bfloat16
        # the carry's rounding shows, and is the XLA form's
        assert 1e-4 < rel(out, sound) < 2e-2 and rel(plain, sound) < 1e-5
        xla = K._kda_forward.__wrapped__(*args, 0.1)[0]
        assert rel(out, xla) < rel(out, sound)
        grads = jax.grad(lambda *a: jnp.sum(
            K.kda_chunked(*a, 0.1)[0] * ct), tuple(range(5)))(*args)
        assert all(bool(jnp.all(jnp.isfinite(x))) for x in grads)
    else:
        monkeypatch.setattr(K, "_CHUNK", 8)
        monkeypatch.setattr(K, "_SUB", 4)
        out, low = KP.kda_fwd(*args, 0.1, K._CHUNK, K._SUB,
                              K._STATE_DTYPE, K._FLOOR)
        _, low_xla = K._kda_forward.__wrapped__(*args, 0.1)
        assert rel(out, sound) < 2e-4
        got = KP.kda_bwd(*args, ct, 0.1, K._CHUNK, K._SUB, K._STATE_DTYPE)
        want = jax.grad(lambda *a: jnp.sum(
            K.kda_recurrence(*a, scale=0.1) * ct),
            tuple(range(5)))(*args)
        for a, w in zip(got, want):
            assert rel(a, w) < 6e-4
        # chunks of 8 reach the floor less often than chunks of 64
        assert float(low) == float(low_xla)
        assert float(low) < float(KP.kda_fwd(
            *args, 0.1, 64, 16, K._STATE_DTYPE, K._FLOOR)[1])


@pytest.mark.parametrize("c", [128, 384])
@pytest.mark.parametrize("k", [2, 4])
@pytest.mark.parametrize("b,s", [(1, 16), (2, 64), (1, 2080)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_short_conv_kernels_are_plain_autodiff(as_on_the_chip, small_tiles,
                                               dtype, b, s, k, c):
    """ops/pallas/kda_small.py's pair, interpreted, through the op, its
    tiles made small: one chunk of 16 rows, a tile of four, and 2,080
    rows = five tiles of 416 (a tile's first taps and last ``g`` come
    from its neighbours), one and three lane tiles; two rows of a batch
    do not meet."""
    KS = small_tiles(128)
    fn, definition, args, dy = _small_op_case(
        "short_conv", 43 + s + k, jnp.dtype(dtype), b, s, c, k)
    assert K._conv_lowering(*args) == "pallas"
    assert KS._conv_specs(args[0])[2] == (c // 128, b, -(-s // 512))
    _matches_plain_autodiff(fn, definition, args, dy)


@pytest.mark.parametrize("c,d", [(128, 128), (768, 128), (512, 256)])
@pytest.mark.parametrize("b,s", [(1, 16), (2, 64), (1, 2080)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gated_rms_norm_kernels_are_plain_autodiff(as_on_the_chip,
                                                   small_tiles, dtype, b,
                                                   s, c, d):
    """The norm's pair, interpreted, its tiles made small: one head,
    six heads in two lane tiles of three, two heads of 256 lanes in a
    tile each; ``dscale`` summed over 2,080 rows' five row tiles."""
    small_tiles(384)
    fn, definition, args, dy = _small_op_case(
        "gated_rms_norm", 47 + s, jnp.dtype(dtype), b, s, c, d=d)
    assert K._norm_lowering(args[0], args[2]) == "pallas"
    _matches_plain_autodiff(fn, definition, args, dy)


@pytest.mark.parametrize("op,b,s,c,k,d,took", [
    ("short_conv", 1, 64, 256, 4, None, "pallas"),
    ("short_conv", 1, 200, 256, 4, None, "xla"),    # no whole row tiles
    ("short_conv", 1, 64, 192, 4, None, "xla"),     # no whole lane groups
    ("short_conv", 1, 64, 256, 5, None, "xla"),     # more taps than tested
    ("gated_rms_norm", 2, 32, 256, None, 128, "pallas"),
    ("gated_rms_norm", 2, 32, 256, None, 64, "xla"),    # a head of 64 lanes
    ("gated_rms_norm", 2, 32, 768, None, 384, "xla"),   # a head of 384
    ("gated_rms_norm", 1, 40, 256, None, 128, "xla")])
def test_small_ops_lowering_follows_the_shapes(as_on_the_chip, op, b, s,
                                               c, k, d, took):
    """``<op>_lowering.pallas`` / ``.xla`` count the one a site took and
    list the other, as ``kda_lowering.*``; off the chip it is XLA's.
    The kernels take what the tests lower for the chip and no more."""
    fn, _, args, _ = _small_op_case(op, 53, jnp.float32, b, s, c, k,
                                    d=d or 64)
    names = ["%s_lowering.%s" % (op, path) for path in ("pallas", "xla")]
    before = [profiler.counter_values().get(n, 0.0) for n in names]
    fn(*args)
    after = [profiler.counter_values()[n] for n in names]
    assert [a - b_ for a, b_ in zip(after, before)] == \
        [float(took == "pallas"), float(took == "xla")]


@pytest.mark.parametrize("d,took", [(128, "pallas_chunked"),
                                    (64, "xla_chunked"),
                                    (256, "pallas_chunked")])
def test_the_lowering_follows_the_head_width(as_on_the_chip, d, took):
    """Whole groups of 128 lanes a head take the kernels; a head of 64
    takes the XLA form. ``kda_lowering.<path>`` counts the one taken
    and lists the other."""
    op = fluid.ops.get("kda_attention").fn
    q, k, v, g, beta = inputs(13, 1, 70, 2, d, d)
    names = ["kda_lowering.pallas_chunked", "kda_lowering.xla_chunked"]
    before = [profiler.counter_values().get(n, 0.0) for n in names]
    out, counters = op(q, k, v, g, beta, jnp.zeros((3,), jnp.float32),
                       scale=d ** -0.5)
    after = [profiler.counter_values()[n] for n in names]
    assert K.lowering(q, v, beta) == took
    assert [a - b for a, b in zip(after, before)] == \
        [float(took == n.split(".")[1]) for n in names]
    assert rel(out, K.kda_recurrence(q, k, v, g, beta,
                                     scale=d ** -0.5)) < 2e-4
    assert counters.tolist()[:2] == [70.0, 2.0]


def test_off_the_chip_the_lowering_is_xla():
    q, k, v, g, beta = inputs(14, 1, 64, 2, 128, 128)
    assert K.lowering(q, v, beta) == "xla_chunked"
    assert K._conv_lowering(q, jnp.ones((256, 4))) == "xla"
    assert K._norm_lowering(q, jnp.ones((128,))) == "xla"
    assert not KP.takes(128, 128, 8, 4) and KP.takes(128, 128, 64, 16)
