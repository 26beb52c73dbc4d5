"""ops/ssd_ops.py: the chunked SSD core against its definition
(``ssd_recurrence``, token by token), forward and every input's
gradient, with rows that are no whole number of chunks and decays whose
in-chunk sums pass -80; the op's counters and its lowering count; what
the custom VJP keeps. Then the same of its Mosaic kernels
(ops/pallas/ssd.py), interpreted, against the definition and the XLA
form, and which widths and backends take them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import profiler
from paddle_tpu.ops import ssd_ops as S

H, P, G, N = 4, 8, 2, 6


def operands(b=2, s=45, seed=0, strong=True):
    """x, dt, a_log, B, C, D, dt_bias. ``strong``: A up to 64 and steps
    up to about 0.2, so a chunk of 16 passes -80."""
    rs = np.random.RandomState(seed)
    draw = lambda *shape: jnp.asarray(rs.randn(*shape), jnp.float32)  # noqa: E731
    a = [1.0, 20.0, 40.0, 64.0] if strong else [1.0, 2.0, 3.0, 4.0]
    return (draw(b, s, H * P), draw(b, s, H),
            jnp.log(jnp.asarray(a, jnp.float32)), draw(b, s, G * N),
            draw(b, s, G * N), draw(H), draw(H) - 2.0)


NAMES = ("x", "dt", "a_log", "b", "c", "d", "dt_bias")


@pytest.mark.parametrize("s,chunk", [(45, 16), (64, 16), (7, 16),
                                     (130, 32)])
def test_chunked_is_the_recurrence_forward_and_backward(s, chunk):
    ops = operands(s=s)
    want = S.ssd_recurrence(*ops, n_groups=G)
    got, _ = S.ssd_chunked(*ops, G, chunk)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    ct = jnp.asarray(np.random.RandomState(1).randn(*want.shape),
                     jnp.float32)
    grads = jax.grad(lambda *a: jnp.sum(
        S.ssd_chunked(*a, G, chunk)[0] * ct), argnums=tuple(range(7)))(*ops)
    wants = jax.grad(lambda *a: jnp.sum(
        S.ssd_recurrence(*a, n_groups=G) * ct),
        argnums=tuple(range(7)))(*ops)
    for name, g, w in zip(NAMES, grads, wants):
        scale = float(jnp.max(jnp.abs(w)))
        assert float(jnp.max(jnp.abs(g - w))) <= 2e-5 * scale, name


def test_the_step_without_its_bias_and_weak_decays():
    x, dt, a_log, b, c, d, _ = operands(strong=False)
    dt = jax.nn.softplus(dt)
    want = S.ssd_recurrence(x, dt, a_log, b, c, d, None, n_groups=G)
    got, low = S.ssd_chunked(x, dt, a_log, b, c, d, None, G, 16)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert float(low) == 0.0


def test_decays_past_the_floor_are_counted_not_clamped():
    """Chunks of 16 at A up to 64 and steps up to about 0.2 pass -80: the
    elements under -80 are counted from the form's own cumulative sum,
    the padded tokens of the last chunk for nothing, and the output is
    the recurrence's still (no factor about the chunk's start could
    have held it)."""
    ops = operands(b=1, s=45)
    x, dt, a_log, b, c, d, dt_bias = ops
    step = np.asarray(jax.nn.softplus(dt + dt_bias))[0]       # [s, H]
    a = np.exp(np.asarray(a_log))
    want = 0
    for lo in range(0, 45, 16):
        acum = np.cumsum(-step[lo:lo + 16] * a, axis=0)
        want += int(np.sum(acum < -80.0))
    assert want > 5
    _, low = S.ssd_chunked(*ops, G, 16)
    assert float(low) == want


def test_the_op_counts_tokens_chunks_and_its_lowering():
    ops = operands(b=2, s=45)
    counters = jnp.zeros((len(S.COUNTER_NAMES),), jnp.float32)
    profiler.reset_counters()
    out, counted = S.ssd_scan(*ops, counters, n_groups=G, chunk_size=16)
    _, low = S.ssd_chunked(*ops, G, 16)
    assert out.shape == (2, 45, H * P)
    assert dict(zip(S.COUNTER_NAMES, np.asarray(counted).tolist())) == {
        "tokens_total": 90.0, "chunks_total": 6.0,
        "decay_floor_hits_total": float(low), "mosaic_tokens_total": 0.0}
    seen = profiler.counter_values()
    assert seen["ssd_lowering.xla_chunked"] == 1.0
    assert seen["ssd_lowering.pallas_chunked"] == 0.0


def test_bf16_inputs_give_bf16_out_with_float32_inside():
    ops = operands()
    low = [t.astype(jnp.bfloat16) if i in (0, 3, 4) else t
           for i, t in enumerate(ops)]
    got, _ = S.ssd_chunked(*low, G, 16)
    assert got.dtype == jnp.bfloat16
    want = S.ssd_recurrence(*low, n_groups=G)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    assert err <= 0.03 * float(jnp.max(jnp.abs(want)))


def test_the_backward_pass_keeps_the_ops_inputs_alone():
    ops = operands(s=64)
    _, pull = jax.vjp(lambda *a: S.ssd_chunked(*a, G, 16)[0], *ops)
    kept = {tuple(leaf.shape) for leaf in jax.tree_util.tree_leaves(pull)
            if hasattr(leaf, "shape")}
    assert kept <= {tuple(t.shape) for t in ops}, kept


# -- the Mosaic kernels (ops/pallas/ssd.py), interpreted --------------------

@pytest.fixture
def as_on_the_chip(monkeypatch):
    """``ssd_chunked`` chooses the kernels where ``interpret_mode()`` is
    false; the kernels' own copy of it stays true, so they are
    interpreted."""
    monkeypatch.setattr(S, "interpret_mode", lambda: False)


def wide(s=300, heads=4, width=64, groups=2, state=128, dtype=jnp.float32,
         bias=True, seed=0):
    """x, dt, a_log, B, C, D, dt_bias at widths the kernels take (a
    group's heads and the state whole 128-lane groups), A up to 64 and
    steps up to about 0.2, so chunks of 128 pass -80."""
    rs = np.random.RandomState(seed)
    draw = lambda *shape: jnp.asarray(rs.randn(*shape), jnp.float32)  # noqa: E731
    a = np.geomspace(1.0, 64.0, heads)
    x, b, c = (draw(1, s, n).astype(dtype)
               for n in (heads * width, groups * state, groups * state))
    dt = draw(1, s, heads)
    if not bias:            # the step as it comes: positive
        dt = jax.nn.softplus(dt - 2.0)
    return (x, dt, jnp.log(jnp.asarray(a, jnp.float32)), b * 0.3, c * 0.3,
            draw(heads), draw(heads) - 2.0 if bias else None)


def rel(got, want):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize("s,heads,width,groups,dtype,bias", [
    (300, 4, 64, 2, jnp.float32, True),     # a padded last chunk
    (256, 4, 64, 2, jnp.float32, False),    # dt_bias None
    (300, 4, 64, 2, jnp.bfloat16, True),    # as under AMP
    (200, 2, 128, 2, jnp.float32, True),    # a head a lane group
    (130, 8, 32, 2, jnp.float32, True),     # four heads a lane group
], ids=["padded", "no_bias", "bf16", "p128", "p32"])
def test_kernels_are_the_recurrence(as_on_the_chip, s, heads, width, groups,
                                    dtype, bias):
    """The output and all seven gradients through ``ssd_chunked`` on the
    kernels, against the recurrence and against the XLA form; the
    elements under -80 counted as the XLA form counts them."""
    ops = wide(s, heads, width, groups, dtype=dtype, bias=bias)
    assert S.lowering(ops[0], ops[2], ops[3], groups, 128) \
        == "pallas_chunked"
    y, low = S.ssd_chunked(*ops, groups, 128)
    y_xla, low_xla = S._ssd_forward(*ops, groups, 128)
    y_rec = S.ssd_recurrence(*ops, n_groups=groups)
    assert y.dtype == dtype and y.shape == y_rec.shape
    assert float(low) == float(low_xla) > 0
    ct = jnp.asarray(np.random.RandomState(1).randn(*y.shape), dtype)
    _, pull = jax.vjp(lambda *a: S.ssd_chunked(*a, groups, 128)[0], *ops)
    grads = pull(ct)
    grads_xla = S._ssd_backward(*ops, ct, groups, 128)
    _, pull_rec = jax.vjp(lambda *a: S.ssd_recurrence(*a, n_groups=groups),
                          *ops)
    grads_rec = pull_rec(ct.astype(jnp.float32))
    # float32: rounding alone (a strong head's gradient of A_log sums
    # terms of both signs whose exponents are differences of cumulative
    # sums near -800: both chunked forms read 2.4e-4 of the largest from
    # the recurrence in float64 there, the recurrence in float32 1e-6);
    # bfloat16: the operands' own 2^-8
    tol_xla, tol_rec = (2e-4, 5e-4) if dtype == jnp.float32 else \
        (2e-2, 3e-2)
    assert rel(y, y_xla) < tol_xla and rel(y, y_rec) < tol_rec
    for name, g, gx, gr in zip(NAMES, grads, grads_xla, grads_rec):
        if gr is None:
            assert g is None and not bias, name
            continue
        assert g.dtype == gx.dtype and g.shape == gx.shape, name
        assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32)))), name
        assert rel(g, gx) < tol_xla, "d%s against the XLA form" % name
        assert rel(g, gr) < tol_rec, "d%s against the recurrence" % name


def test_a_group_reads_its_own_lanes(as_on_the_chip):
    """Another group's x, B, C and steps change nothing in this group's
    output or gradients, bit for bit."""
    ops = wide(150)
    other = list(ops)
    for i in (0, 1, 3, 4):      # the second half of the lanes: group 2
        t = ops[i]
        other[i] = t.at[..., t.shape[-1] // 2:].multiply(-1.7)
    ct = jnp.asarray(np.random.RandomState(2).randn(1, 150, 256),
                     jnp.float32)

    def run(a):
        out, pull = jax.vjp(lambda *t: S.ssd_chunked(*t, 2, 128)[0], *a)
        return (out,) + pull(ct)
    for i, (a, b) in enumerate(zip(run(ops), run(other))):
        if a.ndim == 3:
            half = a.shape[-1] // 2
            assert np.array_equal(a[..., :half], b[..., :half]), i
            assert not np.array_equal(a[..., half:], b[..., half:]), i


def test_the_op_on_the_kernels_counts_its_tokens(as_on_the_chip):
    """``mosaic_tokens_total`` equals the tokens where the kernels ran;
    the lowering count says which."""
    ops = wide(300)
    counters = jnp.zeros((len(S.COUNTER_NAMES),), jnp.float32)
    profiler.reset_counters()
    _, counted = S.ssd_scan(*ops, counters, n_groups=2, chunk_size=128)
    _, low = S._ssd_forward(*ops, 2, 128)
    assert dict(zip(S.COUNTER_NAMES, np.asarray(counted).tolist())) == {
        "tokens_total": 300.0, "chunks_total": 3.0,
        "decay_floor_hits_total": float(low), "mosaic_tokens_total": 300.0}
    seen = profiler.counter_values()
    assert seen["ssd_lowering.pallas_chunked"] == 1.0
    assert seen["ssd_lowering.xla_chunked"] == 0.0


@pytest.mark.parametrize("heads,width,state,chunk,took", [
    (8, 64, 128, 128, True),      # the Nemotron-H cell's
    (2, 64, 128, 128, True),
    (1, 128, 256, 256, True),
    (1, 64, 128, 128, False),     # a group's heads half a lane group
    (8, 64, 96, 128, False),      # a state of 96 lanes
    (8, 64, 128, 64, False),      # a chunk of 64 tokens
    (8, 64, 128, 512, False),     # a chunk past 256
    (128, 128, 128, 128, False),  # a state of 8 MiB
])
def test_takes_follows_the_widths(heads, width, state, chunk, took):
    from paddle_tpu.ops.pallas import ssd as SP
    assert SP.takes(heads, width, state, chunk) is took


@pytest.mark.parametrize("chip", [False, True], ids=["cpu", "tpu"])
def test_the_lowering_follows_the_backend_and_the_shapes(monkeypatch, chip):
    """Off the TPU every site takes the XLA form; on it, a site whose
    widths the kernels take takes them, and the tests' toy widths
    (heads of 8 lanes, a state of 6) do not."""
    monkeypatch.setattr(S, "interpret_mode", lambda: not chip)
    ops = wide(16)
    assert S.lowering(ops[0], ops[2], ops[3], 2, 128) == (
        "pallas_chunked" if chip else "xla_chunked")
    toy = operands()
    assert S.lowering(toy[0], toy[2], toy[3], G, 16) == "xla_chunked"


def test_the_kernels_backward_keeps_the_ops_inputs_alone(as_on_the_chip):
    ops = wide(256)
    _, pull = jax.vjp(lambda *a: S.ssd_chunked(*a, 2, 128)[0], *ops)
    kept = {tuple(leaf.shape) for leaf in jax.tree_util.tree_leaves(pull)
            if hasattr(leaf, "shape")}
    assert kept <= {tuple(t.shape) for t in ops}, kept


def test_a_carry_left_out_of_the_kernels_is_the_planted_xla_fault(
        as_on_the_chip, monkeypatch):
    """tests/benchmark_suite/fault_driver_nemotron_h.py's
    ``state_not_carried`` hands the XLA form's scan noughts; the same
    fault in the kernels is their carry (the forward's state and the
    backward pass's gradient of it, the last ref: VMEM scratch) set to
    nought before each chunk. The two read alike, output and gradients,
    and neither is the sound output."""
    import importlib.util
    import os
    from paddle_tpu.ops.pallas import ssd as SP
    path = os.path.join(os.path.dirname(__file__), "benchmark_suite",
                        "fault_driver_nemotron_h.py")
    spec = importlib.util.spec_from_file_location("planted_faults", path)
    faults = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(faults)

    def no_carry(kernel):
        def each_chunk(*refs, **kw):
            refs[-1][...] = jnp.zeros(refs[-1].shape, refs[-1].dtype)
            return kernel(*refs, **kw)
        return each_chunk

    traced = (S._ssd_forward, S._ssd_backward, S._kernels_forward,
              S._kernels_backward)
    ops = wide(300)
    ct = jnp.asarray(np.random.RandomState(3).randn(1, 300, 256),
                     jnp.float32)
    sound = S.ssd_chunked(*ops, 2, 128)[0]
    monkeypatch.setattr(S, "lax", S.lax)
    for name in ("_fwd_kernel", "_bwd_kernel"):
        monkeypatch.setattr(SP, name, no_carry(getattr(SP, name)))
    try:
        for f in traced:
            f.clear_cache()
        faults.plant("state_not_carried")
        out, pull = jax.vjp(lambda *a: S.ssd_chunked(*a, 2, 128)[0], *ops)
        grads = pull(ct)
        out_xla = S._ssd_forward(*ops, 2, 128)[0]
        grads_xla = S._ssd_backward(*ops, ct, 2, 128)
    finally:
        monkeypatch.undo()
        for f in traced:
            f.clear_cache()
    assert rel(out, sound) > 1e-2
    assert rel(out, out_xla) < 2e-4
    for name, g, gx in zip(NAMES, grads, grads_xla):
        assert rel(g, gx) < 2e-4, name
