"""parallel/moe.py ``held_experts_ffn`` walks its sorted rows in chunks
up to the rows the router sent: the output and every gradient against
the reference's plain loop over the experts
(benchmark/reference/trinity_mini_ep16.py ``held_experts``) at loads
around the chunk's ends, the counters of one call by hand, and the
lowering (each chunk loop once, the op's inputs alone kept for the
backward pass)."""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import grouped_matmul as gmm
from paddle_tpu.parallel import moe as moe_lib

ref = importlib.import_module("benchmark.reference.trinity_mini_ep16")

K, D, F, N_HELD, FIRST, WIDTH = 6, 16, 8, 3, 5, 16
T = 200
R = moe_lib._chunk_rows(T, T * K)
assert T * K >= 3 * R       # assignments enough for three chunks


def operands(n_rows, seed=0):
    """A layer whose router sent exactly ``n_rows`` assignments to the
    three held experts, in unequal shares."""
    rs = np.random.RandomState(seed)
    draw = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32)  # noqa: E731
    others = np.setdiff1d(np.arange(WIDTH), FIRST + np.arange(N_HELD))
    sel = rs.choice(others, size=T * K)
    held = rs.permutation(T * K)[:n_rows]
    sel[held] = FIRST + rs.choice(N_HELD, size=n_rows, p=[0.5, 0.2, 0.3])
    return dict(
        x=draw(T, D), sel=jnp.asarray(sel.reshape(T, K), jnp.int32),
        weight=jnp.asarray(rs.rand(T, K), jnp.float32),
        w_gate=draw(N_HELD, D, F) * 0.3, w_up=draw(N_HELD, D, F) * 0.3,
        w_down=draw(N_HELD, F, D) * 0.3, t=draw(T, D))


def reference(o):
    cfg = {"num_experts": N_HELD, "first_held_expert": FIRST}
    p = {"l.w_gate": o["w_gate"], "l.w_up": o["w_up"],
         "l.w_down": o["w_down"]}
    return ref.held_experts(o["x"], o["sel"], o["weight"], p, "l", cfg,
                            "f32")


DIFF = ("x", "weight", "w_gate", "w_up", "w_down")


def both_ways(o, row_capacity):
    """(out, counts, gradients) of the chunked layer, (out, gradients)
    of the reference: the gradients of sum(out * t) in every float
    input."""
    def ours(*diff):
        a = dict(o, **dict(zip(DIFF, diff)))
        out, *counts = moe_lib.held_experts_ffn(
            a["x"], a["sel"], a["weight"], a["w_gate"], a["w_up"],
            a["w_down"], first_held=FIRST, row_capacity=row_capacity)
        return jnp.sum(out * o["t"]), (out, counts)

    def plain(*diff):
        out = reference(dict(o, **dict(zip(DIFF, diff))))
        return jnp.sum(out * o["t"]), out

    args = [o[n] for n in DIFF]
    over = tuple(range(len(DIFF)))
    (_, (out, counts)), grads = jax.jit(
        jax.value_and_grad(ours, over, has_aux=True))(*args)
    (_, want), want_grads = jax.jit(
        jax.value_and_grad(plain, over, has_aux=True))(*args)
    return out, [float(c) for c in counts], grads, want, want_grads


# rows the router sent, the buffer's rows (0: one for every assignment)
LOADS = {
    "no_rows": (0, 0),
    "under_a_chunk": (R - 57, 0),
    "a_chunk_exactly": (R, 0),
    "a_chunk_and_a_row": (R + 1, 0),
    "three_chunks_groups_split": (2 * R + 37, 0),
    "every_assignment_held": (T * K, 0),
    "a_buffer_exactly_full": (R + 200, R + 200),
    "a_roomy_buffer": (R + 200, 2 * R + 64),
}


@pytest.mark.parametrize("case", sorted(LOADS))
def test_output_and_every_gradient_against_the_plain_loop(case):
    n_rows, row_capacity = LOADS[case]
    o = operands(n_rows)
    sizes = np.bincount(np.asarray(o["sel"]).reshape(-1) - FIRST + WIDTH,
                        minlength=2 * WIDTH)[WIDTH:WIDTH + N_HELD]
    assert sizes.sum() == n_rows
    if case == "three_chunks_groups_split":
        # no expert's rows end where a chunk ends: groups are split
        assert not set(np.cumsum(sizes)) & {R, 2 * R}
    out, counts, grads, want, want_grads = both_ways(o, row_capacity)
    # float32 both; the sums run in another order
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    for name, g, w in zip(DIFF, grads, want_grads):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=5e-5,
                                   err_msg=name)
    chunk = moe_lib._chunk_rows(T, row_capacity or T * K)
    computed, passed, over = counts
    assert over == 0
    assert passed == -(-n_rows // chunk) * chunk
    assert computed == float(gmm.tile_rounded_rows(jnp.asarray(sizes)))


def test_overflow_is_nan_throughout_and_counted():
    o = operands(R + 200)
    out, counts, _, _, _ = both_ways(o, R + 100)
    assert np.isnan(np.asarray(out)).all()
    assert counts[2] == 100
    # what could be addressed was walked, and no more
    chunk = moe_lib._chunk_rows(T, R + 100)
    assert counts[1] == -(-(R + 100) // chunk) * chunk


def test_chunk_rows_are_whole_tiles_from_the_shapes_alone():
    for tokens, cap in [(8192, 65536), (8192, 8192), (48, 192), (48, 8),
                        (T, T * K), (300, 77)]:
        rows = moe_lib._chunk_rows(tokens, cap)
        assert rows > 0 and rows % gmm.TILE_M == 0
        # never more than the buffer rounded to whole tiles
        assert rows <= -(-cap // gmm.TILE_M) * gmm.TILE_M
    # the benchmark's three expert layers: 8 held under top-8 (two
    # cells) keep the chunk they were swept at, 16 held under top-6
    # take an eighth of the tokens for every expert held
    assert moe_lib._chunk_rows(8192, 65536, 8) == 12288
    assert moe_lib._chunk_rows(8192, 49152, 16) == 16384
    assert moe_lib._chunk_rows(8192, 49152, 12) == 12288


def layer_and_pullback(o, row_capacity=0):
    """What the executor lowers for a differentiated op: the forward
    op, then the op again under ``jax.vjp`` for its pullback."""
    counters = jnp.zeros((len(moe_lib.COUNTER_NAMES),), jnp.float32)

    def op(x, weight, w_gate, w_up, w_down):
        return moe_lib.moe_held_experts_op(
            x, o["sel"], weight, w_gate, w_up, w_down, counters,
            first_held=FIRST, row_capacity=row_capacity)

    def step(*diff):
        out, counted = op(*diff)
        _, pull = jax.vjp(op, *diff)
        return out, counted, pull((o["t"], jnp.zeros_like(counted)))

    return op, step, [o[n] for n in DIFF]


def test_each_chunk_loop_is_lowered_once():
    """Forward op + vjp op hold two loops over a carried [T, D] float32
    (forward, backward): the differentiated forward feeds nothing, its
    residuals being the op's inputs, and is dropped."""
    _, step, args = layer_and_pullback(operands(R + 1))
    text = jax.jit(step).lower(*args).as_text()
    carried = "tensor<%dx%dxf32>" % (T, D)
    loops = [m for m in re.finditer(r"stablehlo\.while.*", text)
             if carried in m.group(0)]
    assert len(loops) == 2, [m.group(0)[:200] for m in loops]


def test_the_backward_pass_keeps_the_ops_inputs_alone():
    o = operands(R + 1)
    op, _, args = layer_and_pullback(o)
    _, pull = jax.vjp(op, *args)
    kept = {tuple(leaf.shape) for leaf in jax.tree_util.tree_leaves(pull)
            if hasattr(leaf, "shape")}
    # vectors of the buffer's length (its rows' tokens, weights and
    # order) aside, every array kept has the shape of an input: nothing
    # of the buffer's length, or a chunk's, times a width
    chunks = -(-T * K // R)
    wide = {s for s in kept if len(s) >= 2 and s[-1] > 1} - {(chunks, R)}
    assert wide <= {tuple(a.shape) for a in args} | {(T, K)}, wide
