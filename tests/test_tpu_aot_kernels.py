"""The Mosaic kernels of the benchmark's cells (the flash 1k pair at
transformer-base's and BERT-base's sites, the blocked flash kernels
and the grouped products of the Trinity-Mini cell, the blocked flash
kernels at Kimi-Linear's latent-attention widths and its KDA core's
three kernels and two elementwise ops, the grouped products, the
expert layer and the rotary part at the Kanana-2 cell's), COMPILED for a v5e
that is described and not attached, at the cells' own shapes: what the
chip's compiler would refuse (a tile that does not align, more fast
memory than a kernel may use) is refused here, at no chip time.
Nothing runs, so nothing is said about results or times: the values
are tests/test_sdpa_window_gqa.py's and tests/test_grouped_matmul.py's
(interpreted), and tests/test_chip_kernels.py's on the chip.

The topology is described inside a fixture (never at import: one
process at a time may load the TPU's library), and everything that
compiles is in this one file.
"""

import os

import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import attention as A
from paddle_tpu.ops.pallas import grouped_matmul as G
from paddle_tpu.parallel import moe as M


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_the_chip(monkeypatch):
    """The kernels' TPU lowering, which they choose from
    ``interpret_mode()``: steered here, not by an option of theirs."""
    monkeypatch.setattr(A, "interpret_mode", lambda: False)
    monkeypatch.setattr(G, "interpret_mode", lambda: False)


def compiled(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    # as training runs: conftest's exact float32 products are for the
    # CPU, and Mosaic refuses that precision on bf16 operands
    with jax.default_matmul_precision("default"):
        return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("b,s,h,bias,causal", [
    (128, 256, 8, (128, 1, 256, 256), False),   # tfm_base_*: encoder
    (128, 256, 8, (128, 1, 256, 256), True),    # decoder self-attention
    (56, 512, 12, (56, 1, 1, 512), False),      # bert_base_s512_scan
])
def test_flash_1k_pair_at_the_cells_sites(one_chip, for_the_chip, b, s,
                                          h, bias, causal):
    """q, k, v and the output's gradient as the projections hold them,
    [b, s, h * 64] in bf16, the pad bias, dropout 0.1 from the TPU's
    generator: the forward and the one backward kernel, the heads
    picked out of the lanes inside (G = 8, the whole 512 lanes; G = 6
    of BERT's twelve heads in two q-blocks)."""
    bf = jnp.bfloat16
    x = ((b, s, h * 64), bf)

    def site(q_, k_, v_, g_, bias_):
        seed = jnp.asarray([3.0, 0.0], jnp.float32)
        out, pull = jax.vjp(
            lambda a, b_, c: A._sdpa_flash(a, b_, c, bias_, seed,
                                           64 ** -0.5, 0.1, causal, 0,
                                           h), q_, k_, v_)
        return out, pull(g_)

    c = compiled(site, one_chip, x, x, x, x, (bias, bf))
    text = c.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    # nothing of [b,h,s,64] around the calls: no head split or merge
    # is compiled
    assert "dimensions={0,2,1,3}" not in text
    assert c.memory_analysis().temp_size_in_bytes < 64 << 20


def _blocked_site(one_chip, q, kv, v, window=0):
    """One site's forward and backward, compiled: (its Mosaic calls,
    the bytes of its temporaries)."""
    bf = jnp.bfloat16

    def site(q_, k_, v_, g_):
        seed = jnp.zeros((2,), jnp.float32)
        out, pull = jax.vjp(
            lambda a, b, c: A._sdpa_flash(a, b, c, None, seed,
                                          q[3] ** -0.5, 0.0, True,
                                          window), q_, k_, v_)
        return out, pull(g_)

    c = compiled(site, one_chip, (q, bf), (kv, bf), (v, bf),
                 (q[:3] + v[3:], bf))
    return (c.as_text().count('custom_call_target="tpu_custom_call"'),
            c.memory_analysis().temp_size_in_bytes)


@pytest.mark.parametrize("window", [2048, 0])
def test_blocked_flash_at_the_cells_site(one_chip, for_the_chip, window):
    """32 q heads over 4 kv heads of 128, 8192 positions, bf16: the
    forward and the ONE backward kernel, K and V of a kv head (and the
    float32 sums of its dK and dV) resident in the 100 MB of VMEM the
    kernels ask for."""
    calls, temps = _blocked_site(one_chip, (1, 32, 8192, 128),
                                 (1, 4, 8192, 128), (1, 4, 8192, 128),
                                 window)
    assert calls == 2
    # out, lse, delta and the three gradients: nothing of S x S, and
    # no [BH, S, 128] statistics
    assert temps < 1 << 29


def test_blocked_flash_at_the_mla_site(one_chip, for_the_chip):
    """Latent attention as ``kimi_linear_s8k_scan`` and
    ``kanana2_s8k_scan`` run it: 32 heads, queries and keys 192 wide
    (128 + the 64 shared or rotary lanes) beside 128-wide values, 8192
    positions, bf16: the same two kernels with q, k, dq, dk blocks at
    one width and v, o, do, dv at the other."""
    calls, temps = _blocked_site(one_chip, (1, 32, 8192, 192),
                                 (1, 32, 8192, 192), (1, 32, 8192, 128))
    assert calls == 2
    assert temps < 1 << 30


def test_blocked_flash_at_32k_keys(one_chip, for_the_chip):
    """The fallback, compiled for the v5e too: at 32,768 keys K, V, dK,
    dV and the float32 sums of a head no longer fit together, so the
    backward is the dq kernel (K and V still resident) and its mirror
    image (a k-block's sums against a head's q, dO and statistics)."""
    assert not A._blocked_schedule(4, 4, 32768, 32768, 192, 128, 2).fused
    calls, _ = _blocked_site(one_chip, (1, 4, 32768, 192),
                             (1, 4, 32768, 192), (1, 4, 32768, 128))
    assert calls == 3


@pytest.mark.parametrize("budget_mb,resident", [(30, True), (8, False)])
@pytest.mark.parametrize("site", ["mla", "gqa_window"])
def test_blocked_flash_other_schedules(one_chip, for_the_chip,
                                       monkeypatch, fresh_traces, site,
                                       budget_mb, resident):
    """The schedules the cells' shapes do not take, at the cells'
    widths, steered by the model's budget alone: the split backward
    with K and V resident, and everything streamed in major blocks."""
    monkeypatch.setattr(A, "_BLOCKED_VMEM_BUDGET", budget_mb << 20)
    h, hkv, dqk, window = {"mla": (32, 32, 192, 0),
                           "gqa_window": (32, 4, 128, 2048)}[site]
    sched = A._blocked_schedule(h, hkv, 8192, 8192, dqk, 128, 2)
    assert not sched.fused and sched.kv_resident == resident
    calls, _ = _blocked_site(one_chip, (1, h, 8192, dqk),
                             (1, hkv, 8192, dqk), (1, hkv, 8192, 128),
                             window)
    assert calls == 3


def test_kda_core_at_the_cells_widths(one_chip):
    """The chunked delta rule as ``kimi_linear_s8k_scan`` runs it (32
    heads of 128, 8192 positions, q / k / v in bf16 and the log decay
    in float32), forward and backward: XLA's own lowering, no Mosaic
    call yet, and temporaries that leave the cell its room (one site's
    forward + backward: 1.2 GiB on the chip, my chip run, PR 32)."""
    from paddle_tpu.ops import kda_ops as K
    bf, wide = jnp.bfloat16, (1, 8192, 32 * 128)

    def site(q, k, v, g, beta, ct):
        out, pull = jax.vjp(
            lambda *a: K.kda_chunked(*a, 128 ** -0.5)[0],
            q, k, v, g, beta)
        return out, pull(ct)

    c = compiled(site, one_chip, (wide, bf), (wide, bf), (wide, bf),
                 (wide, jnp.float32), ((1, 8192, 32), bf), (wide, bf))
    assert 'custom_call_target="tpu_custom_call"' not in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 3 << 29


def test_kda_kernels_at_the_cells_widths(one_chip, monkeypatch):
    """The same site as the chip lowers it (``interpret_mode()`` false
    in the op's module and in the kernels'): three Mosaic calls (the
    forward kernel, its state pass again for the backward pass, the
    reverse pass), and for temporaries the chunk-start states and T in
    float32 (256 + 64 MiB) beside the reverse pass's outputs."""
    from paddle_tpu.ops import kda_ops as K
    from paddle_tpu.ops.pallas import kda as KP
    monkeypatch.setattr(K, "interpret_mode", lambda: False)
    monkeypatch.setattr(KP, "interpret_mode", lambda: False)
    bf, wide = jnp.bfloat16, (1, 8192, 32 * 128)

    def site(q, k, v, g, beta, ct):
        out, pull = jax.vjp(
            lambda *a: K.kda_chunked(*a, 128 ** -0.5)[0],
            q, k, v, g, beta)
        return out, pull(ct)

    c = compiled(site, one_chip, (wide, bf), (wide, bf), (wide, bf),
                 (wide, jnp.float32), ((1, 8192, 32), bf), (wide, bf))
    assert c.as_text().count('custom_call_target="tpu_custom_call"') == 3
    assert c.memory_analysis().temp_size_in_bytes < 1 << 29


@pytest.mark.parametrize("e,k,n", [(8, 2048, 1024), (8, 1024, 2048),
                                   (16, 2048, 768), (16, 768, 2048)])
def test_grouped_products_at_the_cells_widths(one_chip, for_the_chip,
                                              e, k, n):
    """8 held experts of width 1024 (Trinity-Mini's and Kimi-Linear's
    cells) and 16 of width 768 (Kanana-2's), one chunk of the cell's
    row buffer (12,288 and 16,384 rows): gmm forward, gmm for the rows' gradient, tgmm adding
    the matrices' to a float32 sum (its output block and the sum's,
    both float32, in VMEM)."""
    bf = jnp.bfloat16
    rows = M._chunk_rows(8192, 8192 * (8 if e == 8 else 6), e)

    def product(lhs, rhs, sizes, g):
        out, pull = jax.vjp(
            lambda a, b: G.grouped_matmul(a, b, sizes), lhs, rhs)
        return out, pull(g)

    c = compiled(product, one_chip, ((rows, k), bf), ((e, k, n), bf),
                 ((e,), jnp.int32), ((rows, n), bf))
    assert c.as_text().count('custom_call_target="tpu_custom_call"') == 3


@pytest.mark.parametrize("f,e,k", [(1024, 8, 8), (768, 16, 6)])
def test_held_experts_layer_at_the_cells_widths(one_chip, for_the_chip,
                                                f, e, k):
    """A cell's expert layer as the executor lowers it (forward op,
    then the op under ``jax.vjp``), at Trinity-Mini's load (8 held of
    width 1024 under top-8) and at Kanana-2's (16 of width 768 under
    top-6): three products in the forward chunk loop, eight in the
    backward one (two made again, three pullbacks of two), and no
    third loop: the differentiated forward is gone from what the chip
    would run."""
    bf, t, d = jnp.bfloat16, 8192, 2048

    def layer(x, sel, w, w_gate, w_up, w_down, g):
        def held(x, w, w_gate, w_up, w_down):
            return M.held_experts_ffn(x, sel, w, w_gate, w_up, w_down,
                                      first_held=e, row_capacity=t * k)[0]
        out = held(x, w, w_gate, w_up, w_down)
        return out, jax.vjp(held, x, w, w_gate, w_up, w_down)[1](g)

    c = compiled(layer, one_chip, ((t, d), bf), ((t, k), jnp.int32),
                 ((t, k), jnp.float32), ((e, d, f), bf), ((e, d, f), bf),
                 ((e, f, d), bf), ((t, d), bf))
    text = c.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 11
    loops = [ln for ln in text.splitlines() if " while(" in ln
             and "f32[%d,%d]" % (t, d) in ln.split(" while(")[0]]
    assert len(loops) == 2
    # nothing of the buffer's t x k rows times a width
    assert c.memory_analysis().temp_size_in_bytes < 1 << 30


def test_rotary_part_at_the_mla_site(one_chip):
    """The decoupled rotary part as ``kanana2_s8k_scan`` runs it: lanes
    128..191 of 32 query heads and the ONE 64-lane key vector a token,
    interleaved pairs, 8192 positions, bf16, forward and gradient: the
    partners come from a product with a signed permutation fused with
    the turn, so nothing wider than the operands is kept (a roll of
    the lanes kept 770 MB of float32 slices here)."""
    from paddle_tpu.ops import nn_ops
    bf = jnp.bfloat16
    q, k = (1, 32, 8192, 192), (1, 1, 8192, 64)

    def site(q_, k_, gq, gk):
        out, pull = jax.vjp(
            lambda a, b: (
                nn_ops.rotary_embedding(a, theta=1e6, start=128, width=64,
                                        interleaved=True),
                nn_ops.rotary_embedding(b, theta=1e6, interleaved=True)),
            q_, k_)
        return out, pull((gq, gk))

    c = compiled(site, one_chip, (q, bf), (k, bf), (q, bf), (k, bf))
    assert c.memory_analysis().temp_size_in_bytes < 1 << 27


def _kda_small_site(one_chip, op):
    """A KDA site's short convolution (4 taps) or gated norm (32 heads
    of 128 lanes) at the cell's ``[1,8192,4096]`` in bf16, forward +
    ``jax.vjp`` backward, compiled."""
    from paddle_tpu.ops import kda_ops as K
    bf, wide = jnp.bfloat16, (1, 8192, 4096)
    if op == "short_conv":
        fn, extra = K.short_conv, [((4096, 4), bf)]
    else:
        fn = lambda *a: K.gated_rms_norm(*a, epsilon=1e-5)  # noqa: E731
        extra = [(wide, bf), ((128,), bf)]

    def site(dy, *ins):
        out, pull = jax.vjp(fn, *ins)
        return out, pull(dy)

    return compiled(site, one_chip, (wide, bf), (wide, bf), *extra)


@pytest.mark.parametrize("op", ["short_conv", "gated_rms_norm"])
def test_kda_small_kernels_at_the_cells_widths(one_chip, monkeypatch, op):
    """As the chip lowers them (``interpret_mode()`` false in the op's
    module and in the kernels'): one Mosaic call each way, and no
    temporary of x's size (``dw`` / ``dscale`` as eight sublanes of
    partial sums a lane)."""
    from paddle_tpu.ops import kda_ops as K
    from paddle_tpu.ops.pallas import kda_small as KS
    monkeypatch.setattr(K, "interpret_mode", lambda: False)
    monkeypatch.setattr(KS, "interpret_mode", lambda: False)
    wrappers = (KS.conv_fwd, KS.conv_bwd, KS.norm_fwd, KS.norm_bwd)
    for f in wrappers:          # jitted on the shapes alone
        f.clear_cache()
    try:
        c = _kda_small_site(one_chip, op)
    finally:
        for f in wrappers:
            f.clear_cache()
    assert c.as_text().count('custom_call_target="tpu_custom_call"') == 2
    assert c.memory_analysis().temp_size_in_bytes < 1 << 20


def test_memory_plane_of_a_small_step(one_chip):
    """The memory plane against the TPU's compiler: a small AMP + Adam
    step, built as the executor builds it (``engine.build_step`` under
    ``run_repeated``'s scan, the state donated), compiled for the
    described v5e. Its record counts temporaries, and
    ``profiler.memory_table`` reads the scheduled text the TPU compiler
    prints (tiles, memory spaces, asynchronous copies) into a peak that
    can be set against them."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import compile_cache, profiler
    from paddle_tpu.contrib import mixed_precision as amp
    from paddle_tpu.engine import build_repeat_fn, build_step

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[1024], dtype="float32")
            y = fluid.layers.data("y", shape=[1], dtype="float32")
            # activations of 128 MB and more: past what VMEM holds
            h = fluid.layers.fc(x, 4096, act="relu")
            h = fluid.layers.fc(h, 4096, act="relu")
            loss = fluid.layers.mean(fluid.layers.square_error_cost(
                fluid.layers.fc(h, 1), y))
            amp.decorate(fluid.optimizer.AdamOptimizer(1e-3)).minimize(
                loss)
    block = main.global_block()
    made = {n for op in startup.global_block().ops
            for names in op.outputs.values() for n in names}
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(
        tuple(shape), dtype, sharding=one_chip)
    persist = {n: sds(v.shape, np.dtype(v.dtype))
               for n, v in block.vars.items()
               if v.persistable and n in made}
    feed = {"x": sds((16384, 1024), jnp.float32),
            "y": sds((16384, 1), jnp.float32)}
    key = jax.eval_shape(lambda: jax.random.key(0))
    step = build_step(main, block, [loss.name],
                      carried=frozenset(persist))
    with jax.default_matmul_precision("default"):
        c = jax.jit(build_repeat_fn(step, 4), donate_argnums=(0,)).lower(
            persist, feed, sds(key.shape, key.dtype)).compile()
    record = compile_cache.memory_record(c)
    assert record["temp_bytes"] > 0
    state = sum(int(np.prod(v.shape)) * v.dtype.itemsize
                for v in persist.values())
    assert record["alias_bytes"] >= state       # the state is donated
    table = profiler.memory_table(c.as_text(), record["temp_bytes"])
    assert table["peak_bytes"] > 0 and table["scope"]
    assert 0.0 < table["coverage"] < float("inf")
    for by in ("by_phase", "by_layer", "by_layer_op"):
        assert sum(table[by].values()) == table["peak_bytes"]
    print(profiler.format_memory_table(table))
