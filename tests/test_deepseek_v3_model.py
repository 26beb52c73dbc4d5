"""models.deepseek_v3 against benchmark/reference/kanana2_ep8.py at a
small size on the CPU, ELEMENT-WISE: the parameters, the loss and every
gradient in float32 and under bf16 AMP, the rotary part (left out, or
turned per head at another position, or with bf16 angles: each fails),
a bf16 router (fails), ``run`` against ``run_repeated``, the cut to a
share of the experts tied to the uncut layer, and the one latent-
attention mixer this model shares with ``models.kimi_linear``."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.contrib import mixed_precision as amp
from paddle_tpu.models import afmoe
from paddle_tpu.models import deepseek_v3 as DS
from paddle_tpu.models import kimi_linear as KL
from paddle_tpu.models import mla
from paddle_tpu.ops import registry
from paddle_tpu.parallel import moe as moe_lib

ref = importlib.import_module("benchmark.reference.kanana2_ep8")
trinity = importlib.import_module("benchmark.reference.trinity_mini_ep16")
common = importlib.import_module("benchmark.reference.common")

CFG = dict(vocab_size=97, hidden_size=32, num_hidden_layers=3,
           first_k_dense_replace=1, num_attention_heads=4,
           kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
           v_head_dim=8, intermediate_size=48, moe_intermediate_size=16,
           n_routed_experts=4, num_experts_published=16,
           first_held_expert=4, n_shared_experts=2, num_experts_per_tok=3,
           norm_topk_prob=True, routed_scaling_factor=2.448,
           rope_theta=1000000.0, rope_interleave=True, rms_norm_eps=1e-6,
           load_balance_coeff=0.001, moe_row_capacity=None, seq_len=48,
           initializer_range=0.1)
BENCH_ONLY = ("initializer_range",)
BATCH = 2
ADAM = dict(learning_rate=3e-3, beta1=0.9, beta2=0.95, epsilon=1e-8)


def _batch(seed=0):
    rs = np.random.RandomState(seed)
    s = CFG["seq_len"]
    mask = np.ones((BATCH, s), np.float32)
    mask[1, 41:] = 0.0
    return {"ids": rs.randint(0, CFG["vocab_size"], (BATCH, s)),
            "labels": rs.randint(0, CFG["vocab_size"], (BATCH, s)),
            "mask": mask}


def _program(cfg, optimizer=None):
    takes = {k: v for k, v in cfg.items() if k not in BENCH_ONLY}
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            loss, _ = DS.deepseek_v3_lm(DS.DeepseekV3Config(**takes))
            if optimizer is None:
                pg = fluid.append_backward(loss)
            else:
                optimizer.minimize(loss)
                pg = None
    return main, startup, loss, pg


def _seeded(scope, cfg, seed=7):
    for n, v in common.init_params(ref.param_spec(cfg), seed).items():
        scope.set_var(n, v)
    return common.init_params(ref.param_spec(cfg), seed)


def _reference(params, batch):
    ref.param_spec(CFG)
    norm = ref.normalizers(batch)
    rows = {k: jnp.asarray(v) for k, v in batch.items()}
    return jax.value_and_grad(ref.block_loss)(
        params, rows, norm, None, CFG, "f32")


def _float32_run():
    """(loss, {parameter: gradient}) of the program without AMP."""
    main, startup, loss, pg = _program(CFG)
    scope, exe = fluid.Scope(), fluid.Executor()
    batch = _batch()
    with fluid.scope_guard(scope):
        exe.run(startup)
        params = _seeded(scope, CFG)
        out = exe.run(main, feed=batch,
                      fetch_list=[loss] + [g for _, g in pg])
    return (float(np.asarray(out[0]).reshape(-1)[0]),
            {p.name: np.asarray(g) for (p, _), g in zip(pg, out[1:])},
            params, batch)


def _worst_gap(got, want):
    """Widest element-wise gap of any leaf, over that leaf's largest
    reference gradient."""
    gaps = {}
    for n, g in want.items():
        scale = float(jnp.max(jnp.abs(g)))
        assert scale > 0, n
        gaps[n] = float(np.max(np.abs(got[n] - np.asarray(g)))) / scale
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


# float32 on both sides leaves the order of the sums (XLA's attention
# chain against the reference's row blocks, ragged_dot against a loop
# over the experts, the rotation by lane rolls against a product with a
# signed permutation): the widest leaf read 1e-6 of its largest
# gradient, so 5e-5; bf16 angles read 1.8e-3, a bf16 router 2.7e-3
F32_TOL = 5e-5


def test_parameters_are_the_references():
    main, _, _, _ = _program(CFG)
    got = {p.name: tuple(p.shape) for p in main.all_parameters()}
    want = {n: tuple(s) for n, s, _ in ref.param_spec(CFG)}
    assert got == want


def test_loss_and_every_gradient_match_the_reference():
    loss, grads, params, batch = _float32_run()
    want, want_grads = _reference(params, batch)
    np.testing.assert_allclose(loss, want, rtol=5e-6)
    assert sorted(grads) == sorted(want_grads)
    gap, leaf = _worst_gap(grads, want_grads)
    assert gap < F32_TOL, (leaf, gap)


def test_bf16_amp_follows_the_reference_element_wise():
    """Under bf16 AMP, as the cell trains: Adam's first moment after
    one step is 0.1 x the gradient as the optimizer got it (unscaled),
    element by element against the float32 reference. Every matrix
    product reads bf16 operands (2^-9 a rounding, a few dozen of them
    between a weight and the loss), the router and the rotation's
    angles stay float32: the widest leaf (a router's matrix) read 2.9%
    of its largest element, so 8%; the rotary part left out reads 110%
    here, and bf16 angles 44% (one token's last expert flips, which a
    32-wide model shows element-wise at once)."""
    opt = amp.decorate(fluid.optimizer.Adam(**ADAM),
                       dest_dtype="bfloat16")
    main, startup, loss, _ = _program(CFG, opt)
    scope, exe = fluid.Scope(), fluid.Executor()
    batch = _batch()
    with fluid.scope_guard(scope):
        exe.run(startup)
        params = _seeded(scope, CFG)
        out, = exe.run(main, feed=batch, fetch_list=[loss])
        names = scope.local_var_names()
        m1 = {}
        for n in params:
            var, = [v for v in names if v.startswith(n + "_moment1_")]
            m1[n] = np.asarray(scope.find_var(var)) / (1 - ADAM["beta1"])
    want, want_grads = _reference(params, batch)
    np.testing.assert_allclose(np.asarray(out).reshape(-1)[0], want,
                               rtol=2e-3)
    gap, leaf = _worst_gap(m1, want_grads)
    assert gap < 0.08, (leaf, gap)


def _rotation_again(x, theta, start, width, angle_dtype):
    """The op's mathematics written again for the faults: interleaved
    pairs of lanes [start, start + width), angles in ``angle_dtype``."""
    s = x.shape[-2]
    i = jnp.arange(width // 2, dtype=jnp.float32)
    ang = (jnp.arange(s, dtype=jnp.float32)[:, None]
           * theta ** (-2.0 * i / width)[None, :])
    ang = ang.astype(angle_dtype).astype(jnp.float32)
    part = x[..., start:start + width]
    a, b = part[..., 0::2], part[..., 1::2]
    turned = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                        b * jnp.cos(ang) + a * jnp.sin(ang)],
                       -1).reshape(part.shape)
    return jnp.concatenate([x[..., :start], turned,
                            x[..., start + width:]], -1)


def _plant(monkeypatch, fault):
    if fault == "rotary_left_out":
        monkeypatch.setattr(layers, "rotary_embedding",
                            lambda x, **kw: x)
    elif fault == "key_turned_per_head":
        # the shared key vector spread over the heads FIRST and then
        # turned head by head, every head but the first at twice its
        # position (the rotation applied again)
        turn = layers.rotary_embedding

        def per_head(x, times, name=None):
            again = turn(x, theta=CFG["rope_theta"], interleaved=True)
            return layers.concat([x] + [again] * (times[1] - 1), axis=1)
        monkeypatch.setattr(layers, "expand", per_head)
    elif fault == "bf16_angles":
        def low(x, *, theta, start=0, width=0, interleaved=False):
            assert interleaved
            return _rotation_again(
                x, theta, start, width or x.shape[-1] - start,
                jnp.bfloat16)
        monkeypatch.setattr(registry.get("rotary_embedding"), "fn", low)
    elif fault == "bf16_router":
        route = moe_lib.sigmoid_topk_route

        def low(x, router_w, bias, **kw):
            return route(x.astype(jnp.bfloat16),
                         router_w.astype(jnp.bfloat16), bias, **kw)
        monkeypatch.setattr(moe_lib, "sigmoid_topk_route", low)
    else:
        assert fault == "float32_angles"
        # the control of the planting itself: the op written again
        # with float32 angles passes
        monkeypatch.setattr(
            registry.get("rotary_embedding"), "fn",
            lambda x, *, theta, start=0, width=0, interleaved=False:
            _rotation_again(x, theta, start,
                                  width or x.shape[-1] - start,
                                  jnp.float32))


@pytest.mark.parametrize("fault", [
    "rotary_left_out", "key_turned_per_head", "bf16_angles",
    "bf16_router"])
def test_a_planted_fault_fails_the_float32_tolerance(monkeypatch, fault):
    """Positions are in effect, and the two float32 islands of the
    bf16 step are held to float32: the rotary part left out, the shared
    key turned per head at another position, the angles rounded to
    bfloat16 (8 bits: position 37 x frequency 0.0316 is off by 0.002
    rad) and the router's product read in bfloat16 each move some
    gradient by more than ``F32_TOL`` of its largest element."""
    _plant(monkeypatch, fault)
    loss, grads, params, batch = _float32_run()
    want, want_grads = _reference(params, batch)
    gap, leaf = _worst_gap(grads, want_grads)
    assert gap > 10 * F32_TOL, (fault, leaf, gap)
    if fault in ("rotary_left_out", "key_turned_per_head"):
        assert abs(loss / float(want) - 1.0) > 1e-4
        assert gap > 0.3


def test_the_planting_itself_is_sound(monkeypatch):
    _plant(monkeypatch, "float32_angles")
    _, grads, params, batch = _float32_run()
    _, want_grads = _reference(params, batch)
    gap, leaf = _worst_gap(grads, want_grads)
    assert gap < F32_TOL, (leaf, gap)


def test_layer_pattern_and_what_the_config_refuses():
    main, _, _, _ = _program(CFG)
    ops = [op.type for op in main.global_block().ops]
    n = CFG["num_hidden_layers"]
    assert ops.count("scaled_dot_product_attention") == n
    assert ops.count("rotary_embedding") == 2 * n      # q, and ONE key
    assert ops.count("moe_held_experts") == n - 1
    rot = [op for op in main.global_block().ops
           if op.type == "rotary_embedding"]
    # the queries' last 8 of 16 lanes; the shared key whole, BEFORE it
    # is spread over the heads
    assert [(op.attrs["start"], op.attrs["width"],
             op.attrs["interleaved"]) for op in rot[:2]] \
        == [(8, 8, True), (0, 0, True)]
    key_in = main.global_block().var(rot[1].inputs["X"][0])
    assert tuple(key_in.shape)[1:] == (1, CFG["seq_len"], 8)
    # the published sizes are the defaults
    c = DS.DeepseekV3Config()
    assert (c.hidden_size, c.num_attention_heads, c.kv_lora_rank,
            c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim,
            c.intermediate_size, c.moe_intermediate_size,
            c.n_routed_experts, c.n_shared_experts, c.num_experts_per_tok,
            c.routed_scaling_factor, c.rope_theta, c.rope_interleave,
            c.num_hidden_layers, c.vocab_size) \
        == (2048, 32, 512, 128, 64, 128, 6144, 768, 128, 2, 6, 2.448,
            1e6, True, 48, 128256)
    for kw, word in [(dict(q_lora_rank=1536), "q_lora_rank"),
                     (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
                     (dict(n_group=8, topk_group=4), "n_group"),
                     (dict(scoring_func="softmax"), "scoring_func"),
                     (dict(topk_method="greedy"), "topk_method")]:
        with pytest.raises(ValueError, match=word):
            DS.DeepseekV3Config(**kw)
    with pytest.raises(ValueError, match="experts 120..135 of 128"):
        DS.DeepseekV3Config(n_routed_experts=16,
                            num_experts_published=128,
                            first_held_expert=120)


def test_one_latent_attention_for_both_models():
    """``models/mla.py latent_attention`` is what both models build:
    Kimi Linear's (NoPE) holds no rotation and the ops it held before
    the move, this model's the same ops with two rotations between the
    projections and the spread of the shared key."""
    assert KL.latent_attention is mla.latent_attention
    assert DS.latent_attention is mla.latent_attention

    def mixer_ops(cfg):
        main = fluid.Program()
        with fluid.unique_name.guard():
            with fluid.program_guard(main, fluid.Program()):
                a = layers.data("a", shape=[cfg.seq_len, cfg.hidden_size],
                                dtype="float32")
                mla.latent_attention(a, cfg, "m")
        return [op.type for op in main.global_block().ops]

    takes = {k: v for k, v in CFG.items() if k not in BENCH_ONLY}
    nope = KL.KimiLinearConfig(
        hidden_size=32, num_attention_heads=4, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, seq_len=48)
    assert nope.mla_use_nope and not DS.DeepseekV3Config.mla_use_nope
    plain = mixer_ops(nope)
    turned = mixer_ops(DS.DeepseekV3Config(**takes))
    assert "rotary_embedding" not in plain
    assert plain == ["mul", "reshape2", "transpose2", "mul", "split",
                     "rms_norm", "mul", "reshape2", "transpose2", "split",
                     "reshape2", "expand", "concat",
                     "scaled_dot_product_attention", "transpose2",
                     "reshape2", "mul"]
    at = plain.index("expand")
    assert turned == plain[:at] + ["rotary_embedding"] * 2 + plain[at:]


def test_run_and_run_repeated_agree_under_bf16_amp():
    """The cell's own path: ``run_repeated`` under bf16 AMP and Adam
    against three ``run``s; the loss falls."""
    batch = _batch(3)
    results = []
    for repeated in (False, True):
        opt = amp.decorate(fluid.optimizer.Adam(**ADAM),
                           dest_dtype="bfloat16")
        main, startup, loss, _ = _program(CFG, opt)
        scope, exe = fluid.Scope(), fluid.Executor()
        with fluid.scope_guard(scope):
            exe.run(startup)
            _seeded(scope, CFG)
            if repeated:
                last, = exe.run_repeated(main, feed=batch,
                                         fetch_list=[loss], iters=3)
            else:
                first = None
                for _ in range(3):
                    last, = exe.run(main, feed=batch, fetch_list=[loss])
                    first = first if first is not None else float(
                        np.asarray(last).reshape(-1)[0])
            results.append((
                float(np.asarray(last).reshape(-1)[-1]),
                np.asarray(scope.find_var("layer1_mla_kv_a.w_0")),
                np.asarray(scope.find_var("layer2_router.bias")),
                exe.telemetry(scope=scope)["moe"]))
    (l1, w1, b1, t1), (l2, w2, b2, t2) = results
    assert l1 < first - 0.01
    assert l1 == pytest.approx(l2, rel=1e-5)
    np.testing.assert_allclose(w1, w2, rtol=1e-3, atol=1e-5)
    np.testing.assert_array_equal(b1, b2)
    assert t1 == t2
    n_moe = CFG["num_hidden_layers"] - CFG["first_k_dense_replace"]
    assert t1["assignments_total"] == 3 * n_moe * BATCH * 48 * 3


def test_all_eight_shares_add_up_to_the_uncut_layer():
    """The cut to one chip's share, tied to the model: the routed parts
    that 8 shares of 4 experts each give (every share routes over all
    32 and computes its own), with the shared pair -- ONE MLP of twice
    an expert's width -- counted once, are the uncut 32-expert layer of
    the reference."""
    shares, per, d, f, s, top = 8, 4, 16, 8, 24, 6
    width = shares * per
    base = {k: v for k, v in CFG.items() if k not in BENCH_ONLY}
    base.update(hidden_size=d, moe_intermediate_size=f, seq_len=s,
                num_experts_published=width, num_experts_per_tok=top)
    rs = np.random.RandomState(11)
    draw = lambda *shape: rs.randn(*shape).astype(np.float32) * 0.3  # noqa: E731
    p = {"l_router.w_0": draw(d, width),
         "l_experts.w_gate": draw(width, d, f),
         "l_experts.w_up": draw(width, d, f),
         "l_experts.w_down": draw(width, f, d),
         "l_shared_gate.w_0": draw(d, 2 * f),
         "l_shared_up.w_0": draw(d, 2 * f),
         "l_shared_down.w_0": draw(2 * f, d)}
    bias = draw(width) * 0.1
    m = draw(2, s, d)

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("m", shape=[s, d], dtype="float32")
            whole = DS.DeepseekV3Config(**dict(
                base, n_routed_experts=width, first_held_expert=0))
            total = afmoe._gated_mlp(
                x, f * whole.num_shared_experts, whole, "l_shared")
            for i in range(shares):
                cfg = DS.DeepseekV3Config(**dict(
                    base, n_routed_experts=per,
                    first_held_expert=i * per))
                total = fluid.layers.elementwise_add(
                    total, afmoe._routed(x, cfg, "share%d" % i))
    scope, exe = fluid.Scope(), fluid.Executor()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for n in ("gate", "up", "down"):
            scope.set_var("l_shared_%s.w_0" % n,
                          jnp.asarray(p["l_shared_%s.w_0" % n]))
        for i in range(shares):
            held = slice(i * per, (i + 1) * per)
            scope.set_var("share%d_router.w_0" % i,
                          jnp.asarray(p["l_router.w_0"]))
            scope.set_var("share%d_router.bias" % i, jnp.asarray(bias))
            for n in ("w_gate", "w_up", "w_down"):
                scope.set_var("share%d_experts.%s" % (i, n),
                              jnp.asarray(p["l_experts." + n][held]))
        got, = exe.run(main, feed={"m": m}, fetch_list=[total])
        held_share = exe.telemetry(scope=scope)["moe"]
    uncut = dict(base, n_routed_experts=width, first_held_expert=0)
    mj = jnp.asarray(m)
    sel, w, _ = ref.route(mj, p["l_router.w_0"], jnp.asarray(bias), uncut,
                          "f32")
    want = trinity.gated_mlp(mj, p, "l_shared", "f32") \
        + ref.held_experts(mj, sel, w, p, "l_experts", uncut, "f32")
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    # every assignment was somebody's: the shares' held counts sum to
    # the assignments of ONE routing
    assert held_share["assignments_held_total"] == 2 * s * top
    assert held_share["assignments_total"] == shares * 2 * s * top
