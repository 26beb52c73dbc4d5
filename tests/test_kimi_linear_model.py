"""models.kimi_linear against benchmark/reference/kimi_linear_ep32.py,
at a small size on the CPU in float32: the parameters, the loss and
every gradient, one Adam step, the layer pattern, the cut to a share
of the experts tied to the uncut layer, and ``run`` against
``run_repeated``. 80 tokens a row: the KDA state is carried from the
first chunk of 64 into the second."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.models import afmoe
from paddle_tpu.models import kimi_linear as KL

ref = importlib.import_module("benchmark.reference.kimi_linear_ep32")
trinity = importlib.import_module("benchmark.reference.trinity_mini_ep16")
common = importlib.import_module("benchmark.reference.common")

LA = {"kda_layers": [1, 2, 3, 5], "full_attn_layers": [4], "num_heads": 2,
      "head_dim": 8, "short_conv_kernel_size": 4}
CFG = dict(vocab_size=97, hidden_size=32, num_hidden_layers=5,
           first_k_dense_replace=1, linear_attn_config=LA,
           num_attention_heads=2, kv_lora_rank=16, qk_nope_head_dim=8,
           qk_rope_head_dim=4, v_head_dim=8, intermediate_size=48,
           moe_intermediate_size=16, num_experts=2,
           num_experts_published=8, first_held_expert=2,
           num_experts_per_token=2, num_shared_experts=1,
           routed_scaling_factor=2.446, moe_renormalize=True,
           load_balance_coeff=0.001, kda_gate_rank=8, rms_norm_eps=1e-5,
           moe_row_capacity=None, seq_len=80, initializer_range=0.1,
           conv_init_std=0.3)
BENCH_ONLY = ("initializer_range", "conv_init_std")
BATCH = 2


def _batch(seed=0):
    rs = np.random.RandomState(seed)
    s = CFG["seq_len"]
    mask = np.ones((BATCH, s), np.float32)
    mask[1, 71:] = 0.0
    return {"ids": rs.randint(0, CFG["vocab_size"], (BATCH, s)),
            "labels": rs.randint(0, CFG["vocab_size"], (BATCH, s)),
            "mask": mask}


def _program(cfg, optimizer=None):
    takes = {k: v for k, v in cfg.items() if k not in BENCH_ONLY}
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            loss, _ = KL.kimi_linear_lm(KL.KimiLinearConfig(**takes))
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


def test_parameters_and_gate_start_are_the_references():
    main, _, _, _ = _program(CFG)
    got = {p.name: tuple(p.shape) for p in main.all_parameters()}
    want = {n: tuple(s) for n, s, _ in ref.param_spec(CFG)}
    assert got == want
    # the family's start of the gate, written twice: bit-equal
    for a, b in zip(KL.kda_gate_start(32, 128), ref.gate_start(32, 128)):
        assert a.dtype == np.float32 and np.array_equal(a, b)
    a_log, dt_bias = KL.kda_gate_start(32, 128)
    assert 0.0 < a_log.min() and a_log.max() < np.log(16.0)
    step = np.log1p(np.exp(dt_bias.astype(np.float64)))
    assert 0.001 <= step.min() and step.max() <= 0.1
    assert np.median(step) == pytest.approx(0.01, rel=0.05)


def test_loss_and_every_gradient_match_the_reference():
    """float32 on both sides: the chunked delta rule against the
    recurrence, the flash-free XLA attention against the reference's
    row blocks, ragged_dot against a per-expert loop. 5e-4 of each
    leaf's largest gradient, where bf16 would read 1e-2."""
    main, startup, loss, pg = _program(CFG)
    scope, exe = fluid.Scope(), fluid.Executor()
    batch = _batch()
    with fluid.scope_guard(scope):
        exe.run(startup)
        params = _seeded(scope, CFG)
        out = exe.run(main, feed=batch,
                      fetch_list=[loss] + [g for _, g in pg])
    want, grads = _reference(params, batch)
    np.testing.assert_allclose(out[0], want, rtol=5e-6)
    assert len(pg) == len(grads)
    for (p, _), got in zip(pg, out[1:]):
        scale = float(jnp.max(jnp.abs(grads[p.name])))
        assert scale > 0, p.name
        np.testing.assert_allclose(got, grads[p.name], rtol=0,
                                   atol=5e-4 * scale, err_msg=p.name)


def _adam():
    return fluid.optimizer.Adam(learning_rate=3e-3, beta1=0.9,
                                beta2=0.95, epsilon=1e-8)


def test_one_adam_step_matches_the_reference():
    main, startup, loss, _ = _program(CFG, _adam())
    scope, exe = fluid.Scope(), fluid.Executor()
    batch = _batch()
    with fluid.scope_guard(scope):
        exe.run(startup)
        params = _seeded(scope, CFG)
        exe.run(main, feed=batch, fetch_list=[loss])
        got = {n: np.asarray(scope.find_var(n)) for n in params}
    _, grads = _reference(params, batch)
    zeros = {n: jnp.zeros_like(v) for n, v in params.items()}
    want, _, _ = common.adam_update(params, grads, zeros, zeros, 1.0,
                                    3e-3, 0.9, 0.95, 1e-8)
    for n in params:
        # the first step moves every element by the rate, to the sign
        # of its gradient: only an element whose gradient is rounding
        # could differ
        moved = np.abs(got[n] - np.asarray(params[n]))
        assert moved.max() <= 3e-3 * 1.001, n
        close = np.isclose(got[n], np.asarray(want[n]), rtol=0,
                           atol=3e-4)
        assert close.mean() > 0.995, (n, close.mean())


def test_layer_pattern_follows_linear_attn_config():
    main, _, _, _ = _program(CFG)
    ops = [op.type for op in main.global_block().ops]
    assert ops.count("kda_attention") == 4
    assert ops.count("scaled_dot_product_attention") == 1
    assert ops.count("moe_held_experts") == 4
    mixers = [t for t in ops
              if t in ("kda_attention", "scaled_dot_product_attention")]
    assert mixers == ["kda_attention"] * 3 \
        + ["scaled_dot_product_attention", "kda_attention"]
    # the published 27 layers: every fourth full, and the last
    la = KL.KimiLinearConfig().linear_attn_config
    assert la["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert la["kda_layers"] == [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15,
                                17, 18, 19, 21, 22, 23, 25, 26]
    assert (la["num_heads"], la["head_dim"],
            la["short_conv_kernel_size"]) == (32, 128, 4)
    with pytest.raises(ValueError, match="each of 5 layers once"):
        KL.KimiLinearConfig(num_hidden_layers=5, linear_attn_config={
            "kda_layers": [1, 2, 3], "full_attn_layers": [4]})
    with pytest.raises(ValueError, match="q_lora_rank"):
        KL.KimiLinearConfig(q_lora_rank=1536)
    with pytest.raises(ValueError, match="experts 250..257 of 256"):
        KL.KimiLinearConfig(num_experts=8, num_experts_published=256,
                            first_held_expert=250)


def test_run_and_run_repeated_agree():
    batch = _batch(3)
    results = []
    for repeated in (False, True):
        main, startup, loss, _ = _program(CFG, _adam())
        scope, exe = fluid.Scope(), fluid.Executor()
        with fluid.scope_guard(scope):
            exe.run(startup)
            _seeded(scope, CFG)
            if repeated:
                last, = exe.run_repeated(main, feed=batch,
                                         fetch_list=[loss], iters=3)
            else:
                for _ in range(3):
                    last, = exe.run(main, feed=batch, fetch_list=[loss])
            results.append((
                float(np.asarray(last).reshape(-1)[-1]),
                np.asarray(scope.find_var("layer2_kda_gate.A_log")),
                np.asarray(scope.find_var("layer3_mla_kv_b.w_0")),
                np.asarray(scope.find_var("layer4_router.bias")),
                exe.telemetry(scope=scope)["kda"]))
    (l1, a1, w1, b1, t1), (l2, a2, w2, b2, t2) = results
    assert l1 == pytest.approx(l2, rel=1e-6)
    # one program inside a scan and outside it: XLA fuses the two
    # alike but not always in the same order
    np.testing.assert_allclose(a1, a2, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(w1, w2, rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(b1, b2)
    assert t1 == t2 and t1["tokens_total"] == 3 * 4 * BATCH * 80
    assert t1["chunks_total"] == 3 * 4 * BATCH * 2


def test_all_shares_add_up_to_the_uncut_layer():
    """The cut to one chip's share, tied to the model: the routed parts
    that 8 shares of 4 experts each give (every share routes over all
    32 and computes its own), with the shared expert counted once, are
    the uncut 32-expert layer of the reference."""
    shares, per, d, f, s = 8, 4, 16, 8, 24
    width = shares * per
    base = {k: v for k, v in CFG.items() if k not in BENCH_ONLY}
    base.update(hidden_size=d, moe_intermediate_size=f, seq_len=s,
                num_experts_published=width, num_experts_per_token=4)
    rs = np.random.RandomState(11)
    draw = lambda *shape: rs.randn(*shape).astype(np.float32) * 0.3  # noqa: E731
    p = {"l_router.w_0": draw(d, width),
         "l_experts.w_gate": draw(width, d, f),
         "l_experts.w_up": draw(width, d, f),
         "l_experts.w_down": draw(width, f, d),
         "l_shared_gate.w_0": draw(d, f), "l_shared_up.w_0": draw(d, f),
         "l_shared_down.w_0": draw(f, d)}
    bias = draw(width) * 0.1
    m = draw(2, s, d)

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("m", shape=[s, d], dtype="float32")
            whole = KL.KimiLinearConfig(**dict(base, num_experts=width,
                                               first_held_expert=0))
            total = KL._gated_mlp(x, f, whole, "l_shared")
            for i in range(shares):
                cfg = KL.KimiLinearConfig(**dict(
                    base, num_experts=per, first_held_expert=i * per))
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
    uncut = dict(base, num_experts=width, first_held_expert=0)
    mj = jnp.asarray(m)
    sel, w, _ = ref.route(mj, p["l_router.w_0"], jnp.asarray(bias), uncut,
                          "f32")
    want = trinity.gated_mlp(mj, p, "l_shared", "f32") \
        + trinity.held_experts(mj, sel, w, p, "l_experts", uncut, "f32")
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    # every assignment was somebody's: the shares' held counts sum to
    # the assignments of ONE routing
    assert held_share["assignments_held_total"] == 2 * s * 4
    assert held_share["assignments_total"] == shares * 2 * s * 4
