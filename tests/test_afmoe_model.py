"""models.afmoe against benchmark/reference/trinity_mini_ep16.py, at a
small size on the CPU in float32: the loss and every gradient, the
bias buffer over three steps, the step's counters, an overflow of the
held experts' buffer."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.models import afmoe
from paddle_tpu.parallel import moe as moe_lib

ref = importlib.import_module("benchmark.reference.trinity_mini_ep16")
common = importlib.import_module("benchmark.reference.common")

CFG = dict(vocab_size=97, hidden_size=32, num_hidden_layers=4,
           num_dense_layers=1,
           layer_types=["sliding_attention", "sliding_attention",
                        "full_attention", "sliding_attention"],
           num_attention_heads=4, num_key_value_heads=2, head_dim=8,
           intermediate_size=48, moe_intermediate_size=16, num_experts=4,
           num_experts_published=16, first_held_expert=4,
           num_experts_per_tok=4, num_shared_experts=1, route_scale=2.826,
           route_norm=True, score_func="sigmoid", load_balance_coeff=0.001,
           sliding_window=6, rope_theta=10000.0, rms_norm_eps=1e-5,
           mup_enabled=True, moe_row_capacity=None, seq_len=16,
           initializer_range=0.1)
BATCH = 3


def _batch(seed=0):
    rs = np.random.RandomState(seed)
    s = CFG["seq_len"]
    mask = np.ones((BATCH, s), np.float32)
    mask[1, 11:] = 0.0
    return {"ids": rs.randint(0, CFG["vocab_size"], (BATCH, s)),
            "labels": rs.randint(0, CFG["vocab_size"], (BATCH, s)),
            "mask": mask}


def _program(cfg, optimizer=None):
    takes = {k: v for k, v in cfg.items() if k != "initializer_range"}
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            loss, _ = afmoe.afmoe_lm(afmoe.AfmoeConfig(**takes))
            if optimizer is None:
                pg = fluid.append_backward(loss)
            else:
                optimizer.minimize(loss)
                pg = None
    return main, startup, loss, pg


def _seeded(scope, cfg, seed=7):
    """The benchmark's weights into ``scope`` (whose buffers the
    executor donates), and the same draw again for the reference."""
    for n, v in common.init_params(ref.param_spec(cfg), seed).items():
        scope.set_var(n, v)
    return common.init_params(ref.param_spec(cfg), seed)


def test_parameters_are_the_references():
    main, _, _, _ = _program(CFG)
    got = {p.name: tuple(p.shape) for p in main.all_parameters()}
    want = {n: tuple(s) for n, s, _ in ref.param_spec(CFG)}
    assert got == want


def test_loss_and_every_gradient_match_the_reference():
    """float32 on both sides, so what is left is the order of the sums
    (XLA's fused chain against the reference's row blocks, ragged_dot
    against a per-expert loop): 2e-4 of each leaf's largest gradient,
    where bf16 would read 1e-2."""
    main, startup, loss, pg = _program(CFG)
    scope, exe = fluid.Scope(), fluid.Executor()
    batch = _batch()
    with fluid.scope_guard(scope):
        exe.run(startup)
        params = _seeded(scope, CFG)
        out = exe.run(main, feed=batch,
                      fetch_list=[loss] + [g for _, g in pg])
    ref.param_spec(CFG)
    norm = ref.normalizers(batch)
    rows = {k: jnp.asarray(v) for k, v in batch.items()}
    want, grads = jax.value_and_grad(ref.block_loss)(
        params, rows, norm, None, CFG, "f32")
    np.testing.assert_allclose(out[0], want, rtol=2e-6)
    for (p, _), got in zip(pg, out[1:]):
        scale = float(jnp.max(jnp.abs(grads[p.name])))
        assert scale > 0, p.name
        np.testing.assert_allclose(got, grads[p.name], rtol=0,
                                   atol=2e-4 * scale, err_msg=p.name)


def _train(cfg, steps, batch):
    main, startup, loss, _ = _program(
        cfg, fluid.optimizer.Adam(learning_rate=3e-3, beta1=0.9,
                                  beta2=0.95, epsilon=1e-8))
    scope, exe = fluid.Scope(), fluid.Executor()
    losses = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        _seeded(scope, cfg)
        for _ in range(steps):
            losses.append(float(np.asarray(exe.run(
                main, feed=batch, fetch_list=[loss])[0]).reshape(-1)[0]))
        tel = exe.telemetry(scope=scope)
    return losses, scope, tel


def test_bias_update_over_three_steps_follows_the_reference():
    """common.train carries the buffer through the host (the module's
    docstring); three Adam steps of the program against three of the
    reference: the losses, and the buffers themselves."""
    batch = _batch()
    losses, scope, _ = _train(CFG, 3, batch)
    opt = {"learning_rate": 3e-3, "beta1": 0.9, "beta2": 0.95,
           "epsilon": 1e-8}
    out = common.train(ref, CFG, opt, batch, 7, steps=3,
                       rows_per_block=1)
    np.testing.assert_allclose(losses, out["loss"], rtol=1e-5)
    bias = ref._STATE["norm"]["router_bias"]
    assert sorted(bias) == [1, 2, 3]
    for i, b in bias.items():
        got = np.asarray(scope.find_var("layer%d_router.bias" % i))
        assert np.abs(b).max() > 0      # three steps moved it
        assert abs(b.sum()) < 1e-6      # and left it centred
        np.testing.assert_allclose(got, b, atol=1e-7)
    # by hand for one step of one layer: the sign of the load's gap
    load = np.array([3.0, 0.0, 5.0, 4.0])
    b1 = np.asarray(moe_lib.balance_bias_update(
        jnp.zeros(4), jnp.asarray(load), 0.001))
    np.testing.assert_allclose(b1, [0.0, 0.001, -0.001, -0.001]
                               - np.mean([0.0, 0.001, -0.001, -0.001]),
                               atol=1e-9)


def test_telemetry_moe_against_counts_made_by_hand():
    batch = _batch()
    _, scope, tel = _train(CFG, 2, batch)
    m = tel["moe"]
    tokens, k = BATCH * CFG["seq_len"], CFG["num_experts_per_tok"]
    n_moe = CFG["num_hidden_layers"] - CFG["num_dense_layers"]
    assert m["assignments_total"] == 2 * n_moe * tokens * k
    assert m["rows_over_capacity_total"] == 0
    assert 0 < m["assignments_held_total"] < m["assignments_total"]
    # the busiest of the held is at least their mean, and the means
    # add up to the held assignments over the experts held
    assert m["held_load_max_total"] >= m["held_load_mean_total"]
    assert m["held_load_mean_total"] * CFG["num_experts"] \
        == pytest.approx(m["assignments_held_total"])
    # tile-rounded: at least the real rows, a multiple of the tile
    from paddle_tpu.ops.pallas import grouped_matmul as gmm
    assert m["rows_computed_total"] >= m["assignments_held_total"]
    assert m["rows_computed_total"] % gmm.TILE_M == 0
    # whole chunks of the buffer, enough for every held row, and per
    # layer and step under a chunk more than that layer's rows
    chunk = moe_lib._chunk_rows(tokens, tokens * k)
    assert m["rows_passed_total"] % chunk == 0
    assert m["assignments_held_total"] <= m["rows_passed_total"] \
        < m["assignments_held_total"] + 2 * n_moe * chunk
    assert fluid.Executor().telemetry(scope=fluid.Scope())["moe"] is None


def test_one_layer_counts_by_hand():
    """The router's and the held experts' counters for one call each
    against numpy's own count."""
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(40, 8), jnp.float32)
    w = jnp.asarray(rs.randn(8, 16), jnp.float32)
    zeros = jnp.zeros((len(moe_lib.COUNTER_NAMES),), jnp.float32)
    sel, weight, bias, counters = moe_lib.moe_sigmoid_router_op(
        x, w, jnp.zeros((16,)), zeros, top_k=3, route_scale=2.0,
        first_held=4, n_held=4)
    scores = 1 / (1 + np.exp(-np.asarray(x) @ np.asarray(w)))
    want = np.argsort(-scores, axis=1, kind="stable")[:, :3]
    assert (np.sort(np.asarray(sel), 1) == np.sort(want, 1)).all()
    np.testing.assert_allclose(np.asarray(weight).sum(1), 2.0, rtol=1e-5)
    load = np.bincount(want.reshape(-1), minlength=16)
    c = dict(zip(moe_lib.COUNTER_NAMES, np.asarray(counters)))
    assert c["assignments_total"] == 120
    assert c["assignments_held_total"] == load[4:8].sum()
    assert c["held_load_max_total"] == load[4:8].max()
    assert c["held_load_mean_total"] == pytest.approx(load[4:8].mean())
    assert (np.asarray(bias) == 0).all()    # balance_coeff 0: unmoved
    mats = [jnp.asarray(rs.randn(*s), jnp.float32)
            for s in [(4, 8, 6), (4, 8, 6), (4, 6, 8)]]
    _, counters = moe_lib.moe_held_experts_op(
        x, sel, weight, *mats, zeros, first_held=4, row_capacity=0)
    c = dict(zip(moe_lib.COUNTER_NAMES, np.asarray(counters)))
    from paddle_tpu.ops.pallas import grouped_matmul as gmm
    chunk = moe_lib._chunk_rows(40, 120)
    assert c["rows_passed_total"] == -(-load[4:8].sum() // chunk) * chunk
    assert c["rows_computed_total"] == int(
        gmm.tile_rounded_rows(jnp.asarray(load[4:8])))
    assert c["rows_over_capacity_total"] == 0


def test_overflow_of_the_row_buffer_is_nan_and_counted():
    """A buffer of 8 rows cannot hold the held experts' tokens: the
    loss is NaN (never a silently smaller sum) and the rows that found
    no room are counted."""
    cfg = dict(CFG, moe_row_capacity=8)
    losses, _, tel = _train(cfg, 1, _batch())
    assert np.isnan(losses[0])
    assert tel["moe"]["rows_over_capacity_total"] > 0
    roomy, _, tel = _train(dict(CFG, moe_row_capacity=BATCH * 16 * 4), 1,
                           _batch())
    assert np.isfinite(roomy[0])
    assert tel["moe"]["rows_over_capacity_total"] == 0


def test_all_sixteen_shares_add_up_to_the_uncut_layer():
    """The cut to one chip's share, tied to the model: the routed parts
    that 16 shares of 2 experts each give (every share routes over all
    32 and computes its own), with the shared expert counted once, are
    the uncut 32-expert layer of the reference."""
    shares, per, d, f, s = 16, 2, 16, 8, 24
    width = shares * per
    base = dict(CFG, hidden_size=d, moe_intermediate_size=f, seq_len=s,
                num_experts_published=width, num_experts_per_tok=4)
    base.pop("initializer_range")
    rs = np.random.RandomState(11)
    draw = lambda *shape: rs.randn(*shape).astype(np.float32) * 0.3  # noqa: E731
    p = {"l_router.w_0": draw(d, width), "l_experts.w_gate": draw(width, d, f),
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
            whole = afmoe.AfmoeConfig(**dict(base, num_experts=width,
                                             first_held_expert=0))
            total = afmoe._gated_mlp(x, f, whole, "l_shared")
            for i in range(shares):
                cfg = afmoe.AfmoeConfig(**dict(
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
        counted = exe.telemetry(scope=scope)["moe"]

    cfg = dict(base, num_experts=width, first_held_expert=0)
    pj = {k: jnp.asarray(v) for k, v in p.items()}
    sel, w, _ = ref.route(jnp.asarray(m), pj["l_router.w_0"],
                          jnp.asarray(bias), cfg, "f32")
    want = ref.gated_mlp(jnp.asarray(m), pj, "l_shared", "f32") \
        + ref.held_experts(jnp.asarray(m), sel, w, pj, "l_experts", cfg,
                           "f32")
    # float32 both; the sums run in another order
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    # every assignment is held by exactly one share
    assert counted["assignments_held_total"] == 2 * s * 4
    assert counted["assignments_total"] == shares * 2 * s * 4
