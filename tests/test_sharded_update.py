"""ZeRO-sharded weight update (parallel/collectives.py sharded_update).

ISSUE 6 acceptance on the virtual CPU mesh: bit-exact loss trajectory
vs the replicated exact psum over >= 50 steps on 1- and 4-device
meshes (adam + weight decay + clip), q8 grad-scatter and q8
param-gather variants within an rtol budget with both error-feedback
residual families live, ~1/n per-chip optimizer-slot bytes,
save -> restore -> continue bit-exactness, and composition with the
anomaly guard (a gated step leaves shards, residuals, and params
bit-identical).
"""

import tempfile

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import io, layers, optimizer, unique_name
from paddle_tpu.parallel import collectives as C
from paddle_tpu.parallel import make_mesh


def _mesh(n):
    return make_mesh({"dp": n}, jax.devices()[:n])


def _build_model(seed=11, clip="gnorm", opt="adamw"):
    """fc(16->32)->fc(32->4) classifier. unique_name.guard keeps var
    names IDENTICAL across builds inside one test, so scopes from
    different runs compare var-by-var."""
    with unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = seed
        startup.random_seed = seed
        with fluid.program_guard(main, startup):
            x = layers.data("x", shape=[16])
            label = layers.data("label", shape=[1], dtype="int64")
            h = layers.fc(x, size=32, act="relu")
            pred = layers.fc(h, size=4, act="softmax")
            loss = layers.mean(layers.cross_entropy(pred, label))
            if opt == "adamw":
                o = optimizer.AdamW(learning_rate=0.01,
                                    weight_decay=0.01)
            else:
                o = optimizer.Adam(learning_rate=0.01)
            if clip == "gnorm":
                gc = fluid.clip.GradientClipByGlobalNorm(1.0)
            elif clip == "value":
                gc = fluid.clip.GradientClipByValue(0.5)
            else:
                gc = None
            o.minimize(loss, grad_clip=gc)
    return main, startup, loss


def _batches(n, batch=16, seed=0):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        x = rng.rand(batch, 16).astype(np.float32)
        y = np.argmax(x[:, :4], 1).reshape(batch, 1).astype(np.int64)
        out.append((x, y))
    return out


def _train(mode, world=4, steps=10, param_gather="fp32", clip="gnorm",
           opt="adamw"):
    main, startup, loss = _build_model(clip=clip, opt=opt)
    bs = fluid.BuildStrategy()
    bs.gradient_sync = mode
    bs.param_gather = param_gather
    prog = fluid.CompiledProgram(main).with_data_parallel(
        build_strategy=bs, mesh=_mesh(world))
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        losses = []
        for x, y in _batches(steps):
            (lv,) = exe.run(prog, feed={"x": x, "label": y},
                            fetch_list=[loss])
            losses.append(float(lv))
        pnames = [p.name for p in main.global_block().all_parameters()]
        params = {n: np.asarray(jax.device_get(scope.find_var(n)))
                  for n in pnames if scope.find_var(n) is not None}
    return main, losses, params, scope


# ---------------------------------------------------------------------------
# acceptance: bit-exact vs replicated exact psum
# ---------------------------------------------------------------------------

def test_sharded_exact_bit_identical_50_steps_4dev():
    """adam + weight decay (adamw) + clip over 50 steps: the
    1/n-sharded update's losses AND final params must equal the
    replicated exact psum's bit-for-bit — the psum_scatter reduces the
    same partials in the same rank order, the flat-shard update is
    purely elementwise, and gather(slice(x)) round-trips exactly.
    (Elementwise clip: a global-norm clip's scalar is a reduction whose
    association differs between the [padded] flat and the shaped
    layout, costing the final ulp — covered with a tight tolerance
    below.)"""
    _, exact, p_exact, _ = _train("exact", world=4, steps=50,
                                  clip="value")
    _, shard, p_shard, _ = _train("sharded_update", world=4, steps=50,
                                  clip="value")
    assert exact == shard
    assert exact[-1] < exact[0]  # actually learning
    for n in p_exact:
        np.testing.assert_array_equal(p_exact[n], p_shard[n], err_msg=n)


def test_sharded_exact_bit_identical_50_steps_1dev():
    """Same contract on a 1-device mesh: the transports degenerate but
    the flat-shard bracket (pad, update on [padded], unpad) remains —
    the mode must mean the same thing at every scale."""
    _, exact, p_exact, _ = _train("exact", world=1, steps=50,
                                  clip="value")
    _, shard, p_shard, _ = _train("sharded_update", world=1, steps=50,
                                  clip="value")
    assert exact == shard
    for n in p_exact:
        np.testing.assert_array_equal(p_exact[n], p_shard[n], err_msg=n)


def test_sharded_global_norm_clip_tracks_exact_tightly():
    """Global-norm clipping inside the bracket: the joint norm is a
    GLOBAL reduction over dp-sharded flats (GSPMD inserts the psum), so
    the trajectory matches the replicated one to reduction-order
    precision (last-ulp, not bit-for-bit)."""
    _, exact, p_exact, _ = _train("exact", world=4, steps=20,
                                  clip="gnorm")
    _, shard, p_shard, _ = _train("sharded_update", world=4, steps=20,
                                  clip="gnorm")
    np.testing.assert_allclose(shard, exact, rtol=1e-5, atol=1e-7)
    for n in p_exact:
        np.testing.assert_allclose(p_shard[n], p_exact[n], rtol=1e-4,
                                   atol=1e-6, err_msg=n)


# ---------------------------------------------------------------------------
# q8 variants: rtol budget + residual families
# ---------------------------------------------------------------------------

def test_sharded_q8_grad_scatter_tracks_exact():
    _, exact, _p, _ = _train("exact", world=4, steps=10)
    main, q8, _p2, scope = _train("sharded_update_q8", world=4,
                                  steps=10)
    np.testing.assert_allclose(q8, exact, rtol=5e-2)
    assert q8 != exact  # quantization actually in the loop
    assert q8[-1] < q8[0]
    res = [n for n in scope.local_var_names()
           if n.endswith(C.RESIDUAL_SUFFIX)
           and scope.find_var(n) is not None]
    assert len(res) == 4, sorted(res)
    assert any(np.abs(np.asarray(scope.find_var(n))).max() > 0
               for n in res)
    # no param-side state in the fp32-gather variant
    assert not any(n.endswith(C.PARAM_RESIDUAL_SUFFIX)
                   for n in scope.local_var_names())


def test_sharded_q8_param_gather_tracks_exact():
    """q8 on BOTH legs: grads scattered int8, params gathered int8 with
    the second residual family; the fp32 master shard never passes
    through the quantizer (it differs from the quantized full param)."""
    _, exact, _p, _ = _train("exact", world=4, steps=10)
    main, q8, _p2, scope = _train("sharded_update_q8", world=4,
                                  steps=10, param_gather="q8")
    np.testing.assert_allclose(q8, exact, rtol=5e-2)
    assert q8[-1] < q8[0]
    pres = [n for n in scope.local_var_names()
            if n.endswith(C.PARAM_RESIDUAL_SUFFIX)
            and scope.find_var(n) is not None]
    masters = [n for n in scope.local_var_names()
               if n.endswith(C.MASTER_SHARD_SUFFIX)
               and scope.find_var(n) is not None]
    assert len(pres) == 4 and len(masters) == 4
    assert any(np.abs(np.asarray(scope.find_var(n))).max() > 0
               for n in pres)
    # master is the exact pre-quantization value: the published full
    # param (a quantized gather) must differ somewhere
    for n in masters:
        pname = n[:-len(C.MASTER_SHARD_SUFFIX)]
        p = np.asarray(jax.device_get(scope.find_var(pname)))
        m = np.asarray(jax.device_get(scope.find_var(n)))[:p.size]
        assert not np.array_equal(m.reshape(-1), p.reshape(-1)), pname


# ---------------------------------------------------------------------------
# memory: per-chip optimizer-slot bytes scale ~1/n
# ---------------------------------------------------------------------------

def test_slot_bytes_per_chip_quarter_on_4dev():
    m_rep, _l, _p, sc_rep = _train("exact", world=4, steps=2)
    m_sh, _l2, _p2, sc_sh = _train("sharded_update", world=4, steps=2)
    rep = C.slot_bytes_per_chip(m_rep, sc_rep)
    shard = C.slot_bytes_per_chip(m_sh, sc_sh)
    assert rep > 0
    # acceptance: <= ~30% of the replicated slot bytes on 4 devices
    # (exactly 25% when every param pads cleanly, as here)
    assert shard <= 0.30 * rep, (shard, rep)


# ---------------------------------------------------------------------------
# checkpointing: save -> restore -> continue is bit-exact
# ---------------------------------------------------------------------------

def _ckpt_run(mesh, load_dir=None, pre=3, post=3):
    main, startup, loss = _build_model()
    bs = fluid.BuildStrategy()
    bs.gradient_sync = "sharded_update_q8"
    bs.param_gather = "q8"
    prog = fluid.CompiledProgram(main).with_data_parallel(
        build_strategy=bs, mesh=mesh)
    exe = fluid.Executor()
    scope = fluid.Scope()
    allb = _batches(pre + post)
    with fluid.scope_guard(scope):
        exe.run(startup)
        if load_dir is None:
            for x, y in allb[:pre]:
                exe.run(prog, feed={"x": x, "label": y},
                        fetch_list=[loss])
            d = tempfile.mkdtemp()
            io.save_persistables(dirname=d, main_program=main,
                                 scope=scope)
        else:
            # restore recipe (docs/gradient_sync.md): materialize the
            # sharded slot layout + residual families on the fresh
            # program BEFORE loading, so every state family restores
            C.ensure_sharded_state(main, scope, mesh,
                                   param_gather="q8")
            C.ensure_residual_vars(main, scope)
            io.load_persistables(dirname=load_dir, main_program=main,
                                 scope=scope)
            d = None
        losses = []
        for x, y in allb[pre:]:
            (lv,) = exe.run(prog, feed={"x": x, "label": y},
                            fetch_list=[loss])
            losses.append(float(lv))
    return d, losses


def test_sharded_checkpoint_roundtrip_bit_exact():
    """World-size-preserving restart under q8-both-legs: sharded m/v,
    grad residuals, param residuals, and master shards all round-trip
    through save_persistables — the continued trajectory is
    bit-identical to the uninterrupted one."""
    mesh = _mesh(4)
    d, cont = _ckpt_run(mesh)
    _, resumed = _ckpt_run(mesh, load_dir=d)
    assert cont == resumed, (cont, resumed)


def test_replicated_checkpoint_loads_into_sharded_slots():
    """A replicated-era checkpoint (full-shape m/v) restores into a
    sharded program: io._check_and_set pad-flattens slot values whose
    element count matches the declared shard geometry."""
    mesh = _mesh(4)
    # train replicated, save
    main, startup, loss = _build_model()
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for x, y in _batches(2):
            exe.run(main, feed={"x": x, "label": y}, fetch_list=[loss])
        d = tempfile.mkdtemp()
        io.save_persistables(dirname=d, main_program=main, scope=scope)
        m1 = np.asarray(scope.find_var("fc_0.w_0_moment1_0"))
    # restore into a sharded program
    main2, startup2, loss2 = _build_model()
    bs = fluid.BuildStrategy()
    bs.gradient_sync = "sharded_update"
    prog = fluid.CompiledProgram(main2).with_data_parallel(
        build_strategy=bs, mesh=mesh)
    exe2 = fluid.Executor()
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe2.run(startup2)
        C.ensure_sharded_state(main2, scope2, mesh)
        io.load_persistables(dirname=d, main_program=main2,
                             scope=scope2)
        got = np.asarray(scope2.find_var("fc_0.w_0_moment1_0"))
        assert got.ndim == 1
        np.testing.assert_array_equal(got[:m1.size], m1.reshape(-1))
        x, y = _batches(1)[0]
        (lv,) = exe2.run(prog, feed={"x": x, "label": y},
                         fetch_list=[loss2])
        assert np.isfinite(lv)


# ---------------------------------------------------------------------------
# composition: anomaly guard x sharded_update x run_repeated
# ---------------------------------------------------------------------------

def test_guard_gated_step_leaves_sharded_state_bit_identical():
    """ISSUE 6 composition smoke: sharded_update_q8 (both legs) under
    the PR 2 anomaly guard, stepped through run_repeated. A poisoned
    (NaN) step must leave every persistable — params, sharded m/v,
    master shards, BOTH residual families — bit-identical, advancing
    only the guard counters; training then resumes finite."""
    from paddle_tpu.resilience import (install_anomaly_guard,
                                       read_counters)
    main, startup, loss = _build_model(clip=None)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        install_anomaly_guard(main, loss=loss)
        bs = fluid.BuildStrategy()
        bs.gradient_sync = "sharded_update_q8"
        bs.param_gather = "q8"
        prog = fluid.CompiledProgram(main).with_data_parallel(
            build_strategy=bs, mesh=_mesh(4))
        exe = fluid.Executor()
        exe.run(startup)
        x, y = _batches(1)[0]
        # the guard counters ride the carry through repeated stepping
        exe.run_repeated(prog, feed={"x": x, "label": y},
                         fetch_list=[loss], iters=3)
        assert read_counters(scope) == (0.0, 0.0)
        snap = {n: np.asarray(jax.device_get(scope.find_var(n)))
                for n in scope.local_var_names()
                if scope.find_var(n) is not None}
        bad = x.copy()
        bad[0, 0] = np.nan
        (lv,) = exe.run(prog, feed={"x": bad, "label": y},
                        fetch_list=[loss])
        assert not np.isfinite(lv)  # the loss itself is poisoned
        assert read_counters(scope) == (1.0, 1.0)
        changed = []
        for n, v in snap.items():
            new = np.asarray(jax.device_get(scope.find_var(n)))
            if not np.array_equal(new, v, equal_nan=True):
                changed.append(n)
        assert sorted(changed) == ["__guard_consec_anomalies__",
                                   "__guard_skipped_steps__"], changed
        (lv2,) = exe.run(prog, feed={"x": x, "label": y},
                         fetch_list=[loss])
        assert np.isfinite(lv2)
        assert read_counters(scope) == (1.0, 0.0)


def test_ema_reads_full_params_after_gather():
    """Optimize-role ops AFTER the bracket (EMA shadow updates) must
    see the gathered full params, not shards."""
    with unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 7
        startup.random_seed = 7
        with fluid.program_guard(main, startup):
            x = layers.data("x", shape=[16])
            label = layers.data("label", shape=[1], dtype="int64")
            pred = layers.fc(x, size=4, act="softmax")
            loss = layers.mean(layers.cross_entropy(pred, label))
            optimizer.Adam(0.01).minimize(loss)
            ema = optimizer.ExponentialMovingAverage(0.9)
            ema.update()
    bs = fluid.BuildStrategy()
    bs.gradient_sync = "sharded_update"
    prog = fluid.CompiledProgram(main).with_data_parallel(
        build_strategy=bs, mesh=_mesh(4))
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for x_, y_ in _batches(2):
            (lv,) = exe.run(prog, feed={"x": x_, "label": y_},
                            fetch_list=[loss])
        assert np.isfinite(lv)
        shadow = [n for n in scope.local_var_names()
                  if ".ema_" in n and not n.endswith("decay_pow_0")]
        assert shadow
        for n in shadow:
            pname = n.split(".ema_")[0]
            want = np.shape(np.asarray(
                jax.device_get(scope.find_var(pname))))
            v = np.asarray(jax.device_get(scope.find_var(n)))
            assert v.shape == want, (n, v.shape, want)
            assert np.isfinite(v).all() and np.abs(v).max() > 0, n


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------

def test_grad_fetch_fails_loudly_under_sharded_update():
    """The full gradient ceases to exist after the reduce-scatter (that
    IS the memory win) — fetching a @GRAD under sharded_update must
    error loudly, not silently return a flat [padded] 1/n shard where
    every other mode yields the full synced gradient."""
    main, startup, loss = _build_model(clip=None)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        bs = fluid.BuildStrategy()
        bs.gradient_sync = "sharded_update"
        prog = fluid.CompiledProgram(main).with_data_parallel(
            build_strategy=bs, mesh=_mesh(4))
        exe = fluid.Executor()
        exe.run(startup)
        x, y = _batches(1)[0]
        from paddle_tpu.framework import Parameter, grad_var_name
        pname = [v.name for v in main.global_block().vars.values()
                 if isinstance(v, Parameter)][0]
        gname = grad_var_name(pname)
        with pytest.raises(Exception, match="not produced|no value"):
            exe.run(prog, feed={"x": x, "label": y},
                    fetch_list=[loss, gname])


def test_sharded_rejects_reduce_strategy_reduce():
    main, startup, loss = _build_model()
    bs = fluid.BuildStrategy()
    bs.reduce_strategy = fluid.BuildStrategy.ReduceStrategy.Reduce
    bs.gradient_sync = "sharded_update"
    prog = fluid.CompiledProgram(main).with_data_parallel(
        build_strategy=bs, mesh=_mesh(4))
    exe = fluid.Executor()
    x, y = _batches(1)[0]
    with pytest.raises(Exception, match="AllReduce"):
        exe.run(prog, feed={"x": x, "label": y}, fetch_list=[loss])


def test_sharded_rejects_bad_param_gather():
    main, startup, loss = _build_model()
    bs = fluid.BuildStrategy()
    bs.gradient_sync = "sharded_update"
    bs.param_gather = "fp8_someday"
    prog = fluid.CompiledProgram(main).with_data_parallel(
        build_strategy=bs, mesh=_mesh(4))
    exe = fluid.Executor()
    x, y = _batches(1)[0]
    with pytest.raises(Exception, match="param_gather"):
        exe.run(prog, feed={"x": x, "label": y}, fetch_list=[loss])


def test_sharded_state_rejects_world_size_change():
    """A scope converted under one device count re-entering
    ensure_sharded_state under another must get an actionable error,
    not an opaque numpy crash: world=3 pads fc weights (numel 512) to
    [513], which is neither full shape nor world=4's [512] layout."""
    main, startup, _ = _build_model()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        C.ensure_sharded_state(main, scope, _mesh(3))
        with pytest.raises(Exception, match="device count"):
            C.ensure_sharded_state(main, scope, _mesh(4))


def test_world_size_change_rejected_for_master_and_residual():
    """The q8 master/param-residual families must hit the same
    world-size guard as the accumulator slots. SGD has no param-shaped
    slots at all, so only the family check can catch a scope converted
    under a different device count — without it the master is silently
    reseeded from the quantized param image and the EF history zeroed."""
    with unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data("x", shape=[16])
            label = layers.data("label", shape=[1], dtype="int64")
            pred = layers.fc(x, size=4, act="softmax")
            loss = layers.mean(layers.cross_entropy(pred, label))
            optimizer.SGD(learning_rate=0.1).minimize(loss)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        C.ensure_sharded_state(main, scope, _mesh(3), param_gather="q8")
        with pytest.raises(Exception, match="device count"):
            C.ensure_sharded_state(main, scope, _mesh(4),
                                   param_gather="q8")


def test_stale_sharded_layout_rejected_without_plan():
    """Once ensure_sharded_state converts a program's slot declarations
    to the [padded] layout, running that program OUTSIDE the sharded
    bracket (plain exe.run on the raw program) must be rejected at
    trace time with an actionable error — not a bare shape mismatch
    deep in the adam lowering. A for_test clone keeps working: its
    optimizer ops are pruned."""
    main, startup, loss = _build_model(clip=None)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        bs = fluid.BuildStrategy()
        bs.gradient_sync = "sharded_update"
        prog = fluid.CompiledProgram(main).with_data_parallel(
            build_strategy=bs, mesh=_mesh(4))
        exe = fluid.Executor()
        exe.run(startup)
        x, y = _batches(1)[0]
        exe.run(prog, feed={"x": x, "label": y}, fetch_list=[loss])
        with pytest.raises(Exception, match="sharded layout"):
            exe.run(main, feed={"x": x, "label": y},
                    fetch_list=[loss])
        # inference path stays open
        (lv,) = exe.run(main.clone(for_test=True),
                        feed={"x": x, "label": y}, fetch_list=[loss])
        assert np.isfinite(lv)


def test_sharded_rejects_dgc():
    with unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data("x", shape=[16])
            label = layers.data("label", shape=[1], dtype="int64")
            pred = layers.fc(x, size=4, act="softmax")
            loss = layers.mean(layers.cross_entropy(pred, label))
            optimizer.DGCMomentum(0.1, momentum=0.9,
                                  rampup_begin_step=0).minimize(loss)
    with pytest.raises(Exception, match="dgc"):
        C.sharded_entries(main.global_block(), 4)
    # the pure measurement helper must scan the same program without
    # tripping the sharded-only dgc rejection
    assert C.slot_bytes_per_chip(main, fluid.Scope()) >= 0
