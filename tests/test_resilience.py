"""Guarded training (paddle_tpu/resilience/): in-graph anomaly
detection, auto-rollback, retry/backoff, checkpoint durability, and the
deterministic fault-injection (chaos) suite — ISSUE 2 acceptance.

Reference analog: the Fluid runtime's checkpoint_notify machinery and
PS RPC retry loops (the runtime, not the model script, owns failure
handling)."""

import os
import signal

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.resilience import (FaultInjector, GuardedTrainer,
                                   InjectedDispatchError, RetryPolicy,
                                   RetryBudgetExhausted, SimulatedCrash,
                                   TrainingAborted, guard,
                                   install_anomaly_guard, is_transient,
                                   make_torn_checkpoint, retry_call)


def _build(seed=7, lr=0.1):
    main, start = fluid.Program(), fluid.Program()
    main.random_seed = start.random_seed = seed
    with fluid.unique_name.guard():
        with fluid.program_guard(main, start):
            x = layers.data("x", [16], dtype="float32")
            y = layers.data("label", [1], dtype="int64")
            h = layers.fc(x, size=32, act="relu")
            pred = layers.fc(h, size=4, act="softmax")
            loss = layers.mean(layers.cross_entropy(pred, y))
            fluid.optimizer.SGD(lr).minimize(loss)
    return main, start, loss


def _batches(n, batch=16, seed=0, as_feed=True):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        x = rng.rand(batch, 16).astype(np.float32)
        y = np.argmax(x[:, :4], 1).reshape(batch, 1).astype(np.int64)
        out.append({"x": x, "label": y} if as_feed else (x, y))
    return out


# ---------------------------------------------------------------------------
# in-graph anomaly guard
# ---------------------------------------------------------------------------

class TestAnomalyGuard:
    def test_bad_step_is_select_noop(self):
        """A NaN feed must leave every parameter and optimizer slot
        bit-identical while the skip counter advances; the next good
        step trains normally and resets the consecutive counter."""
        main, start, loss = _build()
        scope = fluid.Scope()
        exe = fluid.Executor()
        with fluid.scope_guard(scope):
            exe.run(start)
            install_anomaly_guard(main, loss=loss, scope=scope)
            good = _batches(1)[0]
            bad = dict(good)
            bx = good["x"].copy()
            bx[0, 0] = np.nan
            bad["x"] = bx
            exe.run(main, feed=good, fetch_list=[loss])
            w0 = np.asarray(scope.find_var("fc_0.w_0")).copy()
            (lv,) = exe.run(main, feed=bad, fetch_list=[loss])
            assert not np.isfinite(lv)
            np.testing.assert_array_equal(
                np.asarray(scope.find_var("fc_0.w_0")), w0)
            assert guard.read_counters(scope) == (1.0, 1.0)
            exe.run(main, feed=good, fetch_list=[loss])
            assert guard.read_counters(scope) == (1.0, 0.0)
            assert not np.array_equal(
                np.asarray(scope.find_var("fc_0.w_0")), w0)

    def test_inf_loss_also_skips(self):
        """The flag folds the LOSS in, not just grads — an inf anywhere
        in the checked set gates the update."""
        main, start, loss = _build()
        scope = fluid.Scope()
        exe = fluid.Executor()
        with fluid.scope_guard(scope):
            exe.run(start)
            install_anomaly_guard(main, loss=loss, scope=scope)
            bad = _batches(1)[0]
            bx = bad["x"].copy()
            bx[:] = np.inf
            bad["x"] = bx
            exe.run(main, feed=bad, fetch_list=[loss])
            skipped, consec = guard.read_counters(scope)
            assert (skipped, consec) == (1.0, 1.0)

    def test_counters_carry_through_run_repeated_scan(self):
        """The guard compiles INTO the scan: K poisoned steps inside
        one dispatch self-skip on device and the counters come back in
        the persistable carry (no host round-trips)."""
        main, start, loss = _build()
        scope = fluid.Scope()
        exe = fluid.Executor()
        with fluid.scope_guard(scope):
            exe.run(start)
            install_anomaly_guard(main, loss=loss, scope=scope)
            feed = _batches(1)[0]
            exe.run(main, feed=feed, fetch_list=[loss])  # warm state
            w = np.asarray(scope.find_var("fc_0.w_0")).copy()
            bad = dict(feed)
            bx = feed["x"].copy()
            bx[0, 0] = np.nan
            bad["x"] = bx
            exe.run_repeated(main, feed=bad, fetch_list=[loss],
                             iters=3)
            assert guard.read_counters(scope) == (3.0, 3.0)
            np.testing.assert_array_equal(
                np.asarray(scope.find_var("fc_0.w_0")), w)

    def test_install_is_idempotent_and_needs_optimizer(self):
        main, start, loss = _build()
        scope = fluid.Scope()
        v1 = main._version
        install_anomaly_guard(main, loss=loss, scope=scope)
        v2 = main._version
        install_anomaly_guard(main, loss=loss, scope=scope)
        assert main._version == v2 > v1  # second install is a no-op

        fwd = fluid.Program()
        with fluid.program_guard(fwd):
            x = layers.data("x", [4])
            layers.fc(x, size=2)
        with pytest.raises(Exception, match="optimize"):
            install_anomaly_guard(fwd, scope=scope)

    def test_adam_states_gated_too(self):
        """Adam moments and beta-pow schedules freeze on a skipped step
        (through the batched multi-tensor path, which must apply the
        same select as the per-op gate)."""
        main, start = fluid.Program(), fluid.Program()
        main.random_seed = start.random_seed = 3
        with fluid.unique_name.guard():
            with fluid.program_guard(main, start):
                x = layers.data("x", [8], dtype="float32")
                y = layers.data("y", [1], dtype="float32")
                h = layers.fc(x, size=8, act="tanh")
                p = layers.fc(h, size=1)
                loss = layers.mean(layers.square_error_cost(p, y))
                fluid.optimizer.Adam(1e-2).minimize(loss)
        scope = fluid.Scope()
        exe = fluid.Executor()
        with fluid.scope_guard(scope):
            exe.run(start)
            install_anomaly_guard(main, loss=loss, scope=scope)
            rs = np.random.RandomState(0)
            feed = {"x": rs.rand(4, 8).astype(np.float32),
                    "y": rs.rand(4, 1).astype(np.float32)}
            exe.run(main, feed=feed, fetch_list=[loss])
            state = {n: np.asarray(scope.find_var(n)).copy()
                     for n in scope.local_var_names()
                     if "moment" in n or "beta" in n.lower()}
            assert state, "expected adam accumulators in scope"
            bad = dict(feed)
            bx = feed["x"].copy()
            bx[0, 0] = np.nan
            bad["x"] = bx
            exe.run(main, feed=bad, fetch_list=[loss])
            for n, want in state.items():
                np.testing.assert_array_equal(
                    np.asarray(scope.find_var(n)), want, err_msg=n)


# ---------------------------------------------------------------------------
# retry/backoff
# ---------------------------------------------------------------------------

class TestRetry:
    def test_classification(self):
        assert is_transient(InjectedDispatchError("UNAVAILABLE: x"))
        assert is_transient(ConnectionResetError("peer reset"))
        assert is_transient(TimeoutError("deadline"))

        class XlaRuntimeError(RuntimeError):
            pass

        assert is_transient(
            XlaRuntimeError("UNAVAILABLE: failed to connect"))
        assert not is_transient(
            XlaRuntimeError("INVALID_ARGUMENT: shape mismatch"))
        assert not is_transient(ValueError("bad value"))
        # framework-detected misuse is never transient
        from paddle_tpu.core.enforce import InvalidArgumentError
        assert not is_transient(InvalidArgumentError("UNAVAILABLE"))

    def test_schedule_deterministic_and_capped(self):
        p1 = RetryPolicy(max_retries=4, base_delay=1.0, max_delay=3.0,
                         jitter=0.5, seed=42)
        p2 = RetryPolicy(max_retries=4, base_delay=1.0, max_delay=3.0,
                         jitter=0.5, seed=42)
        assert p1.delays() == p2.delays()  # seed-driven, reproducible
        base = [min(3.0, 1.0 * 2 ** k) for k in range(4)]
        for d, b in zip(p1.delays(), base):
            assert b <= d <= b * 1.5  # jitter in [0, 50%]

    def test_budget_and_propagation(self):
        calls = []

        def flaky():
            calls.append(1)
            raise InjectedDispatchError("UNAVAILABLE: nope")

        policy = RetryPolicy(max_retries=2, base_delay=0.0)
        with pytest.raises(RetryBudgetExhausted) as ei:
            retry_call(flaky, policy)
        assert len(calls) == 3  # initial + 2 retries
        assert len(ei.value.attempts) == 3

        def broken():
            raise ValueError("permanent")

        with pytest.raises(ValueError):  # non-transient: no retry
            retry_call(broken, policy)

        n = {"left": 2}

        def heals():
            if n["left"]:
                n["left"] -= 1
                raise InjectedDispatchError("UNAVAILABLE")
            return "ok"

        out, used = retry_call(heals, policy)
        assert (out, used) == ("ok", 2)


# ---------------------------------------------------------------------------
# checkpoint durability (satellite: io.CheckpointSaver._write ordering)
# ---------------------------------------------------------------------------

def _tiny_state(tmp_path, seed=9):
    main, start = fluid.Program(), fluid.Program()
    main.random_seed = start.random_seed = seed
    with fluid.unique_name.guard():
        with fluid.program_guard(main, start):
            x = layers.data("x", shape=[4], append_batch_size=False)
            w = layers.create_parameter(shape=(4,), dtype="float32",
                                        name="w")
            loss = layers.reduce_sum(layers.square(x - w))
            fluid.optimizer.SGD(0.1).minimize(loss)
    return main, start, loss


class TestCheckpointDurability:
    @pytest.mark.chaos
    def test_marker_inside_tmp_before_rename(self, tmp_path,
                                             monkeypatch):
        """The durability contract itself: at rename time the source
        tmp dir already holds the fsynced _COMPLETE marker, so the ONE
        atomic rename publishes a checkpoint that is complete by
        construction."""
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            main, start, loss = _tiny_state(tmp_path)
            exe = fluid.Executor()
            exe.run(start)
            saver = fluid.io.CheckpointSaver(str(tmp_path), main,
                                             scope=scope)
            seen = []
            real_rename = os.rename

            def spy(src, dst):
                if os.path.basename(src).startswith(".tmp-ckpt-"):
                    seen.append(sorted(os.listdir(src)))
                return real_rename(src, dst)

            monkeypatch.setattr(os, "rename", spy)
            saver.save(1, sync=True)
            assert len(seen) == 1
            assert fluid.io.CheckpointSaver.MARKER in seen[0]
            assert saver.list_checkpoints() == [1]

    @pytest.mark.chaos
    def test_writer_killed_mid_write_stays_invisible(self, tmp_path):
        """A writer killed after N data files (preemption model) must
        strand only a tmp dir: no visible checkpoint, restore_latest
        serves the previous complete step, and a restarted saver
        sweeps the wreckage."""
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            main, start, loss = _tiny_state(tmp_path)
            exe = fluid.Executor()
            exe.run(start)
            saver = fluid.io.CheckpointSaver(str(tmp_path), main,
                                             scope=scope)
            saver.save(1, sync=True)
            w1 = np.asarray(scope.find_var("w")).copy()
            exe.run(main, feed={"x": np.ones(4, np.float32)},
                    fetch_list=[loss])
            inj = FaultInjector(seed=0).crash_save_at(2, after_files=1)
            inj.attach_saver(saver)
            with pytest.raises(SimulatedCrash):
                saver.save(2, sync=True)
            assert saver.list_checkpoints() == [1]
            stranded = [n for n in os.listdir(str(tmp_path))
                        if n.startswith(".tmp-ckpt-")]
            assert stranded  # wreckage exists but is invisible
            assert inj.events[0][0] == "crash_save"
            # restore resumes from the previous complete step
            assert saver.restore_latest(exe) == 1
            np.testing.assert_array_equal(
                np.asarray(scope.find_var("w")), w1)
            # a restarted process sweeps the tmp wreckage
            saver2 = fluid.io.CheckpointSaver(str(tmp_path), main,
                                              scope=scope)
            assert not [n for n in os.listdir(str(tmp_path))
                        if n.startswith(".tmp-ckpt-")]
            assert saver2.list_checkpoints() == [1]

    @pytest.mark.chaos
    def test_prune_killed_after_unmark_stays_invisible(self, tmp_path):
        """_prune's commit point is marker removal: a prune killed
        between unmark and rmtree leaves an unmarked dir that
        restore_latest skips and a restarted saver finishes
        deleting."""
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            main, start, loss = _tiny_state(tmp_path)
            exe = fluid.Executor()
            exe.run(start)
            saver = fluid.io.CheckpointSaver(str(tmp_path), main,
                                             max_to_keep=2,
                                             scope=scope)
            for s in (1, 2):
                saver.save(s, sync=True)
            # simulate: prune of ckpt-1 unmarked it, then died before
            # rmtree (exactly what the marker-first ordering produces)
            os.remove(str(tmp_path / "ckpt-1" /
                          fluid.io.CheckpointSaver.MARKER))
            assert saver.list_checkpoints() == [2]
            assert saver.restore_latest(exe) == 2
            fluid.io.CheckpointSaver(str(tmp_path), main, scope=scope)
            assert not (tmp_path / "ckpt-1").exists()  # swept
            assert (tmp_path / "ckpt-2").exists()

    @pytest.mark.chaos
    def test_torn_marked_checkpoint_falls_back(self, tmp_path):
        """A marked-but-torn checkpoint (pre-fix power loss shape) must
        not stop a rollback: restore_latest warns and serves the next
        older complete one."""
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            main, start, loss = _tiny_state(tmp_path)
            exe = fluid.Executor()
            exe.run(start)
            saver = fluid.io.CheckpointSaver(str(tmp_path), main,
                                             scope=scope)
            saver.save(3, sync=True)
            w3 = np.asarray(scope.find_var("w")).copy()
            make_torn_checkpoint(str(tmp_path), 9,
                                 fluid.io.CheckpointSaver.MARKER)
            assert saver.list_checkpoints() == [3, 9]
            with pytest.warns(UserWarning, match="ckpt-9"):
                assert saver.restore_latest(exe) == 3
            np.testing.assert_array_equal(
                np.asarray(scope.find_var("w")), w3)

    @pytest.mark.chaos
    def test_sigterm_mid_save_flushes_complete_checkpoint(
            self, tmp_path, monkeypatch):
        """The preemption notice arriving while a background write lies
        dead mid-tmp-dir: the handler drains, rewrites the retained
        snapshot synchronously, takes a fresh final save, and re-raises
        the default action (observed via the patched os.kill)."""
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            main, start, loss = _tiny_state(tmp_path)
            exe = fluid.Executor()
            exe.run(start)
            saver = fluid.io.CheckpointSaver(str(tmp_path), main,
                                             scope=scope)
            inj = FaultInjector(seed=0).crash_save_at(1, after_files=1)
            inj.attach_saver(saver)
            h = saver.save(1)  # background write dies mid-save
            h._thread.join()
            assert saver.list_checkpoints() == []
            w_at_save = np.asarray(scope.find_var("w")).copy()
            # weights move on after the save — the flushed ckpt-1 must
            # hold the RETAINED snapshot, not these
            exe.run(main, feed={"x": np.ones(4, np.float32)},
                    fetch_list=[loss])

            kills = []
            monkeypatch.setattr(os, "kill",
                                lambda pid, sig: kills.append(sig))
            saver.install_signal_handler(signals=(signal.SIGTERM,),
                                         get_step=lambda: 2)
            try:
                signal.raise_signal(signal.SIGTERM)
            finally:
                signal.signal(signal.SIGTERM, signal.SIG_DFL)
            assert kills == [signal.SIGTERM]
            assert saver.list_checkpoints() == [1, 2]
            import paddle_tpu.io as io_mod
            with open(str(tmp_path / "ckpt-1" / "w"), "rb") as f:
                got, _ = io_mod.deserialize_tensor(f.read())
            np.testing.assert_array_equal(got, w_at_save)


# ---------------------------------------------------------------------------
# GuardedTrainer: the chaos acceptance suite
# ---------------------------------------------------------------------------

def _trainer(tmp_path, faults=None, seed=7, **kw):
    main, start, loss = _build(seed=seed)
    scope = fluid.Scope()
    exe = fluid.Executor()
    kw.setdefault("checkpoint_every", 2)
    kw.setdefault("rollback_after", 3)
    kw.setdefault("retry", RetryPolicy(max_retries=3, base_delay=0.0))
    return GuardedTrainer(exe, main, loss, startup_program=start,
                          scope=scope, checkpoint_dir=str(tmp_path),
                          faults=faults, sync_saves=True, **kw)


class TestGuardedTrainer:
    @pytest.mark.chaos
    def test_chaos_acceptance(self, tmp_path):
        """ISSUE 2 acceptance: with NaN grads at steps 5-7, a writer
        kill mid-save at step 8, and one transient dispatch failure at
        step 11, the guarded run completes; its final loss is within
        rtol 1e-2 of the fault-free twin; and the structured summary
        reports the skipped/rolled-back/retried counts."""
        feeds = _batches(30)
        base = _trainer(tmp_path / "clean").train(feeds)
        assert base["skipped_steps"] == 0
        assert base["aborted"] is None

        inj = (FaultInjector(seed=1)
               .nan_grad_at(5, 6, 7)
               .crash_save_at(8, after_files=1)
               .transient_dispatch_at(11, times=1))
        s = _trainer(tmp_path / "chaos", faults=inj).train(feeds)
        assert s["aborted"] is None
        assert s["steps_run"] == 30
        assert s["skipped_steps"] == 3
        assert s["rollbacks"] == 1
        assert s["retries"] == 1
        assert s["save_failures"] == 1
        fired = [e[0] for e in inj.events]
        assert fired.count("nan_grad") == 3
        assert "crash_save" in fired and "transient_dispatch" in fired
        np.testing.assert_allclose(s["final_loss"],
                                   base["final_loss"], rtol=1e-2)

    @pytest.mark.chaos
    def test_rollback_replays_poisoned_window_exactly(self, tmp_path):
        """One-shot NaN faults + pre-window restore + replay: the
        post-recovery trajectory is BIT-EXACT against fault-free (the
        model has no RNG ops, so the monotonic PRNG re-fold changes
        nothing and the replayed updates land identically)."""
        feeds = _batches(14)
        base = _trainer(tmp_path / "clean").train(feeds)
        inj = FaultInjector(seed=0).nan_grad_at(4, 5, 6)
        s = _trainer(tmp_path / "chaos", faults=inj).train(feeds)
        assert s["rollbacks"] == 1
        clean = [v for v in s["losses"] if np.isfinite(v)]
        assert clean == base["losses"]  # bit-exact, including replay

    @pytest.mark.chaos
    def test_retry_budget_exhaustion_degrades_gracefully(self,
                                                         tmp_path):
        """A persistent dispatch failure aborts with a structured
        report AND a final synchronous checkpoint."""
        inj = FaultInjector(seed=0).transient_dispatch_at(3, times=99)
        t = _trainer(tmp_path, faults=inj,
                     retry=RetryPolicy(max_retries=2, base_delay=0.0))
        with pytest.raises(TrainingAborted) as ei:
            t.train(_batches(10))
        rep = ei.value.report
        assert "retry budget exhausted" in ei.value.reason
        assert rep["retries"] == 0  # budget burned, none succeeded
        assert rep["steps_run"] == 3
        assert rep["checkpoints"], "final checkpoint must be flushed"
        assert isinstance(ei.value.__cause__, RetryBudgetExhausted)

    @pytest.mark.chaos
    def test_persistent_anomaly_spends_rollback_budget(self, tmp_path):
        """NaN on EVERY step re-poisons each replay; after
        max_rollbacks the trainer aborts instead of looping forever."""
        inj = FaultInjector(seed=0).nan_grad_at(*range(40))
        t = _trainer(tmp_path, faults=inj, max_rollbacks=2)
        with pytest.raises(TrainingAborted) as ei:
            t.train(_batches(40))
        assert "anomaly persists" in ei.value.reason
        assert ei.value.report["rollbacks"] == 2

    @pytest.mark.chaos
    def test_stream_input_rollback_continues_forward(self, tmp_path):
        """train_from_dataset posture: a stream cannot be replayed, so
        rollback restores state (weights rewind) and continues with the
        NEXT batches — the run still completes finite."""
        inj = FaultInjector(seed=0).nan_grad_at(3, 4, 5)
        t = _trainer(tmp_path, faults=inj)
        s = t.train(iter(_batches(12)))
        assert s["rollbacks"] == 1
        assert s["aborted"] is None
        # 12 batches consumed, but the restore rewound steps_run to
        # the pre-window checkpoint (step 2): 2 + the 6 post-window
        # batches = 8
        assert s["steps_run"] == 8
        assert s["skipped_steps"] == 3
        assert np.isfinite(s["final_loss"])

    @pytest.mark.chaos
    def test_train_repeated_guarded_chunks(self, tmp_path):
        """The scan-chunked driver: a transient failure before a chunk
        retries; counters ride the scan carry; totals add up."""
        inj = FaultInjector(seed=0).transient_dispatch_at(4, times=1)
        t = _trainer(tmp_path, faults=inj, checkpoint_every=0)
        feed = _batches(1)[0]
        s = t.train_repeated(feed, iters=10, chunk=4)
        assert s["steps_run"] == 10
        assert s["retries"] == 1
        assert s["aborted"] is None
        assert np.isfinite(s["final_loss"])


# ---------------------------------------------------------------------------
# q8 error-feedback residuals across checkpoint/restore (satellite)
# ---------------------------------------------------------------------------

def _q8_setup(seed=11):
    import jax
    from paddle_tpu.parallel import make_mesh
    main, start, loss = _build(seed=seed)
    bs = fluid.BuildStrategy()
    bs.gradient_sync = "q8"
    prog = fluid.CompiledProgram(main).with_data_parallel(
        build_strategy=bs,
        mesh=make_mesh({"dp": 4}, jax.devices()[:4]))
    return main, start, loss, prog


class TestQ8ResidualCheckpointing:
    @pytest.mark.chaos
    def test_save_restore_continue_is_bitexact(self, tmp_path):
        """save -> restore -> continue must match an uninterrupted q8
        run's loss trajectory BIT-exactly: the error-feedback residuals
        are persistables, so they checkpoint and restore with the
        weights; losing them would silently degrade quantized
        training."""
        from paddle_tpu.parallel import collectives as C
        feeds = _batches(6)

        # uninterrupted twin
        main, start, loss, prog = _q8_setup()
        scope = fluid.Scope()
        exe = fluid.Executor()
        full = []
        with fluid.scope_guard(scope):
            exe.run(start)
            for f in feeds:
                (lv,) = exe.run(prog, feed=f, fetch_list=[loss])
                full.append(float(lv))

        # interrupted: 3 steps, checkpoint, fresh process restores
        main2, start2, loss2, prog2 = _q8_setup()
        scope2 = fluid.Scope()
        exe2 = fluid.Executor()
        with fluid.scope_guard(scope2):
            exe2.run(start2)
            first = []
            for f in feeds[:3]:
                (lv,) = exe2.run(prog2, feed=f, fetch_list=[loss2])
                first.append(float(lv))
            saver = fluid.io.CheckpointSaver(str(tmp_path), main2,
                                             scope=scope2)
            saver.save(3, sync=True)
        assert first == full[:3]
        # residual slots are IN the checkpoint, nonzero
        res_files = [n for n in os.listdir(str(tmp_path / "ckpt-3"))
                     if n.endswith(C.RESIDUAL_SUFFIX)]
        assert len(res_files) == 4, res_files

        main3, start3, loss3, prog3 = _q8_setup()
        scope3 = fluid.Scope()
        exe3 = fluid.Executor()
        with fluid.scope_guard(scope3):
            exe3.run(start3)
            # a restarted process must materialize the residual slots
            # before restoring into them
            C.ensure_residual_vars(main3, scope3)
            saver3 = fluid.io.CheckpointSaver(str(tmp_path), main3,
                                              scope=scope3)
            assert saver3.restore_latest(exe3) == 3
            cont = []
            for f in feeds[3:]:
                (lv,) = exe3.run(prog3, feed=f, fetch_list=[loss3])
                cont.append(float(lv))
        assert cont == full[3:]  # bit-exact continuation

    @pytest.mark.chaos
    def test_residuals_shielded_when_sparse_param_sorts_first(self):
        """The guard's boundary (which includes sparse-grad params)
        can sit EARLIER than the q8 collective's (which excludes
        them) — the optimizer sorts params by name, so an embedding
        named 'aaa_*' puts its optimize op first. post_sync must still
        run AFTER the collective, or a NaN step writes NaN residuals
        while reporting the step as handled."""
        import jax
        from paddle_tpu.parallel import collectives as C
        from paddle_tpu.parallel import make_mesh
        main, start = fluid.Program(), fluid.Program()
        main.random_seed = start.random_seed = 5
        with fluid.unique_name.guard():
            with fluid.program_guard(main, start):
                ids = layers.data("ids", shape=[1], dtype="int64")
                label = layers.data("label", shape=[1], dtype="int64")
                emb = layers.embedding(
                    ids, size=(40, 8), is_sparse=True,
                    param_attr=fluid.ParamAttr(name="aaa_table"))
                emb = layers.reshape(emb, (-1, 8))
                pred = layers.fc(emb, size=4, act="softmax")
                loss = layers.mean(layers.cross_entropy(pred, label))
                fluid.optimizer.SGD(0.1).minimize(loss)
        bs = fluid.BuildStrategy()
        bs.gradient_sync = "q8"
        prog = fluid.CompiledProgram(main).with_data_parallel(
            build_strategy=bs,
            mesh=make_mesh({"dp": 4}, jax.devices()[:4]))
        scope = fluid.Scope()
        exe = fluid.Executor()
        with fluid.scope_guard(scope):
            exe.run(start)
            install_anomaly_guard(main, loss=loss, scope=scope)
            # divergence precondition: guard boundary < sync boundary
            gb, _gk, _rk = guard._guard_entries(main.global_block())
            sp = C.make_plan(main.global_block(), "q8",
                             make_mesh({"dp": 4}, jax.devices()[:4]))
            assert gb < sp.boundary
            rs = np.random.RandomState(0)
            iv = rs.randint(0, 40, size=(16, 1)).astype(np.int64)
            yv = (iv % 4).astype(np.int64)
            exe.run(prog, feed={"ids": iv, "label": yv},
                    fetch_list=[loss])
            res = {n: np.asarray(scope.find_var(n)).copy()
                   for n in scope.local_var_names()
                   if n.endswith(C.RESIDUAL_SUFFIX)}
            assert res
            # both feeds are int, so poison the only float state the
            # forward reads: the embedding table — every grad NaNs
            w = np.asarray(scope.find_var("aaa_table")).copy()
            w_bad = w.copy()
            w_bad[0, 0] = np.nan
            scope.set_var("aaa_table", w_bad)
            (lv,) = exe.run(prog, feed={"ids": iv, "label": yv},
                            fetch_list=[loss])
            assert not np.isfinite(lv)
            assert guard.read_counters(scope)[1] >= 1.0
            for n, want in res.items():
                got = np.asarray(scope.find_var(n))
                assert np.isfinite(got).all(), n
                np.testing.assert_array_equal(got, want, err_msg=n)

    @pytest.mark.chaos
    def test_guard_shields_residuals_on_bad_step(self, tmp_path):
        """A NaN step through the q8 collective must leave the
        error-feedback residuals bit-identical (an unguarded NaN there
        would poison every later step through the feedback loop) while
        the guard skips the update."""
        from paddle_tpu.parallel import collectives as C
        main, start, loss, prog = _q8_setup()
        scope = fluid.Scope()
        exe = fluid.Executor()
        feeds = _batches(3)
        with fluid.scope_guard(scope):
            exe.run(start)
            install_anomaly_guard(main, loss=loss, scope=scope)
            exe.run(prog, feed=feeds[0], fetch_list=[loss])
            res = {n: np.asarray(scope.find_var(n)).copy()
                   for n in scope.local_var_names()
                   if n.endswith(C.RESIDUAL_SUFFIX)}
            assert res and any(np.abs(r).max() > 0
                               for r in res.values())
            bad = dict(feeds[1])
            bx = bad["x"].copy()
            bx[0, 0] = np.nan
            bad["x"] = bx
            (lv,) = exe.run(prog, feed=bad, fetch_list=[loss])
            assert not np.isfinite(lv)
            assert guard.read_counters(scope)[1] == 1.0
            for n, want in res.items():
                got = np.asarray(scope.find_var(n))
                assert np.isfinite(got).all(), n
                np.testing.assert_array_equal(got, want, err_msg=n)


class TestGuardLifecycle:
    def test_pre_guard_checkpoint_still_restores(self, tmp_path):
        """Checkpoints written BEFORE the guard existed lack the
        counter vars; restore must default-fill them instead of
        failing (and the trainer's resume path must work)."""
        main, start, loss = _build()
        scope = fluid.Scope()
        exe = fluid.Executor()
        with fluid.scope_guard(scope):
            exe.run(start)
            exe.run(main, feed=_batches(1)[0], fetch_list=[loss])
            fluid.io.CheckpointSaver(str(tmp_path), main,
                                     scope=scope).save(5, sync=True)
        # fresh process installs the guard, then restores the old ckpt
        main2, start2, loss2 = _build()
        scope2 = fluid.Scope()
        exe2 = fluid.Executor()
        with fluid.scope_guard(scope2):
            exe2.run(start2)
            install_anomaly_guard(main2, loss=loss2, scope=scope2)
            saver = fluid.io.CheckpointSaver(str(tmp_path), main2,
                                             scope=scope2)
            assert saver.restore_latest(exe2) == 5
            assert guard.read_counters(scope2) == (0.0, 0.0)
            exe2.run(main2, feed=_batches(1)[0], fetch_list=[loss2])

    def test_accumulation_window_stays_in_lockstep(self):
        """NaN on the window-closing micro-step (accumulate_steps=2):
        the guard zeroes the poisoned grad instead of freezing the
        window, so the accumulator resets with the counter and the
        next window cannot apply a double-sized update."""
        main, start = fluid.Program(), fluid.Program()
        main.random_seed = start.random_seed = 4
        with fluid.unique_name.guard():
            with fluid.program_guard(main, start):
                x = layers.data("x", [8], dtype="float32")
                y = layers.data("y", [1], dtype="float32")
                pred = layers.fc(x, size=1)
                loss = layers.mean(layers.square_error_cost(pred, y))
                fluid.optimizer.SGD(0.1).minimize(
                    loss, accumulate_steps=2)
        scope = fluid.Scope()
        exe = fluid.Executor()
        with fluid.scope_guard(scope):
            exe.run(start)
            install_anomaly_guard(main, loss=loss, scope=scope)
            rs = np.random.RandomState(0)
            feed = {"x": rs.rand(4, 8).astype(np.float32),
                    "y": rs.rand(4, 1).astype(np.float32)}
            bad = dict(feed)
            bx = feed["x"].copy()
            bx[0, 0] = np.nan
            bad["x"] = bx
            exe.run(main, feed=feed, fetch_list=[loss])   # micro 1
            w_mid = np.asarray(scope.find_var("fc_0.w_0")).copy()
            exe.run(main, feed=bad, fetch_list=[loss])    # closing+NaN
            acc_names = [n for n in scope.local_var_names()
                         if "_grad_acc" in n and "counter" not in n]
            assert acc_names
            w_after = np.asarray(scope.find_var("fc_0.w_0"))
            # the window CLOSED with the poisoned contribution zeroed:
            # update applied (params moved, finite), accumulator reset
            assert np.isfinite(w_after).all()
            assert not np.array_equal(w_after, w_mid)
            for n in acc_names:
                np.testing.assert_array_equal(
                    np.asarray(scope.find_var(n)),
                    np.zeros_like(np.asarray(scope.find_var(n))),
                    err_msg=n)
            assert guard.read_counters(scope) == (1.0, 1.0)
            # next full window trains normally and stays finite
            exe.run(main, feed=feed, fetch_list=[loss])
            (lv,) = exe.run(main, feed=feed, fetch_list=[loss])
            assert np.isfinite(lv)
            assert np.isfinite(
                np.asarray(scope.find_var("fc_0.w_0"))).all()

    def test_deleted_buffer_error_heals_via_retry(self):
        """A dispatch that dies after donation leaves deleted arrays;
        the NEXT attempt's 'has been deleted' error must classify
        transient so _on_retry's checkpoint heal can fire."""
        assert is_transient(
            RuntimeError("Array has been deleted with shape=f32[4]"))
        seq = [InjectedDispatchError("UNAVAILABLE: reset"),
               RuntimeError("Array has been deleted"), "ok"]
        healed = []

        def fn():
            step = seq.pop(0)
            if isinstance(step, Exception):
                raise step
            return step

        out, used = retry_call(
            fn, RetryPolicy(max_retries=2, base_delay=0.0),
            on_retry=lambda a, e, d: healed.append(str(e)))
        assert (out, used) == ("ok", 2)
        assert any("deleted" in m for m in healed)


    def test_reinstall_into_fresh_scope_keeps_counting(self):
        """A second install of an already-guarded program into a FRESH
        scope must still materialize the counters there — otherwise
        skip accounting and rollback are silently disabled for the
        second run."""
        main, start, loss = _build()
        s1, s2 = fluid.Scope(), fluid.Scope()
        exe = fluid.Executor()
        install_anomaly_guard(main, loss=loss, scope=s1)
        install_anomaly_guard(main, loss=loss, scope=s2)  # re-install
        assert s2.has_var(guard.SKIPPED_VAR)
        bad = _batches(1)[0]
        bx = bad["x"].copy()
        bx[0, 0] = np.nan
        bad["x"] = bx
        with fluid.scope_guard(s2):
            exe.run(start)
            guard.ensure_guard_state(s2)
            exe.run(main, feed=bad, fetch_list=[loss])
        assert guard.read_counters(s2) == (1.0, 1.0)
        # the in-use scope's counters must NOT be reset by re-install
        s2.set_var(guard.SKIPPED_VAR,
                   np.ones((1,), np.float32))
        install_anomaly_guard(main, loss=loss, scope=s2)
        assert guard.read_counters(s2)[0] == 1.0

    def test_to_dict_roundtrip_keeps_loss_check(self):
        """Serialization must carry the guard config — the loss name
        in particular — not just the gate attrs."""
        main, start, loss = _build()
        install_anomaly_guard(main, loss=loss, scope=fluid.Scope())
        p2 = fluid.Program.from_dict(main.to_dict())
        assert p2._anomaly_guard == {"loss": loss.name}
        # legacy desc (no anomaly_guard key): the sniff path pins
        # loss=None, and a later install with a loss upgrades it
        legacy = main.to_dict()
        legacy.pop("anomaly_guard")
        p3 = fluid.Program.from_dict(legacy)
        assert p3._anomaly_guard == {"loss": None}
        v = p3._version
        install_anomaly_guard(p3, loss=loss.name, scope=fluid.Scope())
        assert p3._anomaly_guard == {"loss": loss.name}
        assert p3._version > v  # cached steps must recompile

    def test_trainer_resumes_prior_checkpoints(self, tmp_path):
        """Pointing a trainer at a dir with prior-run checkpoints must
        RESUME (restore + adopt the step number), keeping the rollback
        invariant 'a checkpoint <= steps_run exists' intact."""
        feeds = _batches(6)
        t1 = _trainer(tmp_path, checkpoint_every=2)
        s1 = t1.train(feeds)
        assert s1["checkpoints"][-1] == 6
        w_end = np.asarray(t1._scope.find_var("fc_0.w_0")).copy()

        t2 = _trainer(tmp_path, checkpoint_every=2)
        s2 = t2.train(feeds)  # fresh trainer, same dir: resumes at 6
        assert s2["steps_run"] == 12
        assert s2["checkpoints"][-1] == 12
        # it started from the restored weights, not from init
        np.testing.assert_array_equal(
            np.asarray(t2._scope.find_var("fc_0.w_0")).shape,
            w_end.shape)
        assert s2["losses"][0] < s1["losses"][0]  # warm start


# ---------------------------------------------------------------------------
# program uid (satellite: executor cache key)
# ---------------------------------------------------------------------------

def test_program_uid_not_id_in_executor_cache():
    """Two same-shaped programs (identical version/feed/fetch
    signatures) must occupy DISTINCT run_repeated cache slots keyed by
    their monotonic uid — id() reuse after GC could alias them."""
    def build(c):
        main = fluid.Program()
        with fluid.program_guard(main):
            x = layers.data("x", [2])
            y = layers.scale(x, scale=float(c))
        return main, y

    exe = fluid.Executor()
    feed = {"x": np.ones((1, 2), np.float32)}
    m1, y1 = build(2.0)
    m2, y2 = build(3.0)
    assert m1._uid != m2._uid
    assert m1.clone()._uid not in (m1._uid, m2._uid)
    r1 = exe.run_repeated(m1, feed=feed, fetch_list=[y1.name], iters=2)
    r2 = exe.run_repeated(m2, feed=feed, fetch_list=[y2.name], iters=2)
    assert float(np.ravel(r1[0])[0]) == 2.0
    assert float(np.ravel(r2[0])[0]) == 3.0
    repeat_keys = [k for k in exe._cache if k[0] == "run_repeated"]
    assert sorted(k[2] for k in repeat_keys) == sorted(
        [m1._uid, m2._uid])
