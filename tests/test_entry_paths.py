"""``Executor.run`` (plain and through ``CompiledProgram.run``),
``run_repeated`` and ``run_pipelined`` share ONE body
(``Executor._run_impl``): what it guarantees is checked here for
every entry, on a plain program and on a dp=4 mesh of the CPU's
virtual devices. ``run_repeated`` of a CompiledProgram is still a loop
of ``run`` calls, so its dp cases dispatch once a step."""

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu import observability as obs
from paddle_tpu.core.flags import FLAGS
from paddle_tpu.parallel import make_mesh

K = 3
ENTRIES = ("run", "run_repeated", "run_pipelined")
PATHS = [(e, m) for e in ENTRIES for m in ("plain", "dp")]


def _ids(params):
    return ["-".join(p) if isinstance(p, tuple) else p for p in params]


def _net():
    """log(x) feeds the loss, so a negative feed fetches NaN."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4])
        loss = layers.mean(layers.fc(layers.log(x), size=2))
        fluid.optimizer.SGD(0.01).minimize(loss)
    return main, startup, loss


def _target(main, loss, mode):
    if mode == "plain":
        return main
    return fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, mesh=make_mesh({"dp": 4}, jax.devices()[:4]))


def _call(exe, entry, target, loss, x, **kw):
    if entry == "run":
        return exe.run(target, feed={"x": x}, fetch_list=[loss], **kw)
    if entry == "run_repeated":
        return exe.run_repeated(target, feed={"x": x}, fetch_list=[loss],
                                iters=K, **kw)
    return exe.run_pipelined(target, feed_chunk={"x": np.stack([x] * K)},
                             fetch_list=[loss], **kw)


def _steps(entry):
    return 1 if entry == "run" else K


def _dispatches(entry, mode):
    return K if (entry, mode) == ("run_repeated", "dp") else 1


@pytest.fixture
def started():
    main, startup, loss = _net()
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        yield exe, main, loss


X = np.full((8, 4), 2.0, np.float32)


@pytest.mark.parametrize("entry,mode", PATHS, ids=_ids(PATHS))
def test_check_nan_inf_raises_from_every_entry(started, entry, mode):
    exe, main, loss = started
    target = _target(main, loss, mode)
    old = FLAGS.check_nan_inf
    FLAGS.check_nan_inf = True
    try:
        assert np.isfinite(_call(exe, entry, target, loss, X)[0])
        with pytest.raises(FloatingPointError, match="NaN/Inf"):
            _call(exe, entry, target, loss, -X)
    finally:
        FLAGS.check_nan_inf = old


@pytest.mark.parametrize("entry,mode", PATHS, ids=_ids(PATHS))
def test_same_signature_again_is_one_dispatch_and_no_build(
        started, entry, mode):
    exe, main, loss = started
    target = _target(main, loss, mode)
    _call(exe, entry, target, loss, X)
    before = exe.telemetry()
    n_exe, n_traceable = len(exe.aot_artifacts()), len(exe._cache)
    evs = obs.journal_events()
    mark = evs[-1]["seq"] if evs else 0
    _call(exe, entry, target, loss, X)
    after = exe.telemetry()
    assert after["steps"] - before["steps"] == _steps(entry)
    assert after["dispatches"] - before["dispatches"] == \
        _dispatches(entry, mode)
    for k in ("compiles", "xla_compiles", "cache_loads"):
        assert after[k] == before[k], k
    assert after["compiles_by_entry"] == before["compiles_by_entry"]
    assert (len(exe.aot_artifacts()), len(exe._cache)) == \
        (n_exe, n_traceable)
    for kind in ("executor_compile", "compile_cache_hit"):
        assert not obs.journal_events(kind=kind, since_seq=mark)
    assert not exe.dispatch_inflight()


@pytest.mark.parametrize("entry,mode", PATHS, ids=_ids(PATHS))
def test_an_executable_that_raises_settles_the_dispatch(
        started, entry, mode, monkeypatch):
    exe, main, loss = started
    target = _target(main, loss, mode)
    _call(exe, entry, target, loss, X)

    def boom(*a, **k):
        raise RuntimeError("device lost")

    monkeypatch.setattr(exe, "_call_executable", boom)
    beacon = exe.dispatch_beacon.count
    with pytest.raises(RuntimeError, match="device lost"):
        _call(exe, entry, target, loss, X)
    monkeypatch.undo()
    assert not exe.dispatch_inflight()
    assert exe._dispatch_count == exe._dispatches_done
    assert exe.dispatch_beacon.count == beacon + 1
    # and the entry still works
    assert np.isfinite(_call(exe, entry, target, loss, X)[0])


class _Plan:
    """Stands where an ``engine.PipelinePlan`` stands: keyed by its
    signature, bound against the block when the step is assembled. It
    binds to no schedule, so the step stays the sequential one."""

    def __init__(self, tag):
        self.tag = tag
        self.bound = []

    def signature(self):
        return ("plan", self.tag)

    def bind(self, block, mesh=None):
        self.bound.append(block)
        return None


@pytest.mark.parametrize("entry", ENTRIES)
def test_a_programs_plan_keys_and_reaches_every_entry(started, entry):
    exe, main, loss = started
    want = _call(exe, entry, main, loss, X)
    keys = set(exe._cache)
    main._pipeline_plan = plan = _Plan(1)
    _call(exe, entry, main, loss, X)
    new, = set(exe._cache) - keys
    assert new[0] == entry and new[-1] == plan.signature()
    assert plan.bound == [main.global_block()]
    # another plan is another executable; the same plan is not
    main._pipeline_plan = _Plan(2)
    _call(exe, entry, main, loss, X)
    main._pipeline_plan = plan
    _call(exe, entry, main, loss, X)
    assert len(set(exe._cache) - keys) == 2
    assert len(plan.bound) == 1
    assert np.isfinite(want[0])


@pytest.mark.parametrize("entry", ENTRIES)
def test_library_and_the_flag_give_one_key(started, entry):
    """``library=`` (the scans take it) and ``FLAGS.op_library`` are
    resolved in one place: the same mix is the same executable."""
    exe, main, loss = started
    mix = "fc:pallas"
    old = FLAGS.op_library
    FLAGS.op_library = mix
    try:
        _call(exe, entry, main, loss, X)
    finally:
        FLAGS.op_library = old
    def keys():
        return [k for k in exe._cache if k[2] == main._uid]

    key, = keys()
    assert key[0] == entry and mix in key
    built = exe.compile_count
    if entry != "run":
        _call(exe, entry, main, loss, X, library=mix)
        assert exe.compile_count == built
        assert keys() == [key]
    _call(exe, entry, main, loss, X)
    assert exe.compile_count == built + 1


@pytest.mark.parametrize("mode", ["plain", "dp"])
def test_use_program_cache_false_is_the_cached_path(started, mode):
    """The parameter is the reference's; it selects nothing: the
    executable is built AOT, booked and kept like any other."""
    exe, main, loss = started
    target = _target(main, loss, mode)
    a = exe.run(target, feed={"x": X}, fetch_list=[loss],
                use_program_cache=False)
    t = exe.telemetry()
    rec, = [r for r in exe.aot_artifacts()
            if r["program_uid"] == main._uid]
    assert rec["mode"] == "xla" and rec["entry"] == "run"
    assert t["compiles_by_entry"]["run"] == 2     # startup's and this
    b = exe.run(target, feed={"x": X}, fetch_list=[loss])
    assert exe.telemetry()["compiles"] == t["compiles"]
    assert np.isfinite(a[0]) and np.isfinite(b[0])
