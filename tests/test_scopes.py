"""The program names its own work: ``name_scope`` stamps ops, backward
ops inherit, every op is lowered under ``<phase>/<layer>/<op type>``,
the names reach the optimized HLO and move neither the lowered text nor
the store's key, and ``profiler.scope_table`` charges a trace to them."""

import contextlib
import re

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import compile_cache, executor, layers, profiler
from paddle_tpu.contrib import mixed_precision as amp
from paddle_tpu.models import bert as B
from paddle_tpu.models import transformer as T

LAYER_KINDS = ("embedding", "attention", "ffn", "residual_norm",
               "vocab_head", "loss")


def _scoped_fc_program():
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4, 8], append_batch_size=False)
        with fluid.name_scope("ffn"):
            h = layers.fc(x, size=8, act="relu")
            with fluid.name_scope("inner"):
                h = layers.scale(h, scale=2.0)
        with fluid.name_scope("loss"):
            loss = layers.reduce_sum(h)
        bare = layers.scale(loss, scale=1.0)
        fluid.optimizer.Adam(0.1).minimize(bare)
    return main, startup, bare


def test_name_scope_stamps_op_namescope():
    main, _startup, _loss = _scoped_fc_program()
    by_type = {}
    for op in main.global_block().ops:
        if op.attrs.get("op_role") is None:
            by_type.setdefault(op.type, []).append(
                op.attrs.get("op_namescope"))
    assert set(by_type["mul"]) == {"/ffn/"}
    assert by_type["reduce_sum"] == ["/loss/"]
    # nested scopes give the reference's full path, innermost last;
    # outside any scope nothing is stamped
    assert sorted(by_type["scale"], key=str) == ["/ffn/inner/", None]
    assert fluid.framework.innermost_scope("/ffn/inner/") == "inner"
    assert fluid.framework.innermost_scope(None) == "-"


def test_backward_ops_inherit_the_forward_scope():
    main, _startup, _loss = _scoped_fc_program()
    ops = main.global_block().ops
    grads = [op for op in ops if op.type == "vjp"]
    assert grads
    for op in grads:
        fwd = ops[op.attrs["fwd_op_index"]]
        assert op.attrs.get("op_namescope") == \
            fwd.attrs.get("op_namescope")
        # the forward op's scope is the gradient op's own attribute,
        # never an argument of the lowering it re-enters
        assert "op_namescope" not in op.attrs["fwd_attrs"]
    assert {op.attrs.get("op_namescope") for op in grads} >= \
        {"/ffn/", "/ffn/inner/", "/loss/", None}
    updates = [op for op in ops if op.type == "adam"]
    assert updates and all(op.attrs["op_namescope"] == "/optimizer/"
                           for op in updates)


def test_clone_keeps_the_scope():
    main, _startup, _loss = _scoped_fc_program()
    want = [(op.type, op.attrs.get("op_namescope"))
            for op in main.global_block().ops]
    assert [(op.type, op.attrs.get("op_namescope"))
            for op in main.clone().global_block().ops] == want
    test = main.clone(for_test=True).global_block().ops
    assert [(op.type, op.attrs.get("op_namescope")) for op in test] \
        == [w for w, op in zip(want, main.global_block().ops)
            if op.attrs.get("op_role") is None]


def _toy(model):
    """(main, startup, loss, feed) of a toy model under AMP + Adam."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        if model == "transformer":
            cfg = T.TransformerConfig(
                src_vocab=64, tgt_vocab=64, max_len=16, d_model=32,
                d_ffn=64, n_head=2, n_layer=2, dropout=0.1)
            loss = T.transformer(cfg)[0]
            feed = T.make_fake_batch(cfg, 4)
        else:
            cfg = B.BertConfig(
                vocab_size=64, hidden_size=32, num_hidden_layers=2,
                num_attention_heads=2, intermediate_size=64,
                max_position_embeddings=16, seq_len=16,
                max_predictions_per_seq=4)
            loss = B.bert_pretrain(cfg)[0]
            feed = B.make_fake_pretrain_batch(cfg, 4)
        amp.decorate(fluid.optimizer.AdamOptimizer(1e-3)).minimize(loss)
    return main, startup, loss, feed


def _train_step_artifact(model, scope):
    main, startup, loss, feed = _toy(model)
    exe = fluid.Executor()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run_repeated(main, feed=feed, fetch_list=[loss], iters=2)
    rec, = [r for r in exe.aot_artifacts()
            if r["entry"] == "run_repeated"]
    return rec


@pytest.mark.parametrize("model", ["transformer", "bert"])
def test_scopes_reach_the_optimized_hlo(model):
    hlo = _train_step_artifact(model, fluid.Scope())["optimized_hlo"]
    names, = profiler.hlo_op_names(hlo).values()
    found = {m.groups()[:2] for m in map(profiler._SCOPE.search,
                                         names.values()) if m}
    assert {p for p, _ in found} >= {"fwd", "bwd", "opt"}
    for kind in LAYER_KINDS:
        assert ("fwd", kind) in found and ("bwd", kind) in found, kind
    assert ("opt", "optimizer") in found and ("opt", "amp") in found
    # what does the work carries a scope: dots, fusions, custom calls
    # (a fusion XLA made without metadata takes what it fused)
    work = re.findall(r"^\s+(?:ROOT )?%?([\w.\-]+) = .*? "
                      r"(?:fusion|dot|custom-call|convolution)\(",
                      hlo, re.M)
    scoped = sum(bool(profiler._SCOPE.search(names.get(w, "")))
                 for w in work)
    assert len(work) > 100 and scoped >= 0.95 * len(work), \
        (scoped, len(work))


def test_scopes_move_neither_the_lowered_text_nor_the_key(monkeypatch):
    """The store's key is the lowered text's hash: a scope that moved
    it would orphan every stored executable."""
    texts = []
    real = compile_cache.canonical_fingerprint

    def recording(text):
        texts.append(text)
        return real(text)

    monkeypatch.setattr(compile_cache, "canonical_fingerprint",
                        recording)
    with_scopes = _train_step_artifact("transformer", fluid.Scope())
    named = list(texts)
    del texts[:]
    monkeypatch.setattr(executor, "_named_scope",
                        lambda name: contextlib.nullcontext())
    without = _train_step_artifact("transformer", fluid.Scope())
    assert "fwd/attention/" in with_scopes["optimized_hlo"]
    assert "fwd/attention/" not in without["optimized_hlo"]
    assert len(named) == len(texts) == 2      # startup, the step
    assert named == texts
    assert with_scopes["fingerprint"] == without["fingerprint"]


# -- scope_table on a hand-made trace -------------------------------------

HLO = """HloModule jit_step, entry_computation_layout={()->f32[]}

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %m = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/while/body/bwd/ffn/mul/transpose(jvp(fwd/ffn/mul))/mul"}
}

%body (c: f32[8]) -> f32[8] {
  %c = f32[8]{0} parameter(0)
  %dot.1 = f32[8]{0} dot(%c, %c), metadata={op_name="jit(step)/while/body/fwd/attention/scaled_dot_product_attention/dot_general"}
  %fusion.7 = f32[8]{0} fusion(%dot.1), kind=kLoop, calls=%fused_computation.1
  %copy.3 = f32[8]{0} copy(%fusion.7)
  %all-reduce-start.2 = f32[8]{0} all-reduce-start(%copy.3), metadata={op_name="jit(step)/while/body/sync/grad/exact/psum"}
  %all-reduce-done.2 = f32[8]{0} all-reduce-done(%all-reduce-start.2), metadata={op_name="jit(step)/while/body/sync/grad/exact/psum"}
  %copy.4 = f32[8]{0} copy(%c)
  ROOT %tuple.5 = (f32[8]{0}, f32[8]{0}) tuple(%all-reduce-done.2, %copy.4)
}

ENTRY %main () -> f32[] {
  %while.9 = f32[8]{0} while(), body=%body
  ROOT %adam.1 = f32[] fusion(%while.9), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/opt/optimizer/adam/sub"}
}
"""


def _op(name, start_us, dur_us, line=profiler.OP_LINE):
    text = "%%%s = f32[8]{0} op()" % name
    return {"name": text, "plane": "/device:TPU:0", "line": line,
            "host": False, "ts_ns": start_us * 1e3,
            "dur_ns": dur_us * 1e3, "stats": {}}


def _span(name, start_us, dur_us):
    return {"name": name, "plane": "/host:CPU", "line": "python",
            "host": True, "ts_ns": start_us * 1e3,
            "dur_ns": dur_us * 1e3, "stats": {"span": "paddle_tpu"}}


def _synthetic_events():
    module = lambda s, d: dict(_op("x", s, d), name="jit_step(123)",  # noqa: E731
                               line=profiler.MODULE_LINE)
    return [
        module(0, 100),
        _op("while.9", 0, 90),          # nests its body: not a leaf
        _op("dot.1", 0, 30),
        _op("fusion.7", 30, 20),        # scope through what it fused
        _op("copy.3", 55, 5),           # XLA's own, feeds the sync;
        _op("all-reduce-done.2", 60, 25),   # gap 50-55
        _op("copy.4", 85, 5),           # XLA's own, feeds nothing named
        _op("all-reduce-start.2", 40, 50, line=profiler.ASYNC_LINE),
        _op("adam.1", 90, 10),
        module(400, 30),
        _op("adam.1", 400, 30),         # next dispatch; gap 100-400
        _span("executor_entry", 95, 320),
        _span("executor_settle", 100, 10),
        _span("executor_prepare", 150, 240),
        # the runtime's own host event: not one of the program's spans
        dict(_span("ReadSyncFlag", 90, 400), stats={}),
    ]


def test_scope_table_charges_leaves_gaps_and_spans():
    t = profiler.scope_table(_synthetic_events(), HLO)
    assert t["devices"] == 1
    leaf_busy_ms = (30 + 20 + 5 + 30 + 10 + 30) / 1e3
    assert t["busy_ms"] == pytest.approx(leaf_busy_ms)
    for key in ("by_phase", "by_layer", "by_layer_op",
                "by_phase_layer"):
        assert sum(t[key].values()) == pytest.approx(leaf_busy_ms), key
    assert t["by_phase"] == pytest.approx(
        {"fwd": 0.030, "bwd": 0.020, "sync": 0.030, "opt": 0.040,
         "unscoped": 0.005})
    assert t["by_layer"]["attention"] == pytest.approx(0.030)
    assert t["by_layer"]["ffn"] == pytest.approx(0.020)
    assert t["by_layer_op"]["optimizer adam"] == pytest.approx(0.040)
    # what XLA made itself: charged to the op that consumes it and
    # marked, or, where nothing named does, left unscoped by opcode
    assert t["by_layer_op"]["grad (xla) copy"] == pytest.approx(0.005)
    assert t["by_layer_op"]["unscoped copy"] == pytest.approx(0.005)
    assert t["unscoped_ms"] == pytest.approx(0.005)
    assert t["collective_ms"] == pytest.approx(0.025)
    assert t["collectives_by_layer"] == pytest.approx({"grad": 0.025})
    assert t["async_collectives_by_layer"] == pytest.approx(
        {"grad": 0.050})
    # the gap inside the program, and the one between two dispatches,
    # charged to the span that covers most of it (prepare's 240 of
    # 300 us; entry covers more but holds prepare: innermost wins
    # only on a tie, so entry's 300 takes it)
    assert t["idle_by_cause"] == pytest.approx(
        {"inside a program": 0.005,
         "between dispatches: executor_entry": 0.300})
    assert t["idle_ms"] == pytest.approx(0.305)
    assert t["longest_gaps"][0] == [
        "between dispatches: executor_entry", pytest.approx(0.300)]
    assert t["note"] is None
    text = profiler.format_scope_table(t, steps=2)
    assert "ms/step" in text and "attention" in text


def test_scope_table_prefers_the_innermost_covering_span():
    events = [e for e in _synthetic_events()
              if e["name"] != "executor_prepare"]
    events.append(_span("executor_prepare", 98, 310))
    t = profiler.scope_table(events, HLO)
    assert "between dispatches: executor_prepare" in t["idle_by_cause"]


def test_a_stale_store_reads_unscoped_and_says_so():
    """An executable compiled before the program named its ops keeps
    the old op_names (the store's key ignores metadata): the table
    must say so, not charge by guess."""
    old = re.sub(r"(fwd|bwd|opt|sync)/[a-z_]+/[a-z_]+/", "", HLO)
    assert "attention" not in old
    t = profiler.scope_table(_synthetic_events(), old)
    assert t["unscoped_ms"] == pytest.approx(t["busy_ms"])
    assert "built before its program named its ops" in t["note"]
    lines = profiler.format_scope_table(t).splitlines()
    assert lines[1].startswith("!! 100% of the device time carries no "
                               "scope")
    none = profiler.scope_table(_synthetic_events(), None)
    assert "no optimized HLO" in none["note"]


def test_one_reader_finds_the_programs_spans_in_any_trace(tmp_path):
    """A jax.profiler trace this module did not start still holds the
    Executor's spans, on the trace's own clock."""
    import jax
    main, startup, loss = _scoped_fc_program()
    exe = fluid.Executor()
    xv = np.ones((4, 8), np.float32)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed={"x": xv}, fetch_list=[loss])
        jax.profiler.start_trace(str(tmp_path))
        try:
            for _ in range(2):
                exe.run(main, feed={"x": xv}, fetch_list=[loss])
        finally:
            jax.profiler.stop_trace()
    events = profiler._collect_device_events(str(tmp_path))
    spans = [e for e in events if e["host"] and "span" in e["stats"]]
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)
    for name in ("executor_entry", "executor_prepare", "feed_h2d",
                 "executor_run", "executor_settle"):
        assert len(by_name.get(name, ())) == 2, (name, sorted(by_name))
    entry, run = by_name["executor_entry"][0], \
        by_name["executor_run"][0]
    assert entry["ts_ns"] <= run["ts_ns"] and \
        run["ts_ns"] + run["dur_ns"] <= entry["ts_ns"] + entry["dur_ns"]
    assert not any(e["name"].startswith("$") for e in events)
    # and the device side of the same capture joins to the scopes
    hlo = [r["optimized_hlo"] for r in exe.aot_artifacts()]
    t = profiler.scope_table(events, hlo)
    if t["busy_ms"]:        # some CPU runtimes trace no op at all
        assert set(t["by_layer"]) & {"ffn", "loss", "optimizer"}
