"""Dropout under a data-parallel mesh draws each shard's bits alone.

An ``rbg`` key lowers to one ``rng-bit-generator``, which XLA's SPMD
partitioner cannot split: a GSPMD step would make the global batch's
bits on every device and slice them. Where ``mesh.per_shard_batch_axis``
says so, the dropout op runs its body under ``shard_map`` over ``dp``
and each shard draws its own rows' bits from the key folded with its
``dp`` index. Partitionable threefry (jax's default, these tests'
default) keeps the global path, whose masks are one device's."""

import contextlib
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec

import paddle_tpu as fluid
from paddle_tpu import compile_cache, layers, profiler
from paddle_tpu.models import bert
from paddle_tpu.models import transformer as T
from paddle_tpu.ops import nn_ops
from paddle_tpu.parallel import make_mesh
from paddle_tpu.parallel import mesh as mesh_lib

RATE = 0.1
B, S, D = 16, 32, 64          # the program's global batch
COLLECTIVES = ("all-gather", "all-to-all", "collective-permute")


@contextlib.contextmanager
def _prng(impl, partitionable=True):
    """The default key generator (and threefry's partitioning) for one
    test alone."""
    old = (jax.config.jax_default_prng_impl,
           jax.config.jax_threefry_partitionable)
    jax.config.update("jax_default_prng_impl", impl)
    jax.config.update("jax_threefry_partitionable", partitionable)
    try:
        yield
    finally:
        jax.config.update("jax_default_prng_impl", old[0])
        jax.config.update("jax_threefry_partitionable", old[1])


@pytest.fixture
def rbg():
    with _prng("rbg"):
        yield


def _dp4():
    return make_mesh({"dp": 4}, jax.devices()[:4])


def _dropout_counts(fn):
    """{path: bumps} of the ``dropout_lowering.*`` counters over fn()."""
    def read():
        return {k.split(".", 1)[1]: v
                for k, v in profiler.counter_values().items()
                if k.startswith("dropout_lowering.")}

    before = read()
    fn()
    after = read()
    return {k: after[k] - before.get(k, 0.0) for k in after}


def _global_draw(monkeypatch):
    """The draw as it was before the per-shard path: one global body."""
    monkeypatch.setattr(mesh_lib, "per_shard_batch_axis",
                        lambda batch, key=None: None)


# -- the program: fc -> dropout -> loss, Adam, over a dp=4 mesh ----------

def _dp_step_hlo():
    """(optimized HLO of the dp=4 step, its loss, the counter bumps)."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", shape=[S, D])
        h = layers.fc(x, D, num_flatten_dims=2)
        h = layers.dropout(h, RATE,
                           dropout_implementation="upscale_in_train")
        loss = layers.reduce_mean(layers.square(h))
        fluid.optimizer.Adam(1e-3).minimize(loss)
    prog = fluid.CompiledProgram(main).with_data_parallel(mesh=_dp4())
    exe = fluid.Executor()
    feed = {"x": np.random.RandomState(0).randn(B, S, D)
            .astype("float32")}
    out = {}
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        counts = _dropout_counts(lambda: out.update(loss=exe.run(
            prog, feed=feed, fetch_list=[loss])[0]))
    step, = [r["optimized_hlo"] for r in exe.aot_artifacts()
             if r["entry"] == "run" and "all-reduce" in r["optimized_hlo"]]
    return step, float(out["loss"]), counts


def _largest_u32(hlo):
    return max(int(np.prod([int(d) for d in m.split(",")]))
               for m in re.findall(r"\bu32\[([0-9,]+)\]", hlo))


def _collectives(hlo):
    return {c: len(re.findall(r"\b%s(?:-start)?\(" % c, hlo))
            for c in COLLECTIVES}


def test_dp_step_draws_no_global_bits(rbg, monkeypatch):
    """(a) no u32 array of the global batch's size in the compiled dp
    step, where the global draw builds one; (e) the shard_map adds no
    resharding; (d) the step's one site is counted per shard, forward
    and under the vjp."""
    per_shard, loss, counts = _dp_step_hlo()
    assert counts == {"per_shard": 2.0, "global": 0.0}
    assert _largest_u32(per_shard) <= B * S * D // 4
    _global_draw(monkeypatch)
    whole, loss_g, _ = _dp_step_hlo()
    assert _largest_u32(whole) >= B * S * D
    assert _collectives(per_shard) == _collectives(whole)
    assert np.isfinite(loss) and abs(loss - loss_g) < 0.05 * loss_g


# -- the op over a dp=4 mesh ---------------------------------------------

def _op_over_mesh(x, key, mesh):
    """Out and Mask of the dropout op traced in a GSPMD jit over
    ``mesh``, x sharded by rows."""
    def f(x_, key_):
        return nn_ops.dropout(x_, dropout_prob=RATE, rng=key_,
                              dropout_implementation="upscale_in_train")

    with mesh_lib.mesh_guard(mesh):
        return jax.jit(f, in_shardings=(
            NamedSharding(mesh, PartitionSpec("dp")), None))(x, key)


def test_shards_keep_their_share_and_differ(rbg):
    """(b) each shard keeps 1 - p of its elements, and no two shards
    share a mask."""
    mesh = _dp4()
    x = jnp.ones((4 * 64, 1024), jnp.float32)
    out, mask = _op_over_mesh(x, jax.random.key(7), mesh)
    mask = np.asarray(mask).reshape(4, 64, 1024)
    shares = mask.mean(axis=(1, 2))
    assert np.all(np.abs(shares / (1 - RATE) - 1) < 0.005), shares
    for i in range(4):
        for j in range(i):
            assert (mask[i] != mask[j]).mean() > 0.1
    # the mask given is the one applied
    np.testing.assert_allclose(np.asarray(out).reshape(4, 64, 1024),
                               mask / (1 - RATE), rtol=1e-6, atol=0)


def test_gradient_applies_the_forward_mask(rbg):
    """(c) dx = g * mask / (1 - p), element-wise, with the mask the
    forward drew: the custom VJP recomputes it inside the shard's body
    from the same folded key."""
    mesh = _dp4()
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(32, 48).astype("float32"))
    g = jnp.asarray(rs.randn(32, 48).astype("float32"))
    key = jax.random.key(11)

    def f(x_, key_):
        return nn_ops.dropout(x_, dropout_prob=RATE, rng=key_,
                              dropout_implementation="upscale_in_train")

    sharded = NamedSharding(mesh, PartitionSpec("dp"))

    def fwd_bwd(x_, key_):
        (out, mask), vjp = jax.vjp(lambda a: f(a, key_), x_)
        dx, = vjp((g, jnp.zeros_like(mask)))
        return out, mask, dx

    with mesh_lib.mesh_guard(mesh):
        out, mask, dx = jax.jit(fwd_bwd, in_shardings=(sharded, None))(
            x, key)
    mask, out, dx = (np.asarray(a) for a in (mask, out, dx))
    assert 0 < mask.mean() < 1
    # the zeros are the mask's, element for element; the kept values
    # agree to XLA's rounding of the division
    np.testing.assert_array_equal(out != 0, mask != 0)
    np.testing.assert_array_equal(dx != 0, mask != 0)
    np.testing.assert_allclose(out, np.asarray(x) * mask / (1 - RATE),
                               rtol=1e-6, atol=0)
    np.testing.assert_allclose(dx, np.asarray(g) * mask / (1 - RATE),
                               rtol=1e-6, atol=0)


@pytest.mark.parametrize("impl,partitionable,on_mesh,path", [
    ("rbg", True, True, "per_shard"),
    ("unsafe_rbg", True, True, "per_shard"),
    ("threefry2x32", False, True, "per_shard"),
    ("rbg", True, False, "global"),
    ("threefry2x32", True, True, "global"),
])
def test_counters_name_the_path(impl, partitionable, on_mesh, path):
    """(d) the key's generator and the mesh choose the path; the path
    not taken is listed with 0. Under partitionable threefry XLA
    already draws each shard alone, and the masks stay one device's."""
    mesh = _dp4()
    x = jnp.ones((16, 8), jnp.float32)
    with _prng(impl, partitionable):
        key = jax.random.key(5)
        if on_mesh:
            counts = _dropout_counts(lambda: _op_over_mesh(x, key, mesh))
        else:
            counts = _dropout_counts(lambda: jax.jit(
                lambda a: nn_ops.dropout(a, dropout_prob=RATE, rng=key))(
                    x))
    other = "global" if path == "per_shard" else "per_shard"
    assert counts == {path: 1.0, other: 0.0}


def test_partitionable_threefry_masks_are_one_devices():
    mesh = _dp4()
    x = jnp.ones((16, 8), jnp.float32)
    key = jax.random.key(5)
    _, on_mesh = _op_over_mesh(x, key, mesh)
    _, alone = jax.jit(lambda a: nn_ops.dropout(
        a, dropout_prob=RATE, rng=key))(x)
    np.testing.assert_array_equal(np.asarray(on_mesh), np.asarray(alone))


def test_no_dp_divisor_keeps_the_global_path(rbg):
    """A batch the dp axis does not divide, or a mesh with no dp axis,
    is drawn whole."""
    key = jax.random.key(5)

    def traced(mesh, rows):
        with mesh_lib.mesh_guard(mesh):
            jax.jit(lambda a: nn_ops.dropout(a, dropout_prob=RATE,
                                             rng=key))(
                jnp.ones((rows, 8), jnp.float32))

    for mesh, rows in ((_dp4(), 6),
                       (make_mesh({"tp": 4}, jax.devices()[:4]), 8)):
        counts = _dropout_counts(lambda: traced(mesh, rows))
        assert counts == {"per_shard": 0.0, "global": 1.0}
    with mesh_lib.mesh_guard(_dp4()):
        assert mesh_lib.per_shard_batch_axis(6) is None
        assert mesh_lib.per_shard_batch_axis(8) == "dp"
        assert mesh_lib.per_shard_batch_axis(8, key) == "dp"
    assert mesh_lib.per_shard_batch_axis(8) is None


def test_inside_a_manual_body_keeps_the_global_path(rbg):
    """(f) a dropout traced inside a shard_map body draws the body's
    local array from the key it is given, and builds no nested
    shard_map (which would fail to trace over the same axis)."""
    mesh = _dp4()
    key = jax.random.key(9)
    spec = PartitionSpec("dp")

    def body(x_):
        assert mesh_lib.in_manual_body()
        return nn_ops.dropout(x_, dropout_prob=RATE, rng=key)[1]

    masks = []
    with mesh_lib.mesh_guard(mesh):
        assert not mesh_lib.in_manual_body()
        f = jax.jit(shard_map(body, mesh=mesh, in_specs=spec,
                              out_specs=spec))
        counts = _dropout_counts(
            lambda: masks.append(f(jnp.ones((16, 8), jnp.float32))))
    assert counts == {"per_shard": 0.0, "global": 1.0}
    local = np.asarray(nn_ops._keep_mask(key, RATE, (4, 8)))
    np.testing.assert_array_equal(np.asarray(masks[0]),
                                  np.tile(local, (4, 1)))


def test_bert_dp_tp_both_ways(monkeypatch):
    """BERT's {"dp": 2, "tp": 4} program with its dropouts on: the
    per-shard draw (rbg) goes manual over dp alone, so the columns tp
    shards stay sharded and the step gathers nothing the global draw
    does not."""
    def step(impl):
        cfg = bert.BertConfig(
            vocab_size=200, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=64,
            max_position_embeddings=64, seq_len=16,
            max_predictions_per_seq=4, hidden_dropout_prob=0.1,
            attention_probs_dropout_prob=0.1)
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 3
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            total, _, _ = bert.bert_pretrain(cfg)
            fluid.optimizer.Adam(1e-3).minimize(total)
        bert.shard_tp(main)
        prog = fluid.CompiledProgram(main).with_data_parallel(
            axes={"dp": 2, "tp": 4})
        exe = fluid.Executor()
        out = {}
        with _prng(impl), fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            feed = bert.make_fake_pretrain_batch(cfg, batch=4, seed=0)
            counts = _dropout_counts(lambda: out.update(tv=exe.run(
                prog, feed=feed, fetch_list=[total])[0]))
        hlo, = [r["optimized_hlo"] for r in exe.aot_artifacts()
                if r["entry"] == "run"
                and "all-reduce" in r["optimized_hlo"]]
        assert np.isfinite(out["tv"])
        return counts, _collectives(hlo)

    counts_rbg, coll_rbg = step("rbg")
    counts_tf, coll_tf = step("threefry2x32")
    assert counts_rbg["per_shard"] > 0 and counts_rbg["global"] == 0
    assert counts_tf["global"] == counts_rbg["per_shard"]
    assert counts_tf["per_shard"] == 0
    _global_draw(monkeypatch)
    counts_whole, coll_whole = step("rbg")
    assert counts_whole["per_shard"] == 0
    assert coll_rbg == coll_whole


# -- one device: the program is the one it was ----------------------------

# sha256 of the lowered text (no locations) of the toy transformer's
# step below, as the dropout op lowered it before the per-shard path
# existed (jax 0.9.0, this suite's CPU settings)
ONE_DEVICE_STEP = {
    "rbg":
        "6214da6ac960a38f0e072492e2d2cb2adf9c8c9351880f30021bd77fb2391421",
    "threefry2x32":
        "c773d2866255330606d04d089f700462037c8dee590526a84f79218d511cdb8b",
}


@pytest.mark.parametrize("impl", sorted(ONE_DEVICE_STEP))
def test_one_device_step_is_unchanged(impl, monkeypatch):
    """On one device the dropout lowers to the text it always did: the
    transformer's and BERT's single-chip steps are untouched."""
    texts = []
    real = compile_cache.canonical_fingerprint

    def recording(text):
        texts.append(text)
        return real(text)

    monkeypatch.setattr(compile_cache, "canonical_fingerprint",
                        recording)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with _prng(impl), fluid.unique_name.guard(), \
            fluid.program_guard(main, startup):
        cfg = T.TransformerConfig(
            src_vocab=64, tgt_vocab=64, max_len=16, d_model=32,
            d_ffn=64, n_head=2, n_layer=2, dropout=0.1)
        loss = T.transformer(cfg)[0]
        fluid.optimizer.Adam(1e-3).minimize(loss)
        exe = fluid.Executor()
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            exe.run(main, feed=T.make_fake_batch(cfg, 4),
                    fetch_list=[loss])
    assert len(texts) == 2                    # startup, the step
    assert "rng_bit_generator" in texts[1] or impl != "rbg"
    got = hashlib.sha256(texts[1].encode()).hexdigest()
    assert got == ONE_DEVICE_STEP[impl], got
