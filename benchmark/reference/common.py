"""Plain pieces the references share: precision modes, layer norm,
attention, dropout, Adam and the blocked training loop.

Everything here is straightforward ``jax.numpy`` in float32. It imports
nothing of the program under test and is handed nothing the program
made: weights come from ``init_params`` (the benchmark's own generator),
the batch from ``benchmark/traffic``.

Precision modes (``mode``):
  f32   every contraction at ``Precision.HIGHEST`` -- the reference.
  int8  operands and cotangents of every contraction rounded to 127
        levels of one scale per tensor -- the control: v5e's faster
        matrix unit is int8, so that is the step below the bf16 the
        configurations state that would tempt a later PR.

Dropout masks are the reference's own, drawn from the seed. The
program's masks cannot be reproduced outside it (its flash kernels
draw them from the TPU's hardware generator), so every number the
comparison reads is a norm or a mean that is steady over masks; the
limits in ``benchmark/limits`` were read over a dozen seeds, which
change masks, weights and data together.
"""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def quantize(x, mode):
    if mode == "f32":
        return x
    if mode != "int8":
        raise ValueError("unknown precision mode %r" % mode)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127.0, 127.0) * scale


def _straight_through(x, mode):
    return x + lax.stop_gradient(quantize(x, mode) - x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round_cotangent(y, mode):
    return y


def _rc_fwd(y, mode):
    return y, None


def _rc_bwd(mode, _res, g):
    return (quantize(g, mode),)


_round_cotangent.defvjp(_rc_fwd, _rc_bwd)


def contract(spec, a, b, mode):
    """``einsum(spec, a, b)`` with both operands, and in the backward
    pass the cotangent, rounded as ``mode`` says; products and sums in
    float32."""
    if mode == "f32":
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    out = jnp.einsum(spec, _straight_through(a, mode),
                     _straight_through(b, mode), precision=HIGHEST)
    return _round_cotangent(out, mode)


def linear(x, w, b, mode):
    y = contract("...k,kn->...n", x, w, mode)
    return y if b is None else y + b


def layer_norm(x, scale, bias, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * scale + bias


def dropout(x, rate, key):
    if not rate:
        return x
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0)


def attention(q, k, v, bias, n_head, rate, key, mode):
    """q [b, sq, d], k/v [b, sk, d], additive bias broadcastable to
    [b, h, sq, sk]; softmax in float32, dropout on the weights."""
    b, sq, d = q.shape
    dh = d // n_head

    def split(t):
        return t.reshape(b, t.shape[1], n_head, dh).transpose(0, 2, 1, 3)

    s = contract("bhqd,bhkd->bhqk", split(q), split(k), mode) \
        * dh ** -0.5 + bias
    w = dropout(jax.nn.softmax(s, axis=-1), rate, key)
    ctx = contract("bhqk,bhkd->bhqd", w, split(v), mode)
    return ctx.transpose(0, 2, 1, 3).reshape(b, sq, d)


# -- weights from the seed --------------------------------------------------

def seed_key(seed, stream):
    """A key from any whole-number seed (the driver's pass 2**31) and a
    small stream number."""
    seed = int(seed) % (1 << 32)
    key = jax.random.key(seed >> 16)
    return jax.random.fold_in(jax.random.fold_in(key, seed & 0xFFFF),
                              stream)


def init_params(spec, seed):
    """All weights in ONE jitted call on the device. ``spec`` is an
    ordered list of (name, shape, kind): ``xavier`` (uniform,
    +-sqrt(6 / (fan_in + fan_out)) -- what the framework's layers
    default to), ``tnormal<std>`` (normal of that deviation cut at two
    deviations, as ``tf.truncated_normal_initializer``), ``ones``,
    ``zeros``. Float32: the type the program stores (bf16 AMP casts at
    the matmuls)."""
    spec = tuple((n, tuple(s), k) for n, s, k in spec)
    return _init_jit(spec)(seed_key(seed, 0))


@functools.lru_cache(maxsize=None)
def _init_jit(spec):
    def make(key):
        out = {}
        for i, (name, shape, kind) in enumerate(spec):
            if kind == "xavier":
                lim = (6.0 / (shape[0] + shape[-1])) ** 0.5
                out[name] = jax.random.uniform(
                    jax.random.fold_in(key, i), shape, jnp.float32,
                    -lim, lim)
            elif kind.startswith("tnormal"):
                out[name] = float(kind[len("tnormal"):]) \
                    * jax.random.truncated_normal(
                        jax.random.fold_in(key, i), -2.0, 2.0, shape,
                        jnp.float32)
            elif kind == "ones":
                out[name] = jnp.ones(shape, jnp.float32)
            elif kind == "zeros":
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                raise ValueError("unknown init kind %r" % kind)
        return out
    return jax.jit(make)


# -- Adam and the blocked step ----------------------------------------------

def adam_update(params, grads, m1, m2, step, lr, beta1, beta2, eps):
    """One Adam step as the published algorithm (and the adam op) has
    it; ``step`` counts from 1."""
    lr_t = lr * (1.0 - beta2 ** step) ** 0.5 / (1.0 - beta1 ** step)
    new_p, new_m1, new_m2 = {}, {}, {}
    for n, g in grads.items():
        new_m1[n] = beta1 * m1[n] + (1.0 - beta1) * g
        new_m2[n] = beta2 * m2[n] + (1.0 - beta2) * jnp.square(g)
        new_p[n] = params[n] - lr_t * new_m1[n] / (
            jnp.sqrt(new_m2[n]) + eps)
    return new_p, new_m1, new_m2


@functools.lru_cache(maxsize=None)
def _compiled(model, cfg_json, opt_json, mode):
    """The jitted pieces of ``train``, built once for a model, its
    sizes, the optimizer's numbers and a precision mode."""
    cfg, opt = json.loads(cfg_json), json.loads(opt_json)

    @jax.jit
    def block_grad(p, rows, nrm, key):
        return jax.value_and_grad(model.block_loss)(
            p, rows, nrm, key, cfg, mode)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def accumulate(acc, g):
        return jax.tree.map(jnp.add, acc, g)

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def apply(p, g, a, b, step):
        return adam_update(p, g, a, b, step, opt["learning_rate"],
                           opt["beta1"], opt["beta2"], opt["epsilon"])

    norms = jax.jit(leaf_norms)
    change = jax.jit(leaf_change)
    return block_grad, accumulate, apply, norms, change


def _slice_rows(batch, lo, hi):
    return {k: v[lo:hi] for k, v in batch.items()}


def leaf_norms(tree):
    return {n: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for n, v in tree.items()}


def leaf_change(params, params0):
    """Per leaf, the norm of the change since ``params0`` and how many
    of its elements changed at all."""
    delta = {n: params[n] - params0[n] for n in params}
    return {"delta": leaf_norms(delta),
            "moved": {n: jnp.count_nonzero(v) for n, v in delta.items()}}


def train(model, cfg, opt, batch, seed, steps, mode="f32",
          rows_per_block=8, snapshot_steps=(), mask_stream=1):
    """Follow ``steps`` steps of training from the seed and return what
    the comparison reads.

    ``model`` is a reference module: ``param_spec(cfg)``,
    ``normalizers(batch)`` and ``block_loss(params, rows, norm, key,
    cfg, mode)`` whose sum over row blocks is the batch loss. The
    gradient is accumulated block by block so that the float32 logits
    of a block, not of the batch, live on the device.

    Returns ``{"loss": [per step], "grad1": {leaf: norm of the first
    gradient}, "snap": {step: {"m1": {leaf: norm}, "delta": {leaf:
    norm of the change since step 0}, "moved": {leaf: elements that
    changed}}}}``, as floats.
    ``mask_stream`` picks the stream of the seed that the dropout masks
    are drawn from: another stream, other masks, all else alike.
    """
    spec = model.param_spec(cfg)
    params0 = init_params(spec, seed)
    params = init_params(spec, seed)     # its own buffers: donated below
    m1 = {n: jnp.zeros_like(v) for n, v in params.items()}
    m2 = {n: jnp.zeros_like(v) for n, v in params.items()}
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    norm = model.normalizers(batch)
    n_rows = next(iter(batch.values())).shape[0]
    rows_per_block = max(r for r in range(1, rows_per_block + 1)
                         if n_rows % r == 0)

    block_grad, accumulate, apply, norms, change = _compiled(
        model, json.dumps(cfg, sort_keys=True),
        json.dumps(opt, sort_keys=True), mode)

    out = {"loss": [], "grad1": None, "snap": {}}
    for step in range(1, steps + 1):
        loss, grads = 0.0, None
        for bi, lo in enumerate(range(0, n_rows, rows_per_block)):
            key = jax.random.fold_in(
                jax.random.fold_in(seed_key(seed, mask_stream), step), bi)
            lb, gb = block_grad(params,
                                _slice_rows(batch, lo,
                                            lo + rows_per_block),
                                norm, key)
            loss = loss + lb
            grads = gb if grads is None else accumulate(grads, gb)
        out["loss"].append(float(loss))
        if step == 1:
            out["grad1"] = {n: float(v)
                            for n, v in norms(grads).items()}
        params, m1, m2 = apply(params, grads, m1, m2,
                               jnp.float32(step))
        if step in snapshot_steps:
            out["snap"][step] = {
                "m1": {n: float(v) for n, v in norms(m1).items()},
                **{k: {n: float(v) for n, v in tree.items()}
                   for k, tree in change(params, params0).items()}}
    return out
