"""Kimi Delta Attention (KDA): a gated delta-rule linear attention with
a per-channel decay, and the two small ops its mixer needs beside it
(a causal depthwise short convolution, an RMSNorm with a sigmoid gate).

Not in the 2019 reference: the first op here that carries a state
across positions and is not a ``lax.scan`` over them.

**The layer, per head** (q, k in R^dk, v in R^dv, a log decay g <= 0 a
channel of k, beta in (0, 1) a head; state S in R^{dk x dv}, S_0 = 0):

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``kda_recurrence`` runs exactly that, token by token, and is the
definition. ``kda_chunked`` is what the op lowers to: chunks of
``_CHUNK`` tokens; with G the in-chunk cumulative sum of g,

    A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)       (j < i)
    T = (I + A)^-1,  W = T (beta K exp(G)),  U = T (beta V)

for every chunk at once (batched matrix products, no state), then
chunk by chunk with the carried S, P the lower triangle (diagonal
included) of Q K^T with the same pairwise decays,

    V' = U - W S
    O  = (Q exp(G)) S + P V'
    S <- Diag(exp(G_C)) S + (K exp(G_C - G))^T V'

The scan over chunks carries S alone (two products a step) and leaves
every chunk's start state; the outputs then come for all chunks at
once as ``(Q exp(G) - P W) S + P U``.

**No decay is ever clamped.** ``exp(-G_j)`` leaves float32 inside one
chunk when the gate is strong, so the pairwise decays are never
factorised about the chunk's start: ``_pair_decay`` splits a chunk's
rows into sub-blocks of ``_SUB``; a row block against ALL the earlier
keys is one product factorised about the row block's first row (both
factors <= 1), a block against itself is computed pair by pair
(``exp(G_i - G_j)``, j <= i, <= 1). Everything else decays forward (``exp(G)``, ``exp(G_C - G)``,
<= 1): an underflow there is the true value's own.

**Precision.** The cumulative sums, A, T and the carried state are
float32 whatever the inputs' type; the matrix products take their
operands in q's type (bf16 under AMP) and accumulate in float32; T is
made and applied at full precision.

**Memory.** Every custom VJP here keeps its op's inputs alone and the
backward pass makes the intermediates again: the core's chunk by chunk
(blocks of ``_BLOCK_CHUNKS`` for the stateless part, the chunk-start
states under the scan), the two small ops' float32 insides in one pass.

**Two lowerings of the chunked form.** On a TPU backend, where a head
of q, k and v is whole groups of 128 lanes, ``kda_chunked`` takes the
Mosaic kernels of ``ops/pallas/kda.py``, forward and backward: the
chunk's G, pairwise decays, T, W, U and the carried state stay in
VMEM, the arrays are read in place from ``[B, S, H*D]``. Everywhere
else (the CPU, a head of 64 lanes) it takes the XLA form below. They
share this module's constants (``_CHUNK``, ``_SUB``, ``_STATE_DTYPE``,
``_FLOOR``, read when a site is traced) and nothing else;
``kda_lowering.pallas_chunked`` / ``.xla_chunked`` count which a site
took.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .pallas import kda as kda_pallas
from .pallas.common import count_lowering, interpret_mode
from .registry import register

_CHUNK = 64           # tokens to a chunk
_SUB = 16             # rows to a sub-block of the pairwise decays
_BLOCK_CHUNKS = 4     # chunks to a block of the stateless part
_FLOOR = -80.0        # below it exp() leaves float32's normal range
# the type of the state carried from chunk to chunk
_STATE_DTYPE = jnp.float32
_HIGHEST = lax.Precision.HIGHEST

# The step's own counts, one persistable the ops add to (as
# parallel/moe.py COUNTERS_VAR): tokens and chunks the KDA layers
# walked, and elements of the in-chunk cumulative log decay below
# ``_FLOOR``, where a factorised form must have re-based.
COUNTERS_VAR = "__kda_counters__"
COUNTER_NAMES = ("tokens_total", "chunks_total", "decay_floor_hits_total")


def read_counters(scope):
    """{name: total} from ``scope``, or None where no program with a
    KDA layer has run in it."""
    import numpy as np
    if not scope.has_var(COUNTERS_VAR):
        return None
    v = scope.find_var(COUNTERS_VAR)
    if v is None:
        return None
    return dict(zip(COUNTER_NAMES, np.asarray(v, np.float64).tolist()))


def _l2norm(x):
    xf = x.astype(jnp.float32)
    return xf * lax.rsqrt(jnp.sum(jnp.square(xf), -1, keepdims=True)
                          + 1e-6)


def _heads(x, n_heads):
    """[B, S, H*D] -> [B, H, S, D]."""
    b, s, w = x.shape
    return x.reshape(b, s, n_heads, w // n_heads).transpose(0, 2, 1, 3)


def kda_recurrence(q, k, v, g, beta, *, scale=1.0):
    """The definition, token by token, in float32: q, k, g [B,S,H*dk],
    v [B,S,H*dv], beta [B,S,H] -> [B,S,H*dv] float32; q and k
    L2-normalised per head, q times ``scale``."""
    n_heads = beta.shape[-1]
    qh, kh, vh, gh = (_heads(x, n_heads).astype(jnp.float32)
                      for x in (q, k, v, g))
    qh, kh = _l2norm(qh) * scale, _l2norm(kh)
    bh = beta.astype(jnp.float32).transpose(0, 2, 1)
    b, h, s, dk = qh.shape

    def step(state, x):
        qt, kt, vt, gt, bt = x           # [B,H,dk] .. [B,H]
        state = state * jnp.exp(gt)[..., None]
        kv = jnp.einsum("bhk,bhkv->bhv", kt, state, precision=_HIGHEST)
        state = state + (bt[..., None] * kt)[..., None] \
            * (vt - kv)[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", qt, state,
                                 precision=_HIGHEST)

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (qh, kh, vh, gh, bh))
    _, o = lax.scan(step, jnp.zeros((b, h, dk, vh.shape[-1]),
                                    jnp.float32), xs)
    return jnp.moveaxis(o, 0, 2).transpose(0, 2, 1, 3).reshape(
        b, s, -1)


def _pair_decay(x, k, G, cd):
    """x, k and G [..., C, D], G the in-chunk cumulative log decay in
    float32 -> [..., C, C] float32: ``sum_c x_ic k_jc exp(G_ic -
    G_jc)`` for j <= i and nought above the diagonal. No exponent is
    positive (the module's docstring): blocks of ``_SUB`` rows, a block
    against ALL the earlier keys in one product factorised about its
    first row, a block against itself pair by pair."""
    C = k.shape[-2]
    b = min(_SUB, C)
    idx = jnp.arange(b)
    tri = (idx[None, :] <= idx[:, None])[:, :, None]
    rows = []
    for lo in range(0, C, b):
        Gi, xi, ki = (t[..., lo:lo + b, :] for t in (G, x, k))
        first = Gi[..., :1, :]
        parts = []
        if lo:
            xd = (xi * jnp.exp(Gi - first)).astype(cd)
            kd = (k[..., :lo, :]
                  * jnp.exp(first - G[..., :lo, :])).astype(cd)
            parts.append(_mm(xd, jnp.swapaxes(kd, -1, -2)))
        pair = jnp.exp(jnp.where(
            tri, Gi[..., :, None, :] - Gi[..., None, :, :], -jnp.inf))
        parts.append(jnp.sum(xi[..., :, None, :]
                             * (ki[..., None, :, :] * pair), axis=-1))
        if lo + b < C:
            parts.append(jnp.zeros(k.shape[:-2] + (b, C - lo - b),
                                   jnp.float32))
        rows.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(rows, axis=-2)


def _chunk_cumsum(g):
    """Cumulative sum over the second-last axis (a chunk's tokens) as a
    product with the lower triangle of ones, at full precision: XLA's
    own cumulative sum walks the 64 rows in log steps of whole
    passes."""
    C = g.shape[-2]
    return jnp.einsum("ij,...jd->...id",
                      jnp.tril(jnp.ones((C, C), jnp.float32)), g,
                      precision=_HIGHEST)


@jax.custom_vjp
def _inv_unit_lower(A):
    """(I + A)^-1 for strictly lower triangular A [..., C, C]: with
    A^C = 0, ``(I - A)(I + A^2)(I + A^4)...`` is the whole series. Its
    gradient is ``-T^T dT T^T``: two products, not the series again."""
    C = A.shape[-1]
    eye = jnp.eye(C, dtype=A.dtype)
    T, P, power = eye - A, A, 2
    while power < C:
        P = _mm_exact(P, P)
        T = T + _mm_exact(T, P)
        power *= 2
    return T


def _mm_exact(a, b):
    return jnp.matmul(a, b, precision=_HIGHEST)


def _inv_fwd(A):
    T = _inv_unit_lower(A)
    return T, T


def _inv_bwd(T, dT):
    Tt = jnp.swapaxes(T, -1, -2)
    return (-_mm_exact(_mm_exact(Tt, dT), Tt),)


_inv_unit_lower.defvjp(_inv_fwd, _inv_bwd)


def _mm(a, b):
    """Operands as they come (q's type under AMP), sums in float32."""
    return jnp.matmul(a, b, preferred_element_type=jnp.float32)


def _intra(q, k, v, g, beta, scale):
    """The stateless part for a block of chunks: q, k, g [..., C, dk],
    v [..., C, dv], beta [..., C] as the op got them -> per chunk
    (W, Kd, Qe in q's type; U, PU, eGC float32) with
    ``V' = U - W S``, ``O = Qe S + PU``, ``S <- eGC S + Kd^T V'``, and
    per token how many lanes of G lie below ``_FLOOR`` [..., C]."""
    cd = q.dtype
    q, k = _l2norm(q) * scale, _l2norm(k)
    v, g = v.astype(jnp.float32), g.astype(jnp.float32)
    beta = beta.astype(jnp.float32)[..., None]
    G = _chunk_cumsum(g)
    P = _pair_decay(q, k, G, cd)
    C = k.shape[-2]
    strict = jnp.tril(jnp.ones((C, C), jnp.float32), -1)
    T = _inv_unit_lower(beta * _pair_decay(k, k, G, cd) * strict)
    eG = jnp.exp(G)
    W = _mm_exact(T, beta * k * eG)
    U = _mm_exact(T, beta * v)
    GC = G[..., -1:, :]
    Pc = P.astype(cd)
    return (W.astype(cd), (k * jnp.exp(GC - G)).astype(cd),
            (q * eG - _mm(Pc, W.astype(cd))).astype(cd), U,
            _mm(Pc, U.astype(cd)), jnp.exp(GC[..., 0, :]),
            jnp.sum(G < _FLOOR, -1, dtype=jnp.float32))


# Jitted for what the flash wrappers are jitted for (ops/pallas/
# attention.py): the sites of one signature share ONE trace, forward
# and backward, so a model's four KDA layers cost the step's build one
# walk through the Python below, not eight.
@functools.partial(jax.jit, static_argnums=5)
def _kda_forward(q, k, v, g, beta, scale):
    """(the output, the elements of G below ``_FLOOR`` over the real
    positions)."""
    cd = q.dtype
    n_heads = beta.shape[-1]
    B, S, _ = q.shape
    C, nb = _CHUNK, _BLOCK_CHUNKS
    blocks = -(-S // (C * nb))
    pad = blocks * C * nb - S

    def blocked(x):       # [B, S, H*D] -> [blocks, B, H, nb, C, D]
        x = _heads(x, n_heads)
        if pad:   # a padded token (k, v, beta, g nought) moves no state
            x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
        x = x.reshape((B, n_heads, blocks, nb, C, x.shape[-1]))
        return jnp.moveaxis(x, 2, 0)

    intra = jax.checkpoint(functools.partial(_intra, scale=scale))
    W, Kd, Qe, U, PU, eGC, low = lax.map(
        lambda a: intra(*a),
        tuple(blocked(x) for x in (q, k, v, g))
        + (blocked(beta)[..., 0],))

    def chunks(x):        # [blocks, B, H, nb, ..] -> [chunks, B, H, ..]
        x = jnp.moveaxis(x, 3, 1)
        return x.reshape((blocks * nb,) + x.shape[2:])

    @jax.checkpoint
    def step(state, x):
        W, Kd, U, eGC = x
        low = state.astype(cd)
        vn = U - _mm(W, low)                             # [B,H,C,dv]
        new = eGC[..., None] * state.astype(jnp.float32) \
            + _mm(jnp.swapaxes(Kd, -1, -2), vn.astype(cd))
        return new.astype(_STATE_DTYPE), low

    dk, dv = Kd.shape[-1], U.shape[-1]
    _, starts = lax.scan(step, jnp.zeros((B, n_heads, dk, dv),
                                         _STATE_DTYPE),
                         tuple(chunks(x) for x in (W, Kd, U, eGC)))
    # every chunk's output at once, from the states the scan left
    o = _mm(chunks(Qe), starts) + chunks(PU)             # [N,B,H,C,dv]
    o = jnp.moveaxis(o, 0, 2).reshape(B, n_heads, -1, dv)[:, :, :S]
    # a padded token's G is the last real one's: it counts for nothing
    low = jnp.moveaxis(low, 0, 2).reshape(B, n_heads, -1)[:, :, :S]
    return (o.transpose(0, 2, 1, 3).reshape(B, S, -1).astype(v.dtype),
            jnp.sum(low))


def lowering(q, v, beta):
    """``pallas_chunked`` or ``xla_chunked``: by what the site's shapes
    and the backend show, as ``grouped_matmul.py`` chooses."""
    h = beta.shape[-1]
    if not interpret_mode() and kda_pallas.takes(
            q.shape[-1] // h, v.shape[-1] // h, _CHUNK, _SUB):
        return "pallas_chunked"
    return "xla_chunked"


def _chunked(q, k, v, g, beta, scale):
    if lowering(q, v, beta) == "pallas_chunked":
        return kda_pallas.kda_fwd(q, k, v, g, beta, scale, _CHUNK, _SUB,
                                  _STATE_DTYPE, _FLOOR)
    return _kda_forward(q, k, v, g, beta, scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def kda_chunked(q, k, v, g, beta, scale=1.0):
    """The chunked form of ``kda_recurrence`` (same arguments): (the
    output in v's type, the count of ``_kda_forward``)."""
    return _chunked(q, k, v, g, beta, scale)


def _kda_fwd(q, k, v, g, beta, scale):
    # the inputs are all the backward pass keeps
    return _chunked(q, k, v, g, beta, scale), (q, k, v, g, beta)


@functools.partial(jax.jit, static_argnums=6)
def _kda_backward(q, k, v, g, beta, d_out, scale):
    _, pull = jax.vjp(lambda *a: _kda_forward(*a, scale)[0],
                      q, k, v, g, beta)
    return pull(d_out)


def _kda_bwd(scale, res, ct):
    q, _, v, _, beta = res
    if lowering(q, v, beta) == "pallas_chunked":
        return kda_pallas.kda_bwd(*res, ct[0], scale, _CHUNK, _SUB,
                                  _STATE_DTYPE)
    return _kda_backward(*res, ct[0], scale)


kda_chunked.defvjp(_kda_fwd, _kda_bwd)


@register("kda_attention", ["Q", "K", "V", "G", "Beta", "Counters"],
          ["Out", "CountersOut"], nondiff=("Counters",))
def kda_attention(q, k, v, g, beta, counters, *, scale=1.0):
    """The KDA core over q, k [B,S,H*dk], v [B,S,H*dv], the log decay g
    [B,S,H*dk] (float32, <= 0) and beta [B,S,H]: q and k L2-normalised
    per head, q times ``scale``, then the gated delta rule (the
    module's docstring) in its chunked form. ``Out`` [B,S,H*dv] has V's
    type; ``CountersOut`` is the input's own variable."""
    took = lowering(q, v, beta)
    for path in ("pallas_chunked", "xla_chunked"):
        count_lowering("kda_lowering." + path, float(path == took))
    out, low = kda_chunked(q, k, v, g, beta, float(scale))
    b, s, _ = q.shape
    add = jnp.stack([jnp.float32(b * s),
                     jnp.float32(b * (-(-s // _CHUNK))), low])
    return out, counters + add


@register("kda_gate", ["X", "ALog", "DtBias"], ["Out"])
def kda_gate(x, a_log, dt_bias):
    """The per-channel log decay ``-exp(A_log_h) * softplus(x +
    dt_bias)`` in float32 whatever x's type: x [B,S,H*dk], ``A_log``
    [H] one scalar a head, ``dt_bias`` [H*dk]."""
    a_log = a_log.astype(jnp.float32)
    a = jnp.repeat(jnp.exp(a_log), x.shape[-1] // a_log.shape[0])
    return -a * jax.nn.softplus(x.astype(jnp.float32)
                                + dt_bias.astype(jnp.float32))


# ---- the two small ops beside the core. Below it in the file: the
# core's Mosaic bodies carry this file's line numbers in their text
from .pallas import kda_small  # noqa: E402


def _small_lowering(takes):
    """``pallas`` (ops/pallas/kda_small.py) on a TPU backend where the
    kernels take the shapes, else ``xla``: read off the site, as
    ``lowering`` chooses for the core."""
    return "pallas" if not interpret_mode() and takes else "xla"


def _count_small(name, took):
    for path in ("pallas", "xla"):
        count_lowering("%s_lowering.%s" % (name, path), float(path == took))


def short_conv_definition(x, w):
    """What ``short_conv`` computes, as plain ``jax.numpy``: x [B,S,C],
    w [C,K] (no bias), ``y_t = silu(sum_i w[:, i] x_{t-(K-1)+i})`` with
    nought before the row's start. Sums in float32, the output in x's
    type."""
    K = w.shape[1]
    s = x.shape[1]
    xf = jnp.pad(x.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    wf = w.astype(jnp.float32)
    y = sum(xf[:, i:i + s, :] * wf[:, i] for i in range(K))
    return jax.nn.silu(y).astype(x.dtype)


def _conv_lowering(x, w):
    return _small_lowering(kda_small.conv_takes(x, w))


@jax.custom_vjp
def _short_conv(x, w):
    if _conv_lowering(x, w) == "pallas":
        return kda_small.conv_fwd(x, w)
    return short_conv_definition(x, w)


def _short_conv_fwd(x, w):
    return _short_conv(x, w), (x, w)


def _short_conv_bwd(res, dy):
    if _conv_lowering(*res) == "pallas":
        return kda_small.conv_bwd(*res, dy)
    return jax.vjp(short_conv_definition, *res)[1](dy)


_short_conv.defvjp(_short_conv_fwd, _short_conv_bwd)


@register("short_conv", ["X", "W"], ["Out"])
def short_conv(x, w):
    """Causal depthwise convolution over positions, then SiLU
    (``short_conv_definition``; the same values). Its own VJP keeps
    (x, w) as they came: the backward pass reads x, w and dy and makes
    the float32 pre-activation again, then ``g = dy silu'(pre)`` in
    float32, ``dx`` as the taps of g shifted the other way, ``dw[:, i]``
    as the sum over positions of ``x_{t-(K-1)+i} g_t``. On a TPU the
    Mosaic kernels of ops/pallas/kda_small.py, one reading each way;
    elsewhere the definition and its autodiff, made again from the
    inputs. ``short_conv_lowering.pallas`` / ``.xla`` count which a
    site took."""
    _count_small("short_conv", _conv_lowering(x, w))
    return _short_conv(x, w)


def gated_rms_norm_definition(x, gate, scale, epsilon=1e-5):
    """What ``gated_rms_norm`` computes, as plain ``jax.numpy``: RMSNorm
    over each group of ``len(scale)`` lanes of the last axis (a head),
    times the weight, times ``sigmoid(gate)``: x and gate [..., H*D],
    scale [D]. Float32 inside, x's type out."""
    d = scale.shape[0]
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, d))
    inv = lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + epsilon)
    y = (xf * inv * scale.astype(jnp.float32)).reshape(x.shape)
    return (y * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(x.dtype)


def _norm_lowering(x, scale):
    return _small_lowering(kda_small.norm_takes(x, scale))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gated_rms_norm(x, gate, scale, epsilon):
    if _norm_lowering(x, scale) == "pallas":
        return kda_small.norm_fwd(x, gate, scale, epsilon)
    return gated_rms_norm_definition(x, gate, scale, epsilon)


def _gated_norm_fwd(x, gate, scale, epsilon):
    return _gated_rms_norm(x, gate, scale, epsilon), (x, gate, scale)


def _gated_norm_bwd(epsilon, res, dy):
    if _norm_lowering(res[0], res[2]) == "pallas":
        return kda_small.norm_bwd(*res, epsilon, dy)
    return jax.vjp(functools.partial(gated_rms_norm_definition,
                                     epsilon=epsilon), *res)[1](dy)


_gated_rms_norm.defvjp(_gated_norm_fwd, _gated_norm_bwd)


@register("gated_rms_norm", ["X", "Gate", "Scale"], ["Y"])
def gated_rms_norm(x, gate, scale, *, epsilon=1e-5):
    """RMSNorm a head times the weight times ``sigmoid(gate)``
    (``gated_rms_norm_definition``). Its own VJP keeps (x, gate, scale)
    as they came: the backward pass reads them and dy, makes ``inv``,
    the normalised rows and ``sigmoid(gate)`` again in float32 and
    gives ``dx``, ``dgate`` and ``dscale``. On a TPU the Mosaic kernels
    of ops/pallas/kda_small.py, one reading each way; elsewhere the
    definition and its autodiff, made again from the inputs.
    ``gated_rms_norm_lowering.pallas`` / ``.xla`` count which."""
    _count_small("gated_rms_norm", _norm_lowering(x, scale))
    return _gated_rms_norm(x, gate, scale, float(epsilon))
