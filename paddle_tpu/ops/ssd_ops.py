"""Mamba-2's state-space core: SSD, the state-space duality of Dao & Gu
2024 (arXiv:2405.21060), as one op with the chunked form as its
lowering.

Not in the 2019 reference: the second op here (beside ``kda_ops``)
that carries a state across positions.

**The recurrence, per head** h of H (x_t in R^P; B_t, C_t in R^N of
the head's group g(h) = h // (H / G), G groups sharing them as GQA's
heads share a key; dt_t > 0; A_h = -exp(A_log_h); state S in R^{P x N},
S_0 = 0):

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D_h x_t

With ``dt_bias`` the step is ``softplus(dt + dt_bias)`` in float32
(Mamba-2's ``dt_softplus``). ``ssd_recurrence`` runs exactly that,
token by token, and is the definition. ``ssd_chunked`` is what the op
lowers to: chunks of ``chunk`` tokens; with a_t = dt_t A_h and
``Acum`` its in-chunk cumulative sum,

    y_t = sum_{s <= t in the chunk} exp(Acum_t - Acum_s) (C_t . B_s) dt_s x_s
          + exp(Acum_t) S_start C_t + D x_t
    S_end = exp(Acum_L) S_start + sum_s exp(Acum_L - Acum_s) dt_s x_s B_s^T

The first line's sum and the chunk's own part of ``S_end`` need no
state: they are made for every chunk at once (blocks of
``_BLOCK_CHUNKS`` under ``lax.map``), then a scan over the chunks
carries S alone and leaves every chunk's start state, and the state
term comes for all chunks at once.

**No decay is factorised.** With A_h up to 64 and steps up to 0.1 a
chunk's cumulative log decay reaches -800, so ``exp(-Acum_s)`` would
leave float32: every in-chunk pairwise decay is ``exp`` of a
DIFFERENCE of two cumulative sums of one chunk, masked to s <= t
before the ``exp`` (so no exponent is ever positive), and everything
else decays forward (``exp(Acum_t)``, ``exp(Acum_L - Acum_s)`` <= 1):
an underflow there is the true value's own.

**Precision.** The steps, the decays, their sums and the carried state
are float32 whatever the inputs' type; the products take x, B, C (and
the decay-weighted matrices, and the state where C reads it) in x's
type (bf16 under AMP) and accumulate in float32.

**Memory.** The one custom VJP keeps the op's inputs alone; the
backward pass is ``jax.vjp`` of the chunked form made again from them.
The in-chunk decay matrices of a layer (64 chunks x 64 heads x 128 x
128 float32: 268 MB at the Nemotron-H cell's size) are never kept.

**Two lowerings of the chunked form.** On a TPU backend, where a
group's heads and the state are whole groups of 128 lanes and the chunk
is too (``ops/pallas/ssd.py takes``), ``ssd_chunked`` takes the Mosaic
kernels of ``ops/pallas/ssd.py``, forward and backward: a grid step is
one chunk of one group of B / C and its heads, the decays and the
decay-weighted matrices are made in VMEM and never written, the state
is carried in VMEM. Everywhere else (the CPU, toy widths) it takes the
XLA form below. The step ``softplus(dt + dt_bias)``, its log decay
``step * A`` and their chain rule stay in XLA around the kernels
(``[B,S,H]`` arrays). ``ssd_lowering.pallas_chunked`` / ``.xla_chunked``
count which a site took, and ``mosaic_tokens_total`` the tokens whose
core ran on the kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .pallas import ssd as ssd_pallas
from .pallas.common import count_lowering, interpret_mode
from .registry import register

_BLOCK_CHUNKS = 8     # chunks to a block of the stateless part
_FLOOR = -80.0        # below it exp() leaves float32's normal range
_HIGHEST = lax.Precision.HIGHEST

# The step's own counts, one persistable the ops add to (as
# ops/kda_ops.py COUNTERS_VAR): tokens and chunks the SSD layers
# walked, elements (token x head) of the in-chunk cumulative log decay
# below ``_FLOOR`` (where a form factorised about the chunk's start
# would have overflowed), and tokens whose core ran as a Mosaic kernel.
COUNTERS_VAR = "__ssd_counters__"
COUNTER_NAMES = ("tokens_total", "chunks_total", "decay_floor_hits_total",
                 "mosaic_tokens_total")


def read_counters(scope):
    """{name: total} from ``scope``, or None where no program with an
    SSD layer has run in it."""
    import numpy as np
    if not scope.has_var(COUNTERS_VAR):
        return None
    v = scope.find_var(COUNTERS_VAR)
    if v is None:
        return None
    return dict(zip(COUNTER_NAMES, np.asarray(v, np.float64).tolist()))


def _step(dt, dt_bias):
    dt = dt.astype(jnp.float32)
    if dt_bias is None:
        return dt
    return jax.nn.softplus(dt + dt_bias.astype(jnp.float32))


def ssd_recurrence(x, dt, a_log, b, c, d, dt_bias=None, *, n_groups=1):
    """The definition, token by token, in float32: x [B,S,H*P], dt
    [B,S,H], a_log and d [H], b and c [B,S,G*N] -> y [B,S,H*P]
    float32."""
    H = a_log.shape[0]
    Bn, S, _ = x.shape
    xh = x.astype(jnp.float32).reshape(Bn, S, H, -1)
    step = _step(dt, dt_bias)                               # [B,S,H]
    per = H // n_groups

    def heads(t):          # [B,S,G*N] -> [B,S,H,N], a group per head
        t = t.astype(jnp.float32).reshape(Bn, S, n_groups, -1)
        return jnp.repeat(t, per, axis=2)

    bh, ch = heads(b), heads(c)
    a = -jnp.exp(a_log.astype(jnp.float32))

    def token(state, inp):
        xt, dtt, bt, ct = inp              # [B,H,P], [B,H], [B,H,N] x2
        state = jnp.exp(dtt * a)[..., None, None] * state \
            + (dtt[..., None] * xt)[..., :, None] * bt[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct,
                                 precision=_HIGHEST)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (xh, step, bh, ch))
    _, y = lax.scan(token, jnp.zeros((Bn, H, xh.shape[-1], bh.shape[-1]),
                                     jnp.float32), xs)
    y = jnp.moveaxis(y, 0, 1) + d.astype(jnp.float32)[:, None] * xh
    return y.reshape(Bn, S, -1)


def _mm(spec, a, b):
    """Operands as they come (x's type under AMP), sums in float32."""
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def _intra(x, step, b, c, a):
    """The stateless part of a block of chunks: x [n,B,L,G,R,P], step
    [n,B,L,G,R] float32, b and c [n,B,L,G,N], a [G,R] -> (the in-chunk
    output [n,B,L,G,R,P] float32, each chunk's own end state
    [n,B,G,R,P,N] float32, exp(Acum) [n,B,L,G,R], exp(Acum_L)
    [n,B,G,R], the elements of Acum under ``_FLOOR`` a token
    [n,B,L])."""
    cd = x.dtype
    L = x.shape[2]
    la = step * a                                        # log decays
    acum = jnp.einsum("ts,nbsgr->nbtgr",
                      jnp.tril(jnp.ones((L, L), jnp.float32)), la,
                      precision=_HIGHEST)
    low = jnp.sum(acum < _FLOOR, (-2, -1), dtype=jnp.float32)
    at = jnp.moveaxis(acum, 2, -1)                       # [n,B,G,R,L]
    tri = jnp.tril(jnp.ones((L, L), bool))
    decay = jnp.exp(jnp.where(tri, at[..., :, None] - at[..., None, :],
                              -jnp.inf))                 # [..,t,s]
    cb = _mm("nbtgk,nbsgk->nbgts", c, b)                 # [n,B,G,L,L]
    m = cb[:, :, :, None] * decay \
        * jnp.moveaxis(step, 2, -1)[..., None, :]
    y = _mm("nbgrts,nbsgrp->nbtgrp", m.astype(cd), x)
    w_end = jnp.exp(acum[:, :, -1:] - acum) * step       # <= step
    s_own = _mm("nbsgrp,nbsgk->nbgrpk",
                (x.astype(jnp.float32) * w_end[..., None]).astype(cd), b)
    return y, s_own, jnp.exp(acum), jnp.exp(acum[:, :, -1]), low


# Jitted for what the KDA core is jitted for (ops/kda_ops.py): a
# model's sites of one signature share ONE trace, forward and backward.
@functools.partial(jax.jit, static_argnums=(7, 8))
def _ssd_forward(x, dt, a_log, b, c, d, dt_bias, n_groups, chunk):
    """(y in x's type, the elements of Acum below ``_FLOOR`` over the
    real positions)."""
    cd = x.dtype
    H = a_log.shape[0]
    Bn, S, _ = x.shape
    G, R = n_groups, H // n_groups
    L = chunk
    blocks = -(-S // (L * _BLOCK_CHUNKS))
    n = blocks * _BLOCK_CHUNKS
    pad = n * L - S

    def chunked(t, *tail):     # [B,S,..] -> [n,B,L,*tail]
        if pad:   # a padded token (step nought) moves no state
            t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return jnp.moveaxis(t.reshape((Bn, n, L) + tail), 1, 0)

    xc = chunked(x, G, R, -1)
    step = chunked(_step(dt, dt_bias), G, R)
    bc, cc = chunked(b.astype(cd), G, -1), chunked(c.astype(cd), G, -1)
    a = -jnp.exp(a_log.astype(jnp.float32)).reshape(G, R)

    def in_blocks(t):          # [n,..] -> [blocks, nb, ..]
        return t.reshape((blocks, _BLOCK_CHUNKS) + t.shape[1:])

    intra = jax.checkpoint(functools.partial(_intra, a=a))
    y, s_own, e_acum, e_end, low = (
        t.reshape((n,) + t.shape[2:]) for t in lax.map(
            lambda t: intra(*t),
            tuple(in_blocks(t) for t in (xc, step, bc, cc))))

    @jax.checkpoint
    def carry(state, inp):
        own, end = inp
        return end[..., None, None] * state + own, state

    P, N = xc.shape[-1], bc.shape[-1]
    _, starts = lax.scan(carry, jnp.zeros((Bn, G, R, P, N), jnp.float32),
                         (s_own, e_end))
    # every chunk's state term at once, from the starts the scan left
    y = y + e_acum[..., None] * _mm("nbtgk,nbgrpk->nbtgrp", cc,
                                    starts.astype(cd)) \
        + d.astype(jnp.float32).reshape(G, R, 1) * xc.astype(jnp.float32)
    y = jnp.moveaxis(y, 0, 1).reshape(Bn, n * L, H * P)[:, :S]
    # a padded token's Acum is the last real one's: it counts for nothing
    low = jnp.moveaxis(low, 0, 1).reshape(Bn, n * L)[:, :S]
    return y.astype(cd), jnp.sum(low)


def lowering(x, a_log, b, n_groups, chunk):
    """``pallas_chunked`` or ``xla_chunked``: by what the site's shapes
    and the backend show, as ``kda_ops.lowering`` chooses."""
    h = a_log.shape[0]
    if not interpret_mode() and ssd_pallas.takes(
            h // n_groups, x.shape[-1] // h, b.shape[-1] // n_groups, chunk):
        return "pallas_chunked"
    return "xla_chunked"


def _steps(dt, a_log, dt_bias):
    """The step and its log decay ``step * A`` [B,S,H], float32."""
    step = _step(dt, dt_bias)
    return step, step * -jnp.exp(a_log.astype(jnp.float32))


# Jitted as the XLA form is: a model's sites of one signature share ONE
# trace of the kernels, forward and backward.
@functools.partial(jax.jit, static_argnums=(7, 8))
def _kernels_forward(x, dt, a_log, b, c, d, dt_bias, n_groups, chunk):
    step, la = _steps(dt, a_log, dt_bias)
    return ssd_pallas.ssd_fwd(x, step, la, b, c, d, n_groups, chunk, _FLOOR)


@functools.partial(jax.jit, static_argnums=(8, 9))
def _kernels_backward(x, dt, a_log, b, c, d, dt_bias, d_out, n_groups,
                      chunk):
    (step, la), pull = jax.vjp(_steps, dt, a_log, dt_bias)
    dx, dstep, dla, db, dc, dd = ssd_pallas.ssd_bwd(
        x, step, la, b, c, d, d_out, n_groups, chunk)
    ddt, da_log, ddt_bias = pull((dstep, dla))
    return dx, ddt, da_log, db, dc, dd, ddt_bias


def _chunked(x, dt, a_log, b, c, d, dt_bias, n_groups, chunk):
    if lowering(x, a_log, b, n_groups, chunk) == "pallas_chunked":
        return _kernels_forward(x, dt, a_log, b, c, d, dt_bias, n_groups,
                                chunk)
    return _ssd_forward(x, dt, a_log, b, c, d, dt_bias, n_groups, chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def ssd_chunked(x, dt, a_log, b, c, d, dt_bias, n_groups=1, chunk=128):
    """The chunked form of ``ssd_recurrence`` (same arguments, dt_bias
    may be None): (y in x's type, the elements of the in-chunk
    cumulative log decay below ``_FLOOR``), on the lowering
    ``lowering`` picks."""
    return _chunked(x, dt, a_log, b, c, d, dt_bias, n_groups, chunk)


def _ssd_fwd(x, dt, a_log, b, c, d, dt_bias, n_groups, chunk):
    # the inputs are all the backward pass keeps
    return (_chunked(x, dt, a_log, b, c, d, dt_bias, n_groups, chunk),
            (x, dt, a_log, b, c, d, dt_bias))


@functools.partial(jax.jit, static_argnums=(8, 9))
def _ssd_backward(x, dt, a_log, b, c, d, dt_bias, d_out, n_groups, chunk):
    _, pull = jax.vjp(
        lambda *t: _ssd_forward(*t, n_groups, chunk)[0],
        x, dt, a_log, b, c, d, dt_bias)
    return pull(d_out)


def _ssd_bwd(n_groups, chunk, res, ct):
    x, _, a_log, b = res[:4]
    if lowering(x, a_log, b, n_groups, chunk) == "pallas_chunked":
        return _kernels_backward(*res, ct[0], n_groups, chunk)
    return _ssd_backward(*res, ct[0], n_groups, chunk)


ssd_chunked.defvjp(_ssd_fwd, _ssd_bwd)


@register("ssd_scan", ["X", "Dt", "ALog", "B", "C", "D", "DtBias",
                       "Counters"],
          ["Out", "CountersOut"], nondiff=("Counters",))
def ssd_scan(x, dt, a_log, b, c, d, dt_bias, counters, *, n_groups=1,
             chunk_size=128):
    """The SSD core (the module's docstring) over x [B,S,H*P], the
    step dt [B,S,H] (``softplus(dt + dt_bias)`` in float32 where
    ``DtBias`` [H] is given), ``A_log`` and ``D`` [H], B and C
    [B,S,G*N]: chunks of ``chunk_size``. ``Out`` [B,S,H*P] has x's
    type; ``CountersOut`` is the input's own variable."""
    n_groups, chunk_size = int(n_groups), int(chunk_size)
    took = lowering(x, a_log, b, n_groups, chunk_size)
    for path in ("pallas_chunked", "xla_chunked"):
        count_lowering("ssd_lowering." + path, float(path == took))
    out, low = ssd_chunked(x, dt, a_log, b, c, d, dt_bias, n_groups,
                           chunk_size)
    bn, s, _ = x.shape
    add = jnp.stack([jnp.float32(bn * s),
                     jnp.float32(bn * (-(-s // chunk_size))), low,
                     jnp.float32(bn * s * (took == "pallas_chunked"))])
    return out, counters + add
