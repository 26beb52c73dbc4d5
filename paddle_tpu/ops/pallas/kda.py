"""The chunked delta rule of Kimi Delta Attention as Mosaic kernels.

The second lowering of ``ops/kda_ops.py kda_recurrence`` (the first is
that module's XLA chunked form): the same chunks, the same precision
and the same rule that no exponent is positive and no decay is
clamped, with everything a chunk makes -- the in-chunk cumulative log
decay G, the pairwise decays of P and A, T = (I + A)^-1, W, U -- made
and used in VMEM, and the float32 state carried from chunk to chunk in
VMEM scratch. HBM holds what the op was given and what it returns.

**Layout.** q, k, g ``[B, S, H*dk]``, v ``[B, S, H*dv]`` and beta
``[B, S, H]`` are read in place: the block of a grid step is one chunk
of a group of heads (``_group``: four where they divide H),
``(1, C, 4 dk)`` at ``(b, chunk, group)``; no head-leading copy is
made. Inside, the group is a leading axis of every array and every
product is batched over it: the heads' chains of dependent products
then interleave in program order, which is what hides the latency of
the inverse's series. The grid is ``(B, chunks, H / 4)`` with the
groups innermost, so that a chunk's ``[C, H]`` block of beta (and of
its gradient) stays put while the heads pass; the states of all H
heads sit in one scratch ``[H, dk, dv]``. Chunks are sequential
(``"arbitrary"``).

**The pairwise decays** ``sum_c x_ic k_jc exp(G_ic - G_jc)``, j <= i,
are matrix products of operands decayed about a reference row r with
j <= r <= i, so both exponents are <= 0: a row block of ``sub`` rows
against ALL the earlier keys about the block's first row (as the XLA
form), and inside a block, where the XLA form goes pair by pair, by
halving: at size s the rows of every odd s-block against the keys of
the even block before it, about the odd block's first row, down to
s = 1; the diagonal needs no decay. Every exponent is a sum of g over
a run of the chunk's rows, made EXACTLY as a 0/1 matrix times g split
into three bfloat16 parts (``_plan``, ``_exact``): no difference of
two cumulative sums is taken.

**Precision** as kda_ops.py states it: G, A, T and the carried state
float32 (the state in ``state_dtype`` where a caller sets another);
products take their operands in q's type and sum in float32; T is made
(the doubling series) and applied at full precision.

**Backward.** The custom VJP of ``kda_ops.kda_chunked`` keeps the op's
inputs alone. ``kda_bwd`` runs the forward kernel's state part again,
which writes every chunk's start state and T (float32, 268 + 134 MB a
site at the benchmark's size: T twice side by side, as its products
want it), then one kernel walks the chunks in
reverse carrying dS, makes the chunk's own quantities again from the
inputs and writes dq, dk, dv, dg and dbeta. The log decay's gradient
needs no pass of its own: G enters only as ``x exp(+G_i)`` or
``k exp(-G_j)``, so ``dG = q dq + k (dk_plus - dk_minus)`` and dg is
its reverse cumulative sum.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import interpret_mode

_F32, _BF16 = jnp.float32, jnp.bfloat16
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))
_VMEM_LIMIT = 64 << 20


def takes(dk, dv, chunk, sub):
    """Whether the kernels lower these widths: a head is whole lane
    groups, a chunk whole sublane tiles of either type, the sub-block a
    power of two that divides it."""
    sub = min(sub, chunk)
    return (dk % 128 == 0 and dv % 128 == 0 and chunk % 16 == 0
            and sub % 8 == 0 and sub & (sub - 1) == 0
            and chunk % sub == 0)


class _Plan:
    """The chunk's exponents as rows of one 0/1 matrix ``D`` [R, C] (an
    exponent is ``D @ g``), where each piece starts (``at``), the
    halving levels' sizes and their masks ``M`` [levels, 2C, 2C] (row i
    against key j, for P above A, and twice side by side)."""

    def __init__(self, C, b):
        t = np.arange(C)
        i, tt = t[:, None], t[None, :]
        pieces, self.at, rows = [], {}, 0

        def add(name, m):
            nonlocal rows
            self.at[name] = (rows, m.shape[0])
            pieces.append(m)
            rows += m.shape[0]

        add("G", tt <= i)                           # G_i
        add("rest", tt > i)                         # G_C - G_i
        add("x_top", (tt > i // b * b) & (tt <= i))   # G_i - G_first(i)
        for lo in range(b, C, b):                   # G_lo - G_j, j < lo
            add(("k_top", lo), (tt > i[:lo]) & (tt <= lo))
        masks, levels, s = [], [], b // 2
        while s >= 1:
            r = i // s * s
            if s > 1:                       # G_i - G_r; nought at s = 1
                add(("x", s), (tt > r) & (tt <= i))
            add(("k", s), (tt > i) & (tt <= np.minimum(r + s, C - 1)))
            m = (i // s == tt // s + 1) & (i // (2 * s) == tt // (2 * s))
            masks.append(np.tile(m, (2, 2)))
            levels.append(s)
            s //= 2
        self.C, self.sub, self.levels = C, b, tuple(levels)
        self.D = np.concatenate(pieces).astype(np.float32)
        self.M = (np.stack(masks).astype(np.float32) if masks
                  else np.zeros((1, 2 * C, 2 * C), np.float32))


_plan = functools.lru_cache(maxsize=None)(_Plan)


def _dot(a, b, dims):
    """A product over the last two axes, summed in float32; operands
    with a leading axis (a group of heads) are batched over it."""
    if a.ndim == 3:
        (ca,), (cb,) = dims
        dims = (((ca + 1,), (cb + 1,)), ((0,), (0,)))
    else:
        dims = (dims, ((), ()))
    return lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _parts(x):
    """float32 ``x`` as three bfloat16 parts that sum to it."""
    hi = x.astype(_BF16)
    r = x - hi.astype(_F32)
    mid = r.astype(_BF16)
    return hi, mid, (r - mid.astype(_F32)).astype(_BF16)


def _exact(ones, x, dims=_NN, ones_first=True):
    """A 0/1 matrix (bfloat16) times float32 ``x``, exact to float32's
    rounding of the sum: x in three bfloat16 parts, each product exact.
    """
    out = None
    for part in _parts(x):
        y = _dot(ones, part, dims) if ones_first else \
            _dot(part, ones, dims)
        out = y if out is None else out + y
    return out


def _left(parts, first):
    """The left operand of ``_times`` from the parts of a [., C, 2C]
    matrix that is itself twice side by side; ``first`` marks its first
    copy's lanes. The parts go side by side along the contraction:
    [h m | h m | h l]."""
    h, m, l = parts
    hm = jnp.where(first, h, m)
    return jnp.concatenate([hm, hm, jnp.where(first, h, l)], axis=-1)


def _right(parts):
    """The right operand from the parts of a [., C, n] matrix, one
    above the other in the order that meets ``_left``'s: h m, m h, l h.
    """
    h, m, l = parts
    return jnp.concatenate([h, m, m, h, l, h], axis=-2)


def _times(left, right):
    """A float32 product at full precision as ONE bfloat16 product over
    a contraction of 6C: ``ah bh + am bm + ah bm + am bh + ah bl +
    al bh``, summed in the MXU's float32 (what ``Precision.HIGHEST``
    makes in six products of C), each operand split once however many
    products it enters."""
    return _dot(left, right, _NN)


def _inv_unit_lower(A2, eye2, first):
    """(I + A)^-1, A strictly lower float32: the whole series
    ``(I - A)(I + A^2)(I + A^4)...`` (A^C = 0). Every matrix here is
    [., C, 2C], itself twice side by side: a product with such a right
    operand comes out so, and its parts pair up by a select."""
    T2, P, power = eye2 - A2, _parts(A2), 2
    while power < A2.shape[-2]:
        P = _parts(_times(_left(P, first), _right(P)))
        T2 = T2 + _times(_left(_parts(T2), first), _right(P))
        power *= 2
    return T2


def _heads(x, n):
    """[C, n * w] as it lies in HBM -> [n, C, w]: whole lane groups."""
    w = x.shape[-1] // n
    return jnp.stack([x[:, i * w:(i + 1) * w] for i in range(n)])


def _lanes(x):
    """[n, C, w] -> [C, n * w]."""
    return jnp.concatenate(list(x), axis=-1)


class _Chunk:
    """What one chunk of a group of heads makes from its inputs alone."""


def _chunk_local(plan, d_ref, lm_ref, q_ref, k_ref, v_ref, g_ref, b_ref,
                 h0, n, *, scale, with_q, t=None):
    """The chunk's own quantities (no state) for the ``n`` heads from
    ``h0`` on, every array [n, ., .]. ``with_q``: P and the q-side
    operands too; ``t`` is T (twice side by side, as
    ``_inv_unit_lower`` leaves it) where it was kept, else it is made.
    Of a pair of operands the q side comes first where there are both.
    """
    c = _Chunk()
    cd = q_ref.dtype
    C, sub = plan.C, plan.sub
    kf = _heads(k_ref[0], n).astype(_F32)
    c.rk = lax.rsqrt(jnp.sum(kf * kf, -1, keepdims=True) + 1e-6)
    c.kn = kf * c.rk
    if with_q:
        qf = _heads(q_ref[0], n).astype(_F32)
        c.rq = lax.rsqrt(jnp.sum(qf * qf, -1, keepdims=True) + 1e-6)
        c.qh = qf * c.rq
        c.qn = c.qh * scale
    sides = ([c.qn] if with_q else []) + [c.kn]
    c.vf = _heads(v_ref[0], n).astype(_F32)
    g = g_ref[0].astype(_F32)                             # [C, n * dk]
    bl = b_ref[0].astype(_F32)                            # [C, H]
    lane = lax.broadcasted_iota(jnp.int32, bl.shape, 1)
    c.beta = jnp.stack([
        jnp.sum(jnp.where(lane == h0 + i, bl, 0.0), -1, keepdims=True)
        for i in range(n)])                               # [n, C, 1]

    X = _exact(d_ref[...], g)                             # [R, n * dk]
    E = jnp.exp(X)

    def piece(name, of=E):
        lo, rows = plan.at[name]
        return _heads(of[lo:lo + rows], n)

    c.piece = piece
    c.G, c.eG, c.eRest = piece("G", X), piece("G"), piece("rest")
    # exp(G_C) down the sublanes, [n, dk, 128]: the state's row decay
    c.eGC_col = jnp.exp(_exact(jnp.ones((C, 128), _BF16), g, _TN,
                               ones_first=False)).reshape(n, -1, 128)
    # where T is made, P and A come twice side by side ([., 2C]): the
    # keys are given twice to the products that make them
    make_t = t is None
    twice = (lambda x: jnp.concatenate([x, x], axis=1)) if make_t else \
        (lambda x: x)
    wide = 2 * C if make_t else C
    row = lax.broadcasted_iota(jnp.int32, (C, 2 * C), 0)
    col = lax.broadcasted_iota(jnp.int32, (C, 2 * C), 1)
    c.first = col < C
    col = jnp.where(c.first, col, col - C)
    eye2 = (row == col).astype(_F32)
    c.eye = eye2[:, :C]
    c.lower = (col <= row).astype(_F32)[:, :C]
    c.strict = (col < row).astype(_F32)[:, :C]

    # row blocks of ``sub`` against all the earlier keys
    c.dx_top = piece("x_top")
    c.x_top = [(x * c.dx_top).astype(cd) for x in sides]
    c.kd_top = {}
    blocks = [jnp.zeros((n, len(sides) * sub, wide), _F32)]
    for lo in range(sub, C, sub):
        kd = (c.kn[:, :lo] * piece(("k_top", lo))).astype(cd)
        kd = jnp.concatenate(
            [kd, jnp.zeros((n, C - lo, kd.shape[-1]), cd)], axis=1)
        c.kd_top[lo] = kd
        lhs = jnp.concatenate([x[:, lo:lo + sub] for x in c.x_top],
                              axis=1)
        blocks.append(_dot(lhs, twice(kd), _NT))    # [n, sides*sub, wide]
    pa = jnp.concatenate([
        jnp.concatenate([z[:, i * sub:(i + 1) * sub] for z in blocks],
                        axis=1)
        for i in range(len(sides))], axis=1)        # [n, sides*C, wide]
    # inside a block, by halving
    c.x_lvl, c.kd_lvl = [], []
    for l, s in enumerate(plan.levels):
        xs = sides
        if s > 1:
            dx = piece(("x", s))
            xs = [x * dx for x in xs]
        lhs = jnp.concatenate([x.astype(cd) for x in xs], axis=1)
        kd = (c.kn * piece(("k", s))).astype(cd)
        c.x_lvl.append(lhs)
        c.kd_lvl.append(kd)
        m = lm_ref[l] if with_q else lm_ref[l, :C]
        pa = pa + _dot(lhs, twice(kd), _NT) * m[:, :wide]
    if with_q:
        c.P = pa[:, :C, :C] + c.eye * _dot(
            c.qn.astype(cd), c.kn.astype(cd), _NT)
    c.Abar = pa[:, -C:, :C]
    c.Kb = c.beta * c.kn * c.eG
    c.Vb = c.beta * c.vf
    c.T2 = _inv_unit_lower(c.beta * pa[:, -C:], eye2, c.first) \
        if make_t else t
    c.Tp = _parts(c.T2)
    c.WU = _times(_left(c.Tp, c.first),
                  _right(_parts(jnp.concatenate([c.Kb, c.Vb], axis=-1))))
    dk = c.Kb.shape[-1]
    c.W, c.U = c.WU[..., :dk], c.WU[..., dk:]
    c.Wc = c.W.astype(cd)
    c.Kd = (c.kn * c.eRest).astype(cd)
    return c


def _row_decay(S, eGC_col):
    """Diag(exp(G_C)) S for S [n, dk, dv], the decay [n, dk, 128]."""
    reps = S.shape[-1] // 128
    return S * (eGC_col if reps == 1 else
                jnp.concatenate([eGC_col] * reps, -1))


def _fwd_kernel(d_ref, lm_ref, q_ref, k_ref, v_ref, g_ref, b_ref, *rest,
                plan, scale, seq, floor, group, with_out):
    """One chunk of ``group`` heads. ``with_out``: the output and the
    floor hits; else every chunk's start state and T, for the backward
    pass."""
    if with_out:
        o_ref, low_ref, s_ref = rest
    else:
        st_ref, t_ref, s_ref = rest
    n, hg = pl.program_id(1), pl.program_id(2)
    at = pl.ds(hg * group, group)
    cd = q_ref.dtype

    @pl.when(n == 0)
    def _start():
        s_ref[at] = jnp.zeros((group,) + s_ref.shape[1:], s_ref.dtype)

    c = _chunk_local(plan, d_ref, lm_ref, q_ref, k_ref, v_ref, g_ref,
                     b_ref, hg * group, group, scale=scale,
                     with_q=with_out)
    S = s_ref[at]                                         # [n, dk, dv]
    Sl = S.astype(cd)
    Vnc = (c.U - _dot(c.Wc, Sl, _NN)).astype(cd)          # [n, C, dv]
    new = _row_decay(S.astype(_F32), c.eGC_col) + _dot(c.Kd, Vnc, _TN)
    s_ref[at] = new.astype(s_ref.dtype)
    if not with_out:
        st_ref[0, 0] = S
        t_ref[0, 0] = c.T2
        return
    o = _dot((c.qn * c.eG).astype(cd), Sl, _NN) \
        + _dot(c.P.astype(cd), Vnc, _NN)
    o_ref[0] = _lanes(o).astype(o_ref.dtype)
    # a padded token's G is the last real one's: it counts for nothing
    real = (lax.broadcasted_iota(jnp.int32, c.G.shape, 1)
            + n * plan.C) < seq
    hits = jnp.sum(jnp.where(real & (c.G < floor), 1.0, 0.0)
                   ).reshape(1, 1)

    @pl.when(hg == 0)
    def _first():
        low_ref[0, 0] = jnp.zeros(low_ref.shape[2:], _F32)
    low_ref[0, 0] = low_ref[0, 0] + hits


def _bwd_kernel(d_ref, lm_ref, u_ref, q_ref, k_ref, v_ref, g_ref, b_ref,
                do_ref, st_ref, t_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                db_ref, ds_ref, *, plan, scale, group):
    """One chunk of ``group`` heads, the chunks last to first:
    ``ds_ref`` carries the gradient of the state a chunk leaves."""
    n, hg = pl.program_id(1), pl.program_id(2)
    at = pl.ds(hg * group, group)
    cd = q_ref.dtype
    C, sub = plan.C, plan.sub

    @pl.when(n == 0)
    def _start():
        ds_ref[at] = jnp.zeros((group,) + ds_ref.shape[1:], ds_ref.dtype)

    c = _chunk_local(plan, d_ref, lm_ref, q_ref, k_ref, v_ref, g_ref,
                     b_ref, hg * group, group, scale=scale, with_q=True,
                     t=t_ref[0, 0])
    S = st_ref[0, 0]
    Sl = S.astype(cd)
    Vnc = (c.U - _dot(c.Wc, Sl, _NN)).astype(cd)
    Qg = (c.qn * c.eG).astype(cd)
    Pc = c.P.astype(cd)
    dO = _heads(do_ref[0], group).astype(cd)
    dSn = ds_ref[at]                    # of the state this chunk leaves
    dSc = dSn.astype(cd)
    dSf = dSn.astype(_F32)

    dQg = _dot(dO, Sl, _NT)                               # [n, C, dk]
    dP = _dot(dO, Vnc, _NT) * c.lower                     # [n, C, C]
    dVn = _dot(Pc, dO, _TN) + _dot(c.Kd, dSc, _NN)        # [n, C, dv]
    dVnc = dVn.astype(cd)
    dKd = _dot(Vnc, dSc, _NT)                             # [n, C, dk]
    dW = -_dot(dVnc, Sl, _NT)                             # [n, C, dk]
    dS = _row_decay(dSf, c.eGC_col) + _dot(Qg, dO, _TN) \
        - _dot(c.Wc, dVnc, _TN)
    ds_ref[at] = dS.astype(ds_ref.dtype)
    # sum_v dS exp(G_C) S as a row [n, 1, dk]: G_C's share of dg
    ones = jnp.ones((group, 8, S.shape[-1]), _BF16)
    dGC = _exact(ones, _row_decay(dSf * S.astype(_F32), c.eGC_col),
                 _NT)[:, :1]

    # W = T Kb, U = T Vb, T = (I + A)^-1: dA = -(T^T dW) W^T - ...
    dk_w = c.Kb.shape[-1]
    # T^T [dW | dU]: the parts meet along the rows (h m, m h, l h)
    th, tm, tl = (x[..., :C] for x in c.Tp)
    dKbVb = _dot(jnp.concatenate([th, tm, th, tm, th, tl], axis=1),
                 _right(_parts(jnp.concatenate([dW, dVn], axis=-1))),
                 _TN)
    dKb, dVb = dKbVb[..., :dk_w], dKbVb[..., dk_w:]
    # -([dKb | dVb] [W | U]^T): the parts side by side on both
    ah, am, al = _parts(dKbVb)
    bh, bm, bl = _parts(c.WU)
    dA = -_dot(jnp.concatenate([ah, am, ah, am, ah, al], axis=-1),
               jnp.concatenate([bh, bm, bm, bh, bl, bh], axis=-1),
               _NT) * c.strict
    dbeta = jnp.sum(dA * c.Abar, -1, keepdims=True) \
        + jnp.sum(dKb * c.kn * c.eG, -1, keepdims=True) \
        + jnp.sum(dVb * c.vf, -1, keepdims=True)
    dAbar = c.beta * dA
    dv_ref[0] = _lanes(c.beta * dVb).astype(dv_ref.dtype)

    # by the sign G enters with: q and the row side of k with +G_i, the
    # key side of k with -G_j
    dqn = dQg * c.eG
    dk_plus = c.beta * dKb * c.eG
    dKd_e = dKd * c.eRest
    dk_minus = dKd_e
    dGC = dGC + jnp.sum(dKd_e * c.kn, 1, keepdims=True)

    dpa = jnp.concatenate([dP, dAbar], axis=1)            # [n, 2C, C]
    # the diagonal of P: q_i . k_i, no decay (its two shares of dG
    # cancel)
    diag = jnp.sum(dP * c.eye, -1, keepdims=True)
    dqn = dqn + diag * c.kn
    dk_minus = dk_minus + diag * c.qn
    # row blocks against all the earlier keys
    dx_q = [jnp.zeros((group, sub, dk_w), _F32)]
    dx_k = [jnp.zeros((group, sub, dk_w), _F32)]
    for lo in range(sub, C, sub):
        dz = jnp.concatenate([dP[:, lo:lo + sub], dAbar[:, lo:lo + sub]],
                             axis=1).astype(cd)
        dx = _dot(dz, c.kd_top[lo], _NN)                  # [n, 2 sub, dk]
        dx_q.append(dx[:, :sub])
        dx_k.append(dx[:, sub:])
        lhs = jnp.concatenate([x[:, lo:lo + sub] for x in c.x_top],
                              axis=1)
        dkd = _dot(dz, lhs, _TN)[:, :lo] * c.piece(("k_top", lo))
        dk_minus = dk_minus + jnp.concatenate(
            [dkd, jnp.zeros((group, C - lo, dk_w), _F32)], axis=1)
    dqn = dqn + jnp.concatenate(dx_q, axis=1) * c.dx_top
    dk_plus = dk_plus + jnp.concatenate(dx_k, axis=1) * c.dx_top
    # inside a block
    for l, s in enumerate(plan.levels):
        dz = (dpa * lm_ref[l, :, :C]).astype(cd)
        dx = _dot(dz, c.kd_lvl[l], _NN)                   # [n, 2C, dk]
        if s > 1:
            dx = dx * jnp.concatenate([c.piece(("x", s))] * 2, axis=1)
        dqn = dqn + dx[:, :C]
        dk_plus = dk_plus + dx[:, C:]
        dk_minus = dk_minus + _dot(dz, c.x_lvl[l], _TN) \
            * c.piece(("k", s))

    dG = c.qn * dqn + c.kn * (dk_plus - dk_minus)
    dg_ref[0] = (_exact(u_ref[...], _lanes(dG))
                 + _lanes(dGC)).astype(dg_ref.dtype)
    # through the two L2 norms
    dq = (c.rq * scale) * (dqn - c.qh * jnp.sum(dqn * c.qh, -1,
                                                keepdims=True))
    dkn = dk_plus + dk_minus
    dk = c.rk * (dkn - c.kn * jnp.sum(dkn * c.kn, -1, keepdims=True))
    dq_ref[0] = _lanes(dq).astype(dq_ref.dtype)
    dk_ref[0] = _lanes(dk).astype(dk_ref.dtype)
    lane = lax.broadcasted_iota(jnp.int32, db_ref.shape[1:], 1)
    db = db_ref[0]
    for i in range(group):
        db = jnp.where(lane == hg * group + i, dbeta[i], db)
    db_ref[0] = db


def _consts(plan):
    return jnp.asarray(plan.D, _BF16), jnp.asarray(plan.M, _F32)


def _padded(xs, chunk):
    """[B, S, .] arrays padded to whole chunks: a padded token (k, v,
    beta, g nought) moves no state."""
    pad = -xs[0].shape[1] % chunk
    if pad:
        xs = [jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in xs]
    return xs


def _group(H):
    """Heads to a grid step: their chains of dependent products (the
    inverse's doubling series) interleave and a step's fixed cost is
    shared. On the chip at 32 heads of 128 a site's forward + backward
    read 29.4 / 21.3 / 17.6 / 16.7 ms at 1 / 2 / 4 / 8; eight compile
    twice as long as four."""
    return next(n for n in (4, 2, 1) if H % n == 0)


def _specs(C, H, group, last=None):
    """BlockSpecs over the grid (B, chunks, H / group); the chunks run
    backwards from ``last`` where it is given."""
    at = (lambda n: n) if last is None else (lambda n: last - n)
    const = lambda shape: pl.BlockSpec(                   # noqa: E731
        shape, lambda b, n, h: (0,) * len(shape))
    head = lambda w: pl.BlockSpec(                        # noqa: E731
        (1, C, group * w), lambda b, n, h: (b, at(n), h))
    beta = pl.BlockSpec((1, C, H), lambda b, n, h: (b, at(n), 0))
    per_chunk = lambda *tail: pl.BlockSpec(               # noqa: E731
        (1, 1, group) + tail, lambda b, n, h: (b, at(n), h, 0, 0))
    return const, head, beta, per_chunk


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9, 10))
def _forward(q, k, v, g, beta, scale, chunk, sub, state_dtype, floor,
             with_out):
    B, S, _ = q.shape
    H = beta.shape[-1]
    dk, dv = q.shape[-1] // H, v.shape[-1] // H
    group = _group(H)
    plan = _plan(chunk, min(sub, chunk))
    q, k, v, g, beta = _padded([q, k, v, g, beta.astype(_F32)], chunk)
    N = q.shape[1] // chunk
    const, head, bspec, per_chunk = _specs(chunk, H, group)
    D, M = _consts(plan)
    if with_out:
        out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype),
                     jax.ShapeDtypeStruct((B, N, 8, 128), _F32)]
        out_specs = [head(dv), pl.BlockSpec(
            (1, 1, 8, 128), lambda b, n, h: (b, n, 0, 0))]
    else:
        out_shape = [
            jax.ShapeDtypeStruct((B, N, H, dk, dv), state_dtype),
            jax.ShapeDtypeStruct((B, N, H, chunk, 2 * chunk), _F32)]
        out_specs = [per_chunk(dk, dv), per_chunk(chunk, 2 * chunk)]
    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, plan=plan, scale=scale, seq=S,
                          floor=floor, group=group, with_out=with_out),
        out_shape=out_shape,
        grid=(B, N, H // group),
        in_specs=[const(D.shape), const(M.shape), head(dk), head(dk),
                  head(dv), head(dk), bspec],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((H, dk, dv), state_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret_mode(),
        name="kda_fwd" if with_out else "kda_states",
    )(D, M, q, k, v, g, beta)
    if with_out:
        return outs[0][:, :S], jnp.sum(outs[1][:, :, 0, 0])
    return outs


def kda_fwd(q, k, v, g, beta, scale, chunk, sub, state_dtype, floor):
    """q, k, g [B,S,H*dk], v [B,S,H*dv], beta [B,S,H] -> (the output
    [B,S,H*dv] in v's type, the elements of the in-chunk cumulative log
    decay below ``floor`` over the real positions)."""
    return _forward(q, k, v, g, beta, scale, chunk, sub,
                    jnp.dtype(state_dtype), floor, True)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def kda_bwd(q, k, v, g, beta, d_out, scale, chunk, sub, state_dtype):
    """The gradients of ``kda_fwd``'s output to q, k, v, g and beta,
    from the inputs alone."""
    state_dtype = jnp.dtype(state_dtype)
    B, S, _ = q.shape
    H = beta.shape[-1]
    dk, dv = q.shape[-1] // H, v.shape[-1] // H
    group = _group(H)
    plan = _plan(chunk, min(sub, chunk))
    starts, T = _forward(q, k, v, g, beta, scale, chunk, sub,
                         state_dtype, 0.0, False)
    types = [x.dtype for x in (q, k, v, g, beta)]
    q, k, v, g, beta, d_out = _padded(
        [q, k, v, g, beta.astype(_F32), d_out], chunk)
    N = q.shape[1] // chunk
    const, head, bspec, per_chunk = _specs(chunk, H, group, N - 1)
    D, M = _consts(plan)
    U = jnp.asarray(np.triu(np.ones((chunk, chunk))), _BF16)
    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, plan=plan, scale=scale,
                          group=group),
        out_shape=[jax.ShapeDtypeStruct(q.shape, types[0]),
                   jax.ShapeDtypeStruct(k.shape, types[1]),
                   jax.ShapeDtypeStruct(v.shape, types[2]),
                   jax.ShapeDtypeStruct(g.shape, _F32),
                   jax.ShapeDtypeStruct(beta.shape, _F32)],
        grid=(B, N, H // group),
        in_specs=[const(D.shape), const(M.shape), const(U.shape),
                  head(dk), head(dk), head(dv), head(dk), bspec,
                  head(dv), per_chunk(dk, dv),
                  per_chunk(chunk, 2 * chunk)],
        out_specs=[head(dk), head(dk), head(dv), head(dk), bspec],
        scratch_shapes=[pltpu.VMEM((H, dk, dv), state_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret_mode(),
        name="kda_bwd",
    )(D, M, U, q, k, v, g, beta, d_out, starts, T)
    return tuple(x[:, :S].astype(t) for x, t in zip(outs, types))
