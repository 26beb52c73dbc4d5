"""Mamba-2's SSD core (``ops/ssd_ops.py``) as Mosaic kernels.

The second lowering of ``ops/ssd_ops.py ssd_recurrence`` (the first is
that module's XLA chunked form): the same chunks, the same precision
and the same rule that no exponent is positive and no decay is
clamped, with the chunk's cumulative log decay, every head's in-chunk
pairwise decays and the decay-weighted matrices made and used in VMEM,
and the float32 state of a group's heads carried from chunk to chunk in
VMEM scratch. HBM holds what the op was given and what it returns.

**Layout.** The grid is ``(B, G, chunks)``, the chunks last and
sequential: a grid step is one chunk of one group of B / C and its R =
H / G heads. x ``[B, S, H*P]`` is read in place as a ``(1, L, R*P)``
block (whole lane groups: ``takes``), B and C ``[B, S, G*N]`` as
``(1, L, N)`` blocks read once for the group's heads, so ``C B^T`` is
one product a group-chunk. The steps and their log decays ``step * a``
come head-major, ``[B, G, 2R, S]`` (2 MB at the Nemotron-H cell), a
chunk's ``[2R, L]`` block. The state is kept transposed, ``S^T [N,
R*P]``: the state term ``C S^T`` and the update ``B^T (x w)`` are then
one product each for all R heads, their P-lane columns side by side as
x holds them.

**The decays.** The chunk's cumulative log decay is ``step * a`` times
the 0/1 upper triangle, exact to float32's rounding of the sum (three
bfloat16 parts, ``kda._exact``), and its transpose gives the column
form. A head's pairwise decay is ``exp`` of the DIFFERENCE of two
cumulative sums, masked to s <= t before the ``exp``, as the XLA form
makes it; nothing is factorised about the chunk's start. Then ``M_h =
(C B^T) o decay_h o step_s`` and ``y_h = M_h x_h``: a product over a
whole 128-lane group of x, of which the head's own lanes are kept.

**Precision** as ssd_ops.py states it: the steps, the decays, their
sums, the carried state and its gradient float32; the products take x,
B, C, the decay-weighted matrices and the state where C reads it in
x's type and sum in float32.

**Backward.** The op's custom VJP keeps its inputs alone. ``ssd_bwd``
runs the forward kernel's state part again, which writes every chunk's
start state (float32, 134 MB a site at the cell's size), then one
kernel walks the chunks in reverse carrying dS^T and writes dx, dB and
dC (summed over the group's heads inside the grid step), the gradients
of the steps and of their log decays per head and token, and D's
per-lane sums of ``dy x``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import interpret_mode
from .kda import _NN, _NT, _TN, _dot, _exact
from .kda_small import _fold

_F32 = jnp.float32
_LANES = 128
_VMEM_LIMIT = 64 << 20
# the float32 state of a group's heads, and its gradient, at most
_STATE_BYTES = 4 << 20


def takes(heads, head_dim, state, chunk):
    """Whether the kernels lower a site: a group's ``heads`` of
    ``head_dim`` lanes are whole lane groups, the ``state`` width is,
    the chunk is whole lane groups too (its steps lie along the lanes)
    and at most 256 tokens, and the state of the group fits VMEM."""
    return (heads * head_dim % _LANES == 0 and state % _LANES == 0
            and chunk % _LANES == 0 and chunk <= 256
            and 4 * heads * head_dim * state <= _STATE_BYTES)


def _iota(shape, dim):
    return lax.broadcasted_iota(jnp.int32, shape, dim)


def _spread(col, width):
    """[M, R] -> [M, R * width]: each head's column over its lanes."""
    m, heads = col.shape
    tiles = []
    for lo in range(0, heads * width, _LANES):
        lane = _iota((m, _LANES), 1) + lo
        first = lo // width
        t = jnp.broadcast_to(col[:, first:first + 1], (m, _LANES))
        for h in range(first + 1, min(heads, (lo + _LANES - 1) // width + 1)):
            t = jnp.where(lane >= h * width, col[:, h:h + 1], t)
        tiles.append(t)
    return jnp.concatenate(tiles, axis=-1)


def _gather(z, heads, width):
    """[M, R * width] float32 -> [M, R]: each head's lanes summed, on the
    matrix unit, exact to float32's rounding of the sum."""
    lane = _iota((heads, heads * width), 1)
    lo = _iota((heads, heads * width), 0) * width
    ones = ((lane >= lo) & (lane < lo + width)).astype(jnp.bfloat16)
    return _exact(ones, z, _NT, ones_first=False)


def _head_tiles(h, width):
    """(tile, first lane, last lane + 1) of head h's lanes, tile by
    tile."""
    lo, hi = h * width, (h + 1) * width
    return [(j, max(lo, j * _LANES), min(hi, (j + 1) * _LANES))
            for j in range(lo // _LANES, (hi - 1) // _LANES + 1)]


class _Chunk:
    """A chunk's steps and cumulative log decays: rows ``[R, L]`` and
    columns ``[L, R]``; ``eL`` [1, R] the chunk's whole decay, ``w``
    [L, R] the weight of a token in the state the chunk leaves."""

    def __init__(self, st_ref, heads):
        st = st_ref[0, 0]                       # [2R, L]: step, step * a
        L = self.L = st.shape[-1]
        self.step = st[:heads]
        self.upper = (_iota((L, L), 0) <= _iota((L, L), 1)).astype(
            jnp.bfloat16)
        self.acum = _exact(self.upper, st[heads:], _NN, ones_first=False)
        cols = jnp.transpose(jnp.concatenate([self.acum, self.step], 0))
        self.acum_c, self.step_c = cols[:, :heads], cols[:, heads:]
        last = self.acum_c[L - 1:L]
        self.eL = jnp.exp(last)
        self.e = jnp.exp(self.acum_c)                        # [L, R]
        self.w_decay = jnp.exp(last - self.acum_c)           # <= 1
        self.w = self.w_decay * self.step_c                  # <= step
        self.tri = _iota((L, L), 0) >= _iota((L, L), 1)

    def decay(self, h):
        """Head h's pairwise decays [t, s], nought above the diagonal."""
        return jnp.exp(jnp.where(
            self.tri, self.acum_c[:, h:h + 1] - self.acum[h:h + 1], -jnp.inf))


def _fwd_kernel(d_ref, x_ref, b_ref, st_ref, *rest, heads, width, seq,
                floor, with_out):
    """One chunk of one group. ``with_out``: C in, the output and the
    floor hits out; else every chunk's start state, for the backward
    pass."""
    if with_out:
        c_ref, y_ref, low_ref, s_ref = rest
    else:
        start_ref, s_ref = rest
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _start():
        s_ref[...] = jnp.zeros(s_ref.shape, s_ref.dtype)

    k = _Chunk(st_ref, heads)
    cd = x_ref.dtype
    S = s_ref[...]                                       # [N, R*P]
    x = x_ref[0]
    xf = x.astype(_F32)
    bm = b_ref[0].astype(cd)
    xw = (xf * _spread(k.w, width)).astype(cd)
    s_ref[...] = S * _spread(k.eL, width) + _dot(bm, xw, _TN)
    if not with_out:
        start_ref[0, 0, 0] = S
        return

    cm = c_ref[0].astype(cd)
    cb = _dot(cm, bm, _NT)                               # [L, L]
    y = _spread(k.e, width) * _dot(cm, S.astype(cd), _NN) + d_ref[0] * xf
    tiles = [y[:, j:j + _LANES] for j in range(0, y.shape[-1], _LANES)]
    lane = _iota((k.L, _LANES), 1)
    for h in range(heads):
        m = (cb * k.decay(h) * k.step[h:h + 1]).astype(cd)
        for j, lo, hi in _head_tiles(h, width):
            at = j * _LANES
            mine = (lane >= lo - at) & (lane < hi - at)
            tiles[j] = tiles[j] + jnp.where(
                mine, _dot(m, x[:, at:at + _LANES], _NN), 0.0)
    y_ref[0] = jnp.concatenate(tiles, axis=-1).astype(y_ref.dtype)

    # a padded token's Acum is the last real one's: it counts for nothing
    real = _iota(k.acum.shape, 1) + n * k.L < seq
    hits = jnp.sum(jnp.where(real & (k.acum < floor), 1.0, 0.0)
                   ).reshape(1, 1)

    @pl.when(n == 0)
    def _first():
        low_ref[0, 0] = jnp.zeros(low_ref.shape[2:], _F32)
    low_ref[0, 0] = low_ref[0, 0] + hits


def _bwd_kernel(d_ref, x_ref, b_ref, st_ref, c_ref, dy_ref, s0_ref, dx_ref,
                db_ref, dc_ref, dst_ref, dd_ref, ds_ref, *, heads, width):
    """One chunk of one group, the chunks last to first: ``ds_ref``
    carries the gradient of the state a chunk leaves, ``dd_ref`` sums
    ``dy x`` a lane over the chunks."""
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _start():
        ds_ref[...] = jnp.zeros(ds_ref.shape, ds_ref.dtype)
        dd_ref[0, 0] = jnp.zeros(dd_ref.shape[2:], _F32)

    k = _Chunk(st_ref, heads)
    L, R = k.L, heads
    cd = x_ref.dtype
    x = x_ref[0]
    xf = x.astype(_F32)
    bm, cm = b_ref[0].astype(cd), c_ref[0].astype(cd)
    dy = dy_ref[0].astype(cd)
    dyf = dy.astype(_F32)
    S = s0_ref[0, 0, 0]                     # the chunk's start state
    Sc = S.astype(cd)
    dSn = ds_ref[...]                       # of the state it leaves
    dSc = dSn.astype(cd)
    dd_ref[0, 0] = dd_ref[0, 0] + _fold(dyf * xf)

    # the state term y += e o (C S^T) and the update S' = eL S + B^T xw
    ys = _dot(cm, Sc, _NN)                                # [L, R*P]
    dys = (dyf * _spread(k.e, width)).astype(cd)
    w = _spread(k.w, width)
    xw = (xf * w).astype(cd)
    dxw = _dot(bm, dSc, _NN)                              # [L, R*P]
    dc = _dot(dys, Sc, _NT)                               # [L, N]
    db = _dot(xw, dSc, _NT)
    ds_ref[...] = dSn * _spread(k.eL, width) + _dot(cm, dys, _TN)
    g = _gather(jnp.concatenate(
        [dyf * ys, dxw * xf, _fold(dSn * S, 16)], axis=0), R, width)
    de, dw = g[:L] * k.e, g[L:2 * L]
    deL = jnp.sum(g[2 * L:], 0, keepdims=True) * k.eL     # [1, R]
    v = dw * k.w
    last = _iota((L, R), 0) == L - 1
    dacum_c = de - v + jnp.where(
        last, deL + jnp.sum(v, 0, keepdims=True), 0.0)
    dstep_c = dw * k.w_decay
    dx = dxw.astype(_F32) * w + d_ref[0] * dyf

    # in the chunk, head by head
    cb = _dot(cm, bm, _NT)
    dcb = jnp.zeros((L, L), _F32)
    dx_tiles = [dx[:, j:j + _LANES] for j in range(0, dx.shape[-1], _LANES)]
    lane = _iota((L, _LANES), 1)
    col_of = _iota((L, R), 1)
    row_of = _iota((R, L), 0)
    dstep_r = jnp.zeros((R, L), _F32)
    dacum_r = jnp.zeros((R, L), _F32)
    for h in range(R):
        decay = k.decay(h)
        ds_row = decay * k.step[h:h + 1]
        m = (cb * ds_row).astype(cd)
        dm = None
        for j, lo, hi in _head_tiles(h, width):
            at = j * _LANES
            dyh = jnp.where((lane >= lo - at) & (lane < hi - at),
                            dy[:, at:at + _LANES], 0).astype(cd)
            part = _dot(dyh, x[:, at:at + _LANES], _NT)   # [L, L]
            dm = part if dm is None else dm + part
            dx_tiles[j] = dx_tiles[j] + _dot(m, dyh, _TN)
        dcb = dcb + dm * ds_row
        t1 = dm * cb * decay
        dz = t1 * k.step[h:h + 1]
        dstep_r = jnp.where(row_of == h, jnp.sum(t1, 0, keepdims=True),
                            dstep_r)
        dacum_r = jnp.where(row_of == h, -jnp.sum(dz, 0, keepdims=True),
                            dacum_r)
        dacum_c = dacum_c + jnp.where(
            col_of == h, jnp.sum(dz, 1, keepdims=True), 0.0)
    dcbc = dcb.astype(cd)
    dc = dc + _dot(dcbc, bm, _NN)
    db = db + _dot(dcbc, cm, _TN)

    cols = jnp.transpose(jnp.concatenate([dacum_c, dstep_c], axis=1))
    dacum = dacum_r + cols[:R]
    # Acum = la U: dla = dAcum U^T, the reverse cumulative sum
    dla = _exact(k.upper, dacum, _NT, ones_first=False)
    dst_ref[0, 0] = jnp.concatenate([dstep_r + cols[R:], dla], axis=0)
    dx_ref[0] = jnp.concatenate(dx_tiles, axis=-1).astype(dx_ref.dtype)
    db_ref[0] = db.astype(db_ref.dtype)
    dc_ref[0] = dc.astype(dc_ref.dtype)


def _specs(L, heads, width, state, last=None):
    """BlockSpecs over the grid (B, G, chunks); the chunks run backwards
    from ``last`` where it is given."""
    at = (lambda n: n) if last is None else (lambda n: last - n)
    lanes = lambda w: pl.BlockSpec(                        # noqa: E731
        (1, L, w), lambda b, g, n: (b, at(n), g))
    steps = pl.BlockSpec((1, 1, 2 * heads, L),
                         lambda b, g, n: (b, g, 0, at(n)))
    per_group = lambda w: pl.BlockSpec(                    # noqa: E731
        (1, 1, 8, w), lambda b, g, n: (b, g, 0, 0))
    starts = pl.BlockSpec((1, 1, 1, state, heads * width),
                          lambda b, g, n: (b, g, at(n), 0, 0))
    d = pl.BlockSpec((1, 1, heads * width), lambda b, g, n: (g, 0, 0))
    return lanes, steps, per_group, starts, d


def _operands(x, step, la, b, c, d, n_groups, chunk):
    """The kernels' operands: x, B, C padded to whole chunks (a padded
    token, its step nought, moves no state), the steps and their log
    decays head-major ``[B, G, 2R, S]``, D a lane ``[G, 1, R*P]``."""
    B, S, _ = x.shape
    H = step.shape[-1]
    R = H // n_groups
    pad = -S % chunk
    x, b, c, step, la = (
        jnp.pad(t, ((0, 0), (0, pad), (0, 0))) if pad else t
        for t in (x, b, c, step, la))
    steps = jnp.concatenate([t.reshape(B, S + pad, n_groups, R)
                             for t in (step, la)], axis=-1)
    steps = steps.transpose(0, 2, 3, 1).astype(_F32)
    width = x.shape[-1] // H
    dl = jnp.repeat(d.astype(_F32), width).reshape(n_groups, 1, R * width)
    return x, b, c, steps, dl


def _call(kernel, grid, in_specs, out_specs, out_shape, state_shape,
          name):
    return pl.pallas_call(
        kernel, out_shape=out_shape, grid=grid, in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=[pltpu.VMEM(state_shape, _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret_mode(), name=name)


def _forward(x, steps, b, c, dl, seq, n_groups, chunk, floor, with_out):
    B, Sp, HP = x.shape
    R = steps.shape[2] // 2
    width, N = HP // (n_groups * R), b.shape[-1] // n_groups
    nc = Sp // chunk
    lanes, stspec, per_group, starts, dspec = _specs(chunk, R, width, N)
    if with_out:
        out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype),
                     jax.ShapeDtypeStruct((B, n_groups, 8, _LANES), _F32)]
        out_specs = [lanes(R * width), per_group(_LANES)]
    else:
        out_shape = jax.ShapeDtypeStruct((B, n_groups, nc, N, R * width),
                                         _F32)
        out_specs = starts
    ins, in_specs = [dl, x, b, steps], [dspec, lanes(R * width), lanes(N),
                                        stspec]
    if with_out:
        ins.append(c)
        in_specs.append(lanes(N))
    return _call(
        functools.partial(_fwd_kernel, heads=R, width=width, seq=seq,
                          floor=floor, with_out=with_out),
        (B, n_groups, nc), in_specs, out_specs, out_shape, (N, R * width),
        "ssd_fwd" if with_out else "ssd_states")(*ins)


def ssd_fwd(x, step, la, b, c, d, n_groups, chunk, floor):
    """x [B,S,H*P], the steps and their log decays ``step * A`` [B,S,H]
    float32, B and C [B,S,G*N], D [H] -> (y [B,S,H*P] in x's type, the
    elements of the in-chunk cumulative log decay below ``floor`` over
    the real positions)."""
    S = x.shape[1]
    xp, bp, cp, steps, dl = _operands(x, step, la, b, c, d, n_groups, chunk)
    y, low = _forward(xp, steps, bp, cp, dl, S, n_groups, chunk, floor,
                      True)
    return y[:, :S], jnp.sum(low[:, :, 0, 0])


def ssd_bwd(x, step, la, b, c, d, dy, n_groups, chunk):
    """The gradients of ``ssd_fwd``'s output to x, the steps, their log
    decays, B, C and D, from the inputs alone."""
    B, S, HP = x.shape
    H = step.shape[-1]
    R, width = H // n_groups, HP // H
    N = b.shape[-1] // n_groups
    xp, bp, cp, steps, dl = _operands(x, step, la, b, c, d, n_groups, chunk)
    starts = _forward(xp, steps, bp, cp, dl, S, n_groups, chunk, 0.0, False)
    if xp.shape[1] != S:
        dy = jnp.pad(dy, ((0, 0), (0, xp.shape[1] - S), (0, 0)))
    nc = xp.shape[1] // chunk
    lanes, stspec, per_group, sspec, dspec = _specs(chunk, R, width, N,
                                                    nc - 1)
    dx, db, dc, dst, dd = _call(
        functools.partial(_bwd_kernel, heads=R, width=width),
        (B, n_groups, nc),
        [dspec, lanes(R * width), lanes(N), stspec, lanes(N),
         lanes(R * width), sspec],
        [lanes(R * width), lanes(N), lanes(N), stspec,
         per_group(R * width)],
        [jax.ShapeDtypeStruct(xp.shape, x.dtype),
         jax.ShapeDtypeStruct(bp.shape, b.dtype),
         jax.ShapeDtypeStruct(cp.shape, c.dtype),
         jax.ShapeDtypeStruct(steps.shape, _F32),
         jax.ShapeDtypeStruct((B, n_groups, 8, R * width), _F32)],
        (N, R * width), "ssd_bwd")(dl, xp, bp, steps, cp, dy, starts)
    # [B, G, 2R, S] -> the steps' and the log decays' [B, S, H]
    dst = dst.transpose(0, 3, 1, 2)[:, :S]
    dstep = dst[..., :R].reshape(B, S, H)
    dla = dst[..., R:].reshape(B, S, H)
    dd = jnp.sum(dd, (0, 2)).reshape(H, width).sum(-1)
    return (dx[:, :S], dstep, dla, db[:, :S], dc[:, :S],
            dd.astype(d.dtype))
