"""The two small ops beside the KDA core (ops/kda_ops.py ``short_conv``
and ``gated_rms_norm``) as Mosaic kernels, forward and backward: each
reads its inputs once and writes its outputs once, in their own types,
and nothing of x's size lives in HBM between them.

One shape for the four kernels: a grid step holds a tile of whole rows
(as many lanes as the array has, up to ``_TILE_LANES``: long bursts
from HBM) by ``_TILE_ELEMENTS`` elements; inside it a ``fori_loop``
walks the rows in chunks and, for each, the lanes in groups (``_GROUP``
channels of the convolution by ``_ROWS`` rows, a head of the norm by up
to ``_NORM_ROWS``) whose float32 values are a few vector registers: a
group's chunk is loaded, worked on and stored once. ``_UNROLL`` groups
stand side by side in the loop's body and a loop over the lanes walks
the rest, so a body's size (the seconds its trace and lowering take)
does not grow with the width; the eight or sixteen registers of a
group's array are the independent chains the scheduler interleaves (two
registers an array waited out every exp, reciprocal and lane reduction:
2 to 5 times slower). What a pass sums over positions (``dw``, ``dscale``)
is added, eight sublanes of partial sums a lane, to an output block
that stays in VMEM over the row tiles.

The convolution's taps reach ``K - 1`` rows back: a chunk is worked on
together with the chunk before it (for a tile's first chunk the last
rows of the tile before, read as a block of their own), and the taps
are sublane rolls of the pair. Its backward pass needs ``g = dy
silu'(pre)`` ``K - 1`` rows AHEAD, so it walks a tile's chunks from the
last to the first, the chunk after's g kept in scratch, and makes the
first g of the tile after again from that tile's first rows.

On the chip at ``[1,8192,4096]`` in bf16 (PERF.md section 6, PR 37):
0.28 + 0.50 ms a convolution and 0.35 + 0.61 ms a norm, forward +
backward, where the arrays read and written once at the chip's 819
GB/s are 0.16 + 0.25 and 0.25 + 0.41.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import interpret_mode

_F32 = jnp.float32
_ROWS = 16            # a chunk: one sublane tile of bf16, two of float32
_TILE_LANES = 4096    # lanes to a grid step, at most: whole rows stream best
_TILE_ELEMENTS = 1 << 20      # of one array to a grid step (2 MiB of bf16)
_GROUP = 512          # the convolution's channels to a chunk
_NORM_ROWS = 128      # the norm's rows to a chunk, at most (its lanes: a head)
_UNROLL = 2           # lane groups side by side in a loop's body
_VMEM_LIMIT = 64 << 20


def _tile(n, unit, cap):
    """The largest multiple of ``unit`` that divides n and is <= cap."""
    return max(t for t in range(unit, min(n, cap) + 1, unit) if n % t == 0)


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _fold(v):
    """[R, L] -> [8, L]: the sublane tiles added."""
    out = v[:8]
    for at in range(8, v.shape[0], 8):
        out = out + v[at:at + 8]
    return out


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _chunk_at(r, rows=_ROWS):
    return pl.ds(pl.multiple_of(r * rows, rows), rows)


def _over_lanes(lanes, width, body):
    """``body(ln)`` for every group of ``width`` lanes: a loop whose
    body holds ``_UNROLL`` of them."""
    per = max(u for u in range(1, _UNROLL + 1) if (lanes // width) % u == 0)

    def block(i, _):
        for u in range(per):
            body(pl.ds(pl.multiple_of((i * per + u) * width, width), width))
        return 0

    lax.fori_loop(0, lanes // (per * width), block, 0)


# ---------------------------------------------------------------- conv

def conv_takes(x, w):
    """Whole sublane tiles of rows, whole lane groups of channels, 2 to
    4 taps: what the tests lower for the chip."""
    return (x.ndim == 3 and x.shape[1] % _ROWS == 0
            and x.shape[2] % 128 == 0 and 2 <= w.shape[1] <= 4)


def _taps(lo, cur, w_ref, ln, k):
    """lo, cur [16, L] float32, consecutive rows -> (the float32
    pre-activation of cur's rows, the k views ``x_{t-(k-1)+i}``)."""
    pair = jnp.concatenate([lo, cur], 0)
    taps = [pltpu.roll(pair, k - 1 - i, 0)[_ROWS:] for i in range(k - 1)]
    taps.append(cur)
    pre = taps[0] * w_ref[0:1, ln]
    for i in range(1, k):
        pre = pre + taps[i] * w_ref[i:i + 1, ln]
    return pre, taps


def _conv_fwd_kernel(w_ref, prev_ref, x_ref, o_ref, *, k, rows):
    first = pl.program_id(2) == 0
    lanes = x_ref.shape[2]
    width = _tile(lanes, 128, _GROUP)

    def chunk(before, at):
        def group(ln):
            pre, _ = _taps(before(ln), x_ref[0, at, ln].astype(_F32),
                           w_ref, ln, k)
            o_ref[0, at, ln] = (pre * _sigmoid(pre)).astype(o_ref.dtype)
        _over_lanes(lanes, width, group)

    chunk(lambda ln: jnp.where(first, 0.0, prev_ref[0, :, ln].astype(_F32)),
          pl.ds(0, _ROWS))

    def step(r, _):
        chunk(lambda ln: x_ref[0, _chunk_at(r - 1), ln].astype(_F32),
              _chunk_at(r))
        return 0

    lax.fori_loop(1, rows // _ROWS, step, 0)


def _conv_bwd_kernel(w_ref, prev_ref, x_ref, next_ref, dy_ref, dy_next_ref,
                     dx_ref, dw_ref, g_ref, *, k, rows):
    b, s = pl.program_id(1), pl.program_id(2)
    first, last = s == 0, s == pl.num_programs(2) - 1
    n, lanes = rows // _ROWS, x_ref.shape[2]
    width = _tile(lanes, 128, _GROUP)

    @pl.when((b == 0) & first)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def g_of(lo, cur, dy, ln):
        pre, taps = _taps(lo, cur, w_ref, ln, k)
        sig = _sigmoid(pre)
        return dy * (sig * (1.0 + pre * (1.0 - sig))), taps

    def after(ln):      # the tile after's first g: nought past the end
        g_ref[:, ln], _ = g_of(
            x_ref[0, _chunk_at(n - 1), ln].astype(_F32),
            next_ref[0, :, ln].astype(_F32),
            jnp.where(last, 0.0, dy_next_ref[0, :, ln].astype(_F32)), ln)

    _over_lanes(lanes, width, after)

    def chunk(before, at):
        def group(ln):
            g, taps = g_of(before(ln), x_ref[0, at, ln].astype(_F32),
                           dy_ref[0, at, ln].astype(_F32), ln)
            pair = jnp.concatenate([g, g_ref[:, ln]], 0)
            g_ref[:, ln] = g
            dx = g * w_ref[k - 1:k, ln]
            for i in range(k - 1):  # tap i took x_{t-(k-1)+i} to y_t
                dx = dx + pltpu.roll(pair, 2 * _ROWS - (k - 1 - i),
                                     0)[:_ROWS] * w_ref[i:i + 1, ln]
            dx_ref[0, at, ln] = dx.astype(dx_ref.dtype)
            for i in range(k):
                dw_ref[i, :, ln] += _fold(taps[i] * g)
        _over_lanes(lanes, width, group)

    def step(j, _):         # from the tile's last chunk to its second
        r = n - 1 - j
        chunk(lambda ln: x_ref[0, _chunk_at(r - 1), ln].astype(_F32),
              _chunk_at(r))
        return 0

    lax.fori_loop(0, n - 1, step, 0)
    chunk(lambda ln: jnp.where(first, 0.0, prev_ref[0, :, ln].astype(_F32)),
          pl.ds(0, _ROWS))


def _conv_specs(x):
    """(lanes and rows to a tile, the grid, the tile's spec, the 16 rows
    before it, the 16 after)."""
    B, S, C = x.shape
    lanes = _tile(C, 128, _TILE_LANES)
    rows = _tile(S, _ROWS, max(_ROWS, _TILE_ELEMENTS // lanes))
    per, last = rows // _ROWS, S // _ROWS - 1
    return (lanes, rows, (C // lanes, B, S // rows),
            pl.BlockSpec((1, rows, lanes), lambda c, b, s: (b, s, c)),
            pl.BlockSpec((1, _ROWS, lanes), lambda c, b, s: (
                b, jnp.maximum(s * per - 1, 0), c)),
            pl.BlockSpec((1, _ROWS, lanes), lambda c, b, s: (
                b, jnp.minimum((s + 1) * per, last), c)))


# Jitted for what the flash and KDA wrappers are jitted for: a model's
# sites of one signature (twelve convolutions, four norms) share ONE
# trace each way. The tiles' constants are read when that trace is made.
@jax.jit
def conv_fwd(x, w):
    """x [B,S,C], w [C,K] -> ``silu`` of the causal depthwise
    convolution, in x's type."""
    k = w.shape[1]
    lanes, rows, grid, tile, before, _ = _conv_specs(x)
    return pl.pallas_call(
        functools.partial(_conv_fwd_kernel, k=k, rows=rows),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((k, lanes), lambda c, b, s: (0, c)),
                  before, tile],
        out_specs=tile,
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=interpret_mode(),
        name="short_conv_fwd",
    )(w.astype(_F32).T, x, x)


@jax.jit
def conv_bwd(x, w, dy):
    """-> (dx in x's type, dw [C,K] in w's)."""
    k = w.shape[1]
    lanes, rows, grid, tile, before, after = _conv_specs(x)
    dx, dw = pl.pallas_call(
        functools.partial(_conv_bwd_kernel, k=k, rows=rows),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((k, 8, x.shape[2]), _F32)],
        grid=grid,
        in_specs=[pl.BlockSpec((k, lanes), lambda c, b, s: (0, c)),
                  before, tile, after, tile, after],
        out_specs=[tile, pl.BlockSpec((k, 8, lanes),
                                      lambda c, b, s: (0, 0, c))],
        scratch_shapes=[pltpu.VMEM((_ROWS, lanes), _F32)],
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret_mode(),
        name="short_conv_bwd",
    )(w.astype(_F32).T, x, x, x, dy, dy)
    return dx, jnp.sum(dw, 1).T.astype(w.dtype)


# ---------------------------------------------------------------- norm

def norm_takes(x, scale):
    """Whole heads of 128 or 256 lanes (a group's float32 values stay a
    few vector registers), whole sublane tiles of rows."""
    d, rows = scale.shape[0], x.size // x.shape[-1]
    return d in (128, 256) and x.shape[-1] % d == 0 and rows % _ROWS == 0


def _norm_fwd_kernel(sc_ref, x_ref, gate_ref, o_ref, *, d, eps, rows):
    per = _tile(rows, _ROWS, _NORM_ROWS)

    def chunk(r, _):
        at = _chunk_at(r, per)

        def head(ln):
            x = x_ref[at, ln].astype(_F32)
            inv = lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) / d + eps)
            o_ref[at, ln] = (
                x * inv * sc_ref[:, ln]
                * _sigmoid(gate_ref[at, ln].astype(_F32))).astype(o_ref.dtype)
        _over_lanes(x_ref.shape[1], d, head)
        return 0

    lax.fori_loop(0, rows // per, chunk, 0)


def _norm_bwd_kernel(sc_ref, x_ref, gate_ref, dy_ref, dx_ref, dgate_ref,
                     dsc_ref, *, d, eps, rows):
    @pl.when(pl.program_id(1) == 0)
    def _():
        dsc_ref[...] = jnp.zeros_like(dsc_ref)

    per = _tile(rows, _ROWS, _NORM_ROWS)

    def chunk(r, _):
        at = _chunk_at(r, per)

        def head(ln):
            x = x_ref[at, ln].astype(_F32)
            dy = dy_ref[at, ln].astype(_F32)
            sig = _sigmoid(gate_ref[at, ln].astype(_F32))
            sc = sc_ref[:, ln]
            inv = lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) / d + eps)
            n = x * inv
            dn = dy * sig * sc
            back = jnp.sum(dn * n, -1, keepdims=True) / d
            dx_ref[at, ln] = (inv * (dn - n * back)).astype(dx_ref.dtype)
            dgate_ref[at, ln] = (dy * n * sc * (sig * (1.0 - sig))).astype(
                dgate_ref.dtype)
            dsc_ref[:, ln] += _fold(dy * sig * n)
        _over_lanes(x_ref.shape[1], d, head)
        return 0

    lax.fori_loop(0, rows // per, chunk, 0)


def _norm_call(kernel, name, x, scale, epsilon, n_in, out_types, sums):
    """``kernel`` over the arrays as [rows, W]: lane tiles of whole
    heads (``parallel``), then row tiles. It gives arrays of x's shape
    in ``out_types`` and, with ``sums`` (the backward pass, which adds
    to them over the row tiles), [8, W] float32."""
    d, width = scale.shape[0], x.shape[-1]
    total = x.size // width
    lanes = _tile(width, d, max(d, _TILE_LANES))
    rows = _tile(total, _ROWS, max(_ROWS, _TILE_ELEMENTS // lanes))
    tile = pl.BlockSpec((rows, lanes), lambda c, r: (r, c))
    per_lane = lambda n: pl.BlockSpec((n, lanes), lambda c, r: (0, c))  # noqa: E731
    shapes = [jax.ShapeDtypeStruct((total, width), t) for t in out_types]
    specs = [tile] * len(out_types)
    if sums:
        shapes.append(jax.ShapeDtypeStruct((8, width), _F32))
        specs.append(per_lane(8))
    return pl.pallas_call(
        functools.partial(kernel, d=d, eps=epsilon, rows=rows),
        out_shape=shapes, grid=(width // lanes, total // rows),
        in_specs=[per_lane(1)] + [tile] * n_in, out_specs=specs,
        compiler_params=_params("parallel",
                                "arbitrary" if sums else "parallel"),
        interpret=interpret_mode(), name=name)


def _flat(x, scale):
    """(x as [rows, W], the weight over all W lanes as [1, W])."""
    width = x.shape[-1]
    return ((-1, width),
            jnp.tile(scale.astype(_F32), width // scale.shape[0])[None])


@functools.partial(jax.jit, static_argnums=3)
def norm_fwd(x, gate, scale, epsilon):
    """RMSNorm a head of ``len(scale)`` lanes, times the weight, times
    ``sigmoid(gate)``, in x's type."""
    flat, sc = _flat(x, scale)
    y, = _norm_call(_norm_fwd_kernel, "gated_rms_norm_fwd", x, scale,
                    epsilon, 2, [x.dtype], False)(
        sc, x.reshape(flat), gate.reshape(flat))
    return y.reshape(x.shape)


@functools.partial(jax.jit, static_argnums=3)
def norm_bwd(x, gate, scale, epsilon, dy):
    """-> (dx, dgate, dscale) in the inputs' types."""
    flat, sc = _flat(x, scale)
    dx, dgate, sums = _norm_call(
        _norm_bwd_kernel, "gated_rms_norm_bwd", x, scale, epsilon, 3,
        [x.dtype, gate.dtype], True)(
        sc, x.reshape(flat), gate.reshape(flat), dy.reshape(flat))
    dscale = jnp.sum(sums.reshape(8, -1, scale.shape[0]), (0, 1))
    return (dx.reshape(x.shape), dgate.reshape(gate.shape),
            dscale.astype(scale.dtype))
