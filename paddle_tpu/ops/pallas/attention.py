"""Flash attention: fused scaled-dot-product attention pallas kernels.

The analog of the reference's fused attention path — the 2019 reference
composes attention op-by-op (matmul/softmax/matmul through separate
kernels, e.g. the benchmark transformer), which round-trips the
[B,H,Sq,Sk] score matrix through HBM twice in the forward and again in
the backward. On TPU this kernel family never materializes the score
matrix in HBM in either direction:

- **Forward**: k-blocked online softmax. Running max ``m``, normalizer
  ``l`` and the output accumulator live in VMEM scratch; the softmax
  statistics ``lse = m + log(l)`` are saved for the backward.
- **Backward**: two pallas kernels with per-block recompute —
  ``dq`` (scanning k-blocks) and ``dk/dv`` (scanning q-blocks). Each
  block recomputes ``p = exp(s - lse)`` from q/k and the saved
  statistics; only O(seq * head_dim) residuals (out, lse) ever hit HBM.
- **Dropout** runs in-kernel with the TPU PRNG
  (``pltpu.prng_seed``/``prng_random_bits``), seeded per
  (grid cell, q-block, k-block) so the backward regenerates the exact
  forward mask without storing it.
- **Causal** masking skips fully-masked k-blocks (roughly halves the
  decoder self-attention work).
- **Short-sequence batching** (blocked kernels): each grid cell
  processes ``G`` (batch, head) rows at once (batched dot_generals
  over the leading dim). G divides H, so a cell never straddles a
  batch row and per-BATCH bias blocks stay well-defined.
- **Single-k-block specialization** (``_1k_applicable``: Sk<=512,
  and Sq at most 256 or a multiple of 256, natural tiling): when the
  whole key range fits one block, the online-softmax machinery is
  dropped (plain softmax in registers, no m/l scratch, no
  lane-replicated statistics), and the backward is ONE kernel
  producing dq/dk/dv from a single exp recompute with lse and delta
  derived in-kernel — the only HBM residual is the forward output.
  The pair reads q, k, v and the output's gradient, and writes o, dq,
  dk, dv, **in the layout the projections produce and consume,
  ``[B, S, H*Dh]``**: a grid cell is a batch row's ``G`` heads
  (``G*Dh`` lanes: a multiple of 128, or the whole width), and a
  loop over the cell's heads picks each out of the lanes
  (``_for_lane_groups``). No ``[B,H,S,Dh]`` array, and none of the eight
  materialised transposes a site that built and undid it (16.8 ms of
  transformer-base's 164 ms step, 16.3 of BERT's 178: ledger, PR 28),
  exists in a training step of either model. Queries are blocked
  ``_1K_BLK_Q`` rows to a grid step (the last grid axis; k and v stay
  resident across it), so one head's ``[blk_q, Sk]`` score tile is
  what VMEM holds beside the blocks; the backward sums dk/dv over the
  q-blocks in float32 scratch. This is what ``FLAGS_sdpa_auto_flash``
  dispatches in training: transformer-base (S=256, 18 sites, one
  q-block, G=8) and BERT-base at S=128 and S=512 (12 sites, two
  q-blocks, G=6). The argument for it in-model: XLA's fused chain
  pays RNG mask materialization + probs HBM round-trips at every
  attention site (BERT S=512: 136 ms of a 253 ms step, ledger PR 26).
  Everything else — Sk > 512 (S=1024 self-attention), a ragged Sq —
  takes the blocked kernels below under ``FLAGS_op_library=pallas``
  and XLA's chain by default.
- **Two entry layouts, one pair.** The op takes rank 4
  ``[B,H,S,Dh]`` or rank 3 ``[B,S,H*Dh]`` with ``num_heads``; the
  rank of Q is all the lowering looks at. The 1k pair has the rank-3
  layout only, the blocked kernels and the sp schedules the rank-4
  one only: a caller in the other layout is adapted by a transpose in
  the lowering (``sdpa_pallas``), which is what a model's own head
  split costs. ``sdpa_lowering.flash_1k_transposed`` counts the sites
  that reached the pair that way.

``Bias`` is an additive attention mask (0 / -1e9, built from data by the
models) and is registered non-differentiable: the base lowering and the
pallas kernel therefore agree that no dbias flows. A *trainable*
attention bias should be added with a separate elementwise_add before a
bias-free sdpa call.

Reference precedent for the fused-kernel + refer-impl pairing:
/root/reference/paddle/fluid/operators/jit/README.en.md (best-impl-wins
kernel dispatch), operators/fused/.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..registry import register, register_variant
from .common import blk, count_lowering, interpret_mode

_NEG_INF = -1e30

# batched dot_general dimension numbers over leading G dim
_QK = (((2,), (2,)), ((0,), (0,)))     # [G,q,d] x [G,k,d] -> [G,q,k]
_PV = (((2,), (1,)), ((0,), (0,)))     # [G,q,k] x [G,k,d] -> [G,q,d]
_TT = (((1,), (1,)), ((0,), (0,)))     # [G,q,k] x [G,q,d] -> [G,k,d]


def _causal_mask(s, j, kk, blk_q, blk_k, window=0):
    """Row i reads key c only where c <= i and, with a ``window``,
    i - c < window."""
    rows = j * blk_q + lax.broadcasted_iota(jnp.int32, s.shape,
                                            s.ndim - 2)
    cols = kk * blk_k + lax.broadcasted_iota(jnp.int32, s.shape,
                                             s.ndim - 1)
    keep = rows >= cols
    if window:
        keep = jnp.logical_and(keep, rows - cols < window)
    return jnp.where(keep, s, _NEG_INF)


def _seed_block(seed_ref, i, j, kk, n_q, n_k):
    """Seed the TPU's generator for one (cell, q-block, k-block): the
    coordinates are folded into one scalar seed (single-arg prng_seed —
    the multi-arg form doesn't lower on this Mosaic version) with a
    Knuth-style odd multiplier so nearby blocks decorrelate.
    ``seed_ref`` = [step seed, this call's first cell]: a shard of a
    mesh numbers its cells where a single device would (_seed_smem)."""
    flat = ((seed_ref[1] + i) * n_q + j) * n_k + kk
    pltpu.prng_seed(seed_ref[0] + flat * jnp.int32(-1640531527))


def _keep_bits(shape, rate):
    """The next ``shape`` of keep / drop bits of the seeded generator:
    the backward kernels seed alike and draw in the same order, so they
    regenerate the forward's mask without storing it."""
    bits = pltpu.prng_random_bits(shape)
    u = lax.bitcast_convert_type(bits, jnp.uint32)
    thresh = jnp.uint32(min(int(rate * (1 << 32)), (1 << 32) - 1))
    return u >= thresh


def _dropout_keep(seed_ref, i, j, kk, n_q, n_k, shape, rate):
    """Deterministic per-block dropout mask of the blocked kernels."""
    _seed_block(seed_ref, i, j, kk, n_q, n_k)
    return _keep_bits(shape, rate)


@functools.lru_cache(maxsize=None)
def _softmax_save_lowp(dtype_name):
    """Softmax computed in f32 that SAVES ONLY the low-precision
    probabilities for its backward (flash-attention discipline).
    jax.nn.softmax's own vjp residual is the f32 output — at
    [B,H,S,S] x 18 attention sites that one choice added ~4 GB of
    HLO temps at batch 128 (observed in the round-4 OOM dump) and
    doubled the probs read/write traffic; the bf16-rounded residual
    changes the gradient by <=1 ulp of bf16, the same rounding every
    flash kernel accepts."""
    out_dtype = jnp.dtype(dtype_name)

    @jax.custom_vjp
    def f(s):
        return jax.nn.softmax(s, axis=-1).astype(out_dtype)

    def fwd(s):
        w = jax.nn.softmax(s, axis=-1).astype(out_dtype)
        return w, w

    def bwd(w, g):
        w32 = w.astype(jnp.float32)
        g32 = g.astype(jnp.float32)
        inner = jnp.sum(g32 * w32, axis=-1, keepdims=True)
        return ((g32 - inner) * w32,)

    f.defvjp(fwd, bwd)
    return f


def _geometry(q, k, num_heads):
    """(B, H, Hkv, Sq, Sk, Dh) of either layout the op takes: rank 4,
    heads leading, q [B,H,Sq,Dh] and k [B,Hkv,Sk,Dh]; or rank 3, as
    the projections produce it, q [B,Sq,H*Dh] and k [B,Sk,Hkv*Dh] with
    ``num_heads`` query heads (a head's Dh lanes are contiguous)."""
    if q.ndim == 4:
        B, H, Sq, Dh = q.shape
        return B, H, k.shape[1], Sq, k.shape[2], Dh
    B, Sq, width = q.shape
    if num_heads <= 0 or width % num_heads:
        raise ValueError(
            "scaled_dot_product_attention: rank-3 Q of width %d needs "
            "num_heads dividing it, got %d" % (width, num_heads))
    Dh = width // num_heads
    return B, num_heads, k.shape[2] // Dh, Sq, k.shape[1], Dh


def _split_heads(x, Dh):
    """[B,S,H*Dh] -> [B,H,S,Dh]: a materialised transpose."""
    B, S, width = x.shape
    return x.reshape(B, S, width // Dh, Dh).transpose(0, 2, 1, 3)


def _merge_heads(x):
    """[B,H,S,Dh] -> [B,S,H*Dh]."""
    B, H, S, Dh = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, S, H * Dh)


def _sdpa_reference(q, k, v, bias, *, scale, dropout_rate=0.0,
                    causal=False, window=0, num_heads=0, rng=None):
    """Pure-jnp composite (the jit/refer/ analog): q [B,H,S,Dh], k and
    v [B,Hkv,S,Dh] with Hkv dividing H (q head i reads kv head
    i // (H // Hkv)), or all three rank 3 (_geometry: a free reshape
    to [B,S,H,Dh], the einsums pick the heads and XLA the layouts);
    bias additive, broadcastable to [B,1_or_H,Sq,Sk]; with a
    ``window`` (causal only) row i reads keys i-window+1..i.

    Precision follows standard TPU practice (and the reference's f32
    softmax accumulate): scores and softmax in float32 — the MXU
    accumulates f32 for free and bf16 exp/sums over the key axis lose
    real mantissa — then the probabilities drop back to the input
    dtype (saving only the low-precision copy for the backward) for
    the dropout mask and the PV matmul, so the [B,H,S,S] traffic
    rides at half width under AMP."""
    B, H, hkv, sq, sk, dh = _geometry(q, k, num_heads)
    heads_last = q.ndim == 3
    group = H // hkv
    # q as [.., hkv, group, ..] where kv heads are shared: the group
    # folds into the batch of heads, the kv heads are never repeated
    if heads_last:
        q = q.reshape((B, sq, H, dh) if group == 1
                      else (B, sq, hkv, group, dh))
        k = k.reshape(B, sk, hkv, dh)
        v = v.reshape(B, sk, hkv, dh)
        qs, ks = ("bqhd", "bkhd") if group == 1 else ("bqhgd", "bkhd")
    else:
        if group > 1:
            q = q.reshape(B, hkv, group, sq, dh)
        qs, ks = ("bhqd", "bhkd") if group == 1 else ("bhgqd", "bhkd")
    ps = "bhqk" if group == 1 else "bhgqk"
    s = jnp.einsum("%s,%s->%s" % (qs, ks, ps), q, k,
                   preferred_element_type=jnp.float32
                   ).reshape(B, H, sq, sk) * scale
    if bias is not None:
        s = s + lax.stop_gradient(bias).astype(jnp.float32)
    if causal:
        rows = lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        cols = lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        keep = rows >= cols
        if window:
            keep = jnp.logical_and(keep, rows - cols < window)
        s = jnp.where(keep, s, _NEG_INF)
    w = _softmax_save_lowp(jnp.dtype(v.dtype).name)(s)
    if dropout_rate > 0.0:
        from ..nn_ops import _keep_mask
        keep = _keep_mask(rng, dropout_rate, w.shape)
        w = jnp.where(keep, w / (1.0 - dropout_rate),
                      jnp.zeros((), v.dtype))
    if group > 1:
        w = w.reshape(B, hkv, group, sq, sk)
    out = jnp.einsum("%s,%s->%s" % (ps, ks, qs), w, v,
                     preferred_element_type=jnp.float32)
    out_dtype = v.dtype if group > 1 else q.dtype
    return out.reshape((B, sq, H * dh) if heads_last
                       else (B, H, sq, v.shape[-1])).astype(out_dtype)


@register("scaled_dot_product_attention", ["Q", "K", "V", "Bias"],
          ["Out"], nondiff=("Bias",), needs_rng=True)
def scaled_dot_product_attention(q, k, v, bias, *, scale=1.0,
                                 dropout_rate=0.0, causal=False,
                                 is_test=False, window=0, num_heads=0,
                                 rng=None):
    """Base lowering: XLA fuses the chain — except inside the flash
    kernels' envelopes, where the base dispatches to them
    (FLAGS_sdpa_auto_flash, the jit/README.en.md best-impl-wins pool
    applied at run time), on the TPU with low-precision operands:
    with dropout active and single-k-block shapes the 1k pair; with no
    dropout and keys past that envelope the blocked kernels (at
    S=8192 XLA's chain would hold a [B,H,S,S] float32 score tensor);
    everything else keeps the XLA chain. Inside an envelope the kernel
    is THE lowering — a Mosaic compile error propagates, nothing
    retries with the reference.

    Two entry layouts, told apart by Q's rank (_geometry): rank 4 with
    heads leading, or rank 3 ``[B, S, H*Dh]`` with ``num_heads``, which
    is what the projections produce and the output projection reads;
    ``Out`` has Q's shape. The 1k pair reads rank 3 in place; whatever
    wants the other layout than the caller's (the pair for a rank-4
    caller; the blocked kernels and the sp route for a rank-3 one)
    gets it by a transpose here, which is what a model's own head
    split costs.

    K and V may carry fewer heads than Q (grouped queries: q head i
    reads kv head i // (H // Hkv)); ``window`` > 0 (causal only) lets
    row i read keys i-window+1..i."""
    rate = 0.0 if is_test else float(dropout_rate)
    window = int(window)
    num_heads = int(num_heads)
    if window and not causal:
        raise ValueError("scaled_dot_product_attention: a window is "
                         "defined for causal attention only")
    _, H, hkv, sq, sk, dh = _geometry(q, k, num_heads)
    if H % hkv:
        raise ValueError("scaled_dot_product_attention: %d query heads "
                         "over %d key heads" % (H, hkv))
    from ...core.flags import FLAGS
    plain = not window and hkv == H
    if FLAGS.sp_attention and rate == 0.0 and plain:
        # model-parallel production path: under a mesh with an sp axis
        # (CompiledProgram.with_data_parallel(axes={"dp":d,"sp":s})
        # installs it as the ambient mesh for the whole trace) the one
        # attention op the models build lowers to the zigzag ring /
        # Ulysses schedule — activations stay sequence-sharded through
        # the S^2 core instead of replicating. Returns None when no sp
        # axis is in scope or the geometry doesn't admit a schedule,
        # in which case the replicated lowerings below stay in charge.
        from ...parallel.ulysses import sequence_parallel_attention
        heads_last = q.ndim == 3
        q4, k4, v4 = ((_split_heads(x, dh) for x in (q, k, v))
                      if heads_last else (q, k, v))
        routed = sequence_parallel_attention(q4, k4, v4, bias=bias,
                                             scale=scale,
                                             causal=causal)
        if routed is not None:
            _count_lowering("sp")
            return _merge_heads(routed) if heads_last else routed
    if (FLAGS.sdpa_auto_flash and not interpret_mode()
            and jnp.dtype(q.dtype).itemsize <= 2):
        if (rate > 0.0 and rng is not None and plain
                and _1k_applicable(sq, sk)) \
                or (rate == 0.0 and _blocked_applicable(sq, sk)):
            return sdpa_pallas(q, k, v, bias, scale=scale,
                               dropout_rate=dropout_rate, causal=causal,
                               is_test=is_test, window=window,
                               num_heads=num_heads, rng=rng)
    _count_lowering("xla")
    return _sdpa_reference(q, k, v, bias, scale=scale,
                           dropout_rate=rate, causal=causal,
                           window=window, num_heads=num_heads, rng=rng)


def _blocked_applicable(Sq, Sk):
    """The envelope in which a site with no dropout takes the blocked
    kernels by itself: keys past the single-k-block envelope, whole
    256 x 512 tiles."""
    return Sk > 512 and Sk % _BLK_K_TARGET == 0 \
        and Sq % _BLK_Q_TARGET == 0


def _count_lowering(path, by=1.0):
    """``sdpa_lowering.<path>`` with path ``flash_1k``,
    ``flash_blocked``, ``xla`` or ``sp`` (common.count_lowering), and
    ``flash_1k_transposed`` beside ``flash_1k`` where the pair was
    reached from rank 4, behind the lowering's transposes. A
    differentiated site is lowered once for the forward and once more
    under ``jax.vjp``."""
    count_lowering("sdpa_lowering." + path, by)


# ---------------------------------------------------------------------------
# single-k-block specialization (the flagship S=256 and BERT's S=128
# and S=512 shapes). When the whole key range fits one block the
# online-softmax machinery is pure overhead: no m/l scratch, no alpha
# rescales, no lane-replicated statistics round-tripping through HBM.
# The backward is ONE kernel computing dq/dk/dv together from a single
# exp recompute (the blocked path needs two kernels = two recomputes),
# with lse and delta = rowsum(dO*O) derived in-kernel so the only HBM
# residual is the forward output itself.
#
# Layout: q / o / do / dq are [B, Sq, H*Dh] and k / v / dk / dv
# [B, Sk, H*Dh], as the projections produce and consume them: no
# [B,H,S,Dh] array exists around the pair. Grid (batch rows, cells of
# G heads, q-blocks); a cell's blocks are (1, blk_q, G*Dh) and
# (1, Sk, G*Dh), lane-dense (G*Dh a multiple of 128, or the whole
# width), and k and v keep their block index across the q-blocks of a
# cell, so they are fetched once a cell. Inside, a loop over
# the cell's heads picks each head's Dh lanes out of the block
# (_for_lane_groups).
# ---------------------------------------------------------------------------

# one head's 2-D tiles
_NT = (((1,), (1,)), ((), ()))         # [q,d] x [k,d] -> [q,k]
_NN = (((1,), (0,)), ((), ()))         # [q,k] x [k,d] -> [q,d]
_TN = (((0,), (0,)), ((), ()))         # [q,k] x [q,d] -> [k,d]


def _for_lane_groups(G, Dh, body):
    """``body(lanes of the block, first head, heads)`` for the cell's G
    heads in the groups that are loaded and stored together, as many
    as fill a 128-lane tile (two at Dh=64), so that every load and
    store is lane-aligned. A head is then picked by noughts, not by a
    slice: the other heads' lanes are zeroed in one operand of a
    contraction over the group's lanes, and a product that writes them
    is kept in the head's own lanes only (_own_lanes). On a 128 x 128
    matrix unit a contraction or an output of 64 fills half the array
    already, so this costs no extra pass; a static 64-lane slice cost
    a relayout of every operand (one transformer-base site, forward +
    backward: 1.69 ms sliced, 1.06 ms this way; my chip run, PR 29,
    call 1).

    Whole tiles are walked by a ``fori_loop`` over a dynamic, aligned
    lane offset, so the Mosaic body holds one group's code and not
    G / 2 copies of it: unrolled over the transformer's eight heads
    the pair added 2.3 s of lowering and 2.1 s of loading to every
    start of its step (my chip run, PR 29, call 3). Lanes that do not
    tile (a test's 96-lane width) are walked statically."""
    per = max(1, 128 // Dh)
    width = per * Dh
    if width % 128 == 0 and G % per == 0 and G > per:
        def group(i, carry):
            body(pl.ds(pl.multiple_of(i * width, width), width),
                 i * per, per)
            return carry

        lax.fori_loop(0, G // per, group, 0)
        return
    for g0 in range(0, G, per):
        body(slice(g0 * Dh, min(g0 + per, G) * Dh), g0, min(per, G - g0))


def _own_lanes(x, t, n, Dh, other=None):
    """``x`` [rows, n*Dh] in head t's lanes of its group of n, and
    ``other`` (noughts by default) in the rest."""
    if n == 1:
        return x
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    own = jnp.logical_and(lane >= t * Dh, lane < (t + 1) * Dh)
    return jnp.where(own, x, jnp.zeros((), x.dtype)
                     if other is None else other)


def _head_scores(q, k, b_ref, g, j, *, scale, causal):
    """One head's [blk_q, Sk] float32 scores; head ``g`` of the cell
    reads its own bias slab where the bias is per head."""
    s = lax.dot_general(q, k, _NT,
                        preferred_element_type=jnp.float32) * scale
    if b_ref is not None:
        s = s + b_ref[g if b_ref.shape[0] > 1 else 0, 0].astype(
            jnp.float32)
    if causal:
        s = _causal_mask(s, j, 0, s.shape[0], s.shape[1])
    return s


def _seed_cell_1k(seed_ref, n_q):
    """One seed a (cell, q-block); the cell's heads then draw their
    [blk_q, Sk] masks one after another, forward and backward in the
    same order. Cells are numbered batch row by batch row."""
    cell = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)
    _seed_block(seed_ref, cell, pl.program_id(2), 0, n_q, 1)


def _fwd_kernel_1k(seed_ref, q_ref, k_ref, v_ref, b_ref, o_ref, *, Dh,
                   scale, rate, causal, n_q):
    j = pl.program_id(2)
    if rate > 0.0:
        _seed_cell_1k(seed_ref, n_q)

    def group(lanes, g0, n):
        q, k, v = q_ref[0, :, lanes], k_ref[0, :, lanes], \
            v_ref[0, :, lanes]
        out = None
        for t in range(n):
            s = _head_scores(_own_lanes(q, t, n, Dh), k, b_ref, g0 + t,
                             j, scale=scale, causal=causal)
            m = jnp.max(s, -1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, -1, keepdims=True)
            if rate > 0.0:
                p = jnp.where(_keep_bits(p.shape, rate),
                              p * (1.0 / (1.0 - rate)), 0.0)
            pv = lax.dot_general(p.astype(v.dtype), v, _NN,
                                 preferred_element_type=jnp.float32)
            # reciprocal-multiply: a [Sq,1]-broadcast divide on the
            # [Sq,Dh] tile costs ~4x a multiply on the VPU
            rl = 1.0 / jnp.where(l == 0.0, 1.0, l)
            out = _own_lanes(pv * rl, t, n, Dh, out)
        o_ref[0, :, lanes] = out.astype(o_ref.dtype)

    _for_lane_groups(q_ref.shape[2] // Dh, Dh, group)


def _bwd_kernel_1k(seed_ref, q_ref, k_ref, v_ref, b_ref, do_ref, o_ref,
                   dq_ref, dk_ref, dv_ref, *acc, Dh, scale, rate,
                   causal, n_q):
    j = pl.program_id(2)
    if rate > 0.0:
        _seed_cell_1k(seed_ref, n_q)

    def group(lanes, g0, n):
        k, v = k_ref[0, :, lanes], v_ref[0, :, lanes]
        q_all, do_all = q_ref[0, :, lanes], do_ref[0, :, lanes]
        o = o_ref[0, :, lanes].astype(jnp.float32)
        dq = dk = dv = None
        for t in range(n):
            # with the other heads' lanes of q and do at nought, the
            # contractions over the group's lanes are this head's, and
            # dk and dv (which q and do write) are nought outside them
            q = _own_lanes(q_all, t, n, Dh)
            do = _own_lanes(do_all, t, n, Dh)
            s = _head_scores(q, k, b_ref, g0 + t, j, scale=scale,
                             causal=causal)
            m = jnp.max(s, -1, keepdims=True)
            e = jnp.exp(s - m)
            l = jnp.sum(e, -1, keepdims=True)
            p = e * (1.0 / jnp.where(l == 0.0, 1.0, l))  # [blk_q, Sk]
            delta = jnp.sum(do.astype(jnp.float32) * o, -1,
                            keepdims=True)               # [blk_q, 1]
            dp = lax.dot_general(do, v, _NT,
                                 preferred_element_type=jnp.float32)
            if rate > 0.0:
                keep = _keep_bits(p.shape, rate)
                inv = 1.0 / (1.0 - rate)
                pd = jnp.where(keep, p * inv, 0.0)
                dp = jnp.where(keep, dp * inv, 0.0)
            else:
                pd = p
            dv_t = lax.dot_general(pd.astype(do.dtype), do, _TN,
                                   preferred_element_type=jnp.float32)
            ds = (p * (dp - delta) * scale).astype(q.dtype)
            dq = _own_lanes(lax.dot_general(
                ds, k, _NN, preferred_element_type=jnp.float32),
                t, n, Dh, dq)
            dk_t = lax.dot_general(ds, q, _TN,
                                   preferred_element_type=jnp.float32)
            dk = dk_t if dk is None else dk + dk_t
            dv = dv_t if dv is None else dv + dv_t
        dq_ref[0, :, lanes] = dq.astype(dq_ref.dtype)
        if n_q == 1:
            dk_ref[0, :, lanes] = dk.astype(dk_ref.dtype)
            dv_ref[0, :, lanes] = dv.astype(dv_ref.dtype)
            return
        # dk, dv are sums over the cell's q-blocks: float32 scratch
        # over the "arbitrary" j axis, written out at the last block
        dk_acc, dv_acc = acc

        @pl.when(j == 0)
        def _first():
            dk_acc[:, lanes] = dk
            dv_acc[:, lanes] = dv

        @pl.when(j > 0)
        def _rest():
            dk_acc[:, lanes] += dk
            dv_acc[:, lanes] += dv

    _for_lane_groups(q_ref.shape[2] // Dh, Dh, group)
    if n_q > 1:
        @pl.when(j == n_q - 1)
        def _finish():
            dk_ref[0] = acc[0][...].astype(dk_ref.dtype)
            dv_ref[0] = acc[1][...].astype(dv_ref.dtype)


# Query rows to a grid step of the single-k-block kernels: the whole
# Sq up to 256 (one q-block: the S=256 flagship and BERT's S=128, as
# before the q axis existed), 256-row blocks above it (BERT's S=512:
# two). 256 x 512 is the score tile PR 21 compiled on the v5e.
_1K_BLK_Q = 256


def _1k_blk_q(Sq):
    return min(Sq, _1K_BLK_Q)


def _1k_applicable(Sq, Sk):
    """The envelope FLAGS_sdpa_auto_flash dispatches, and the switch
    between the single-k-block and the blocked kernels: the whole key
    range in one block, whole q-blocks, natural TPU tiling (no
    padding). Sk > 512 (S=1024 self-attention) and a ragged Sq
    (384, 520) stay outside."""
    if Sk > 512 or Sk % 128:
        return False
    return Sq % 8 == 0 if Sq <= _1K_BLK_Q else Sq % _1K_BLK_Q == 0


# VMEM model for the single-k-block kernels. A cell of G heads holds:
#   - streamed blocks, double-buffered: q/do/o/dq rows of blk_q and
#     k/v/dk/dv rows of Sk, G*Dh lanes wide (lane-dense: nothing is
#     padded where G*Dh is a multiple of 128);
#   - ONE head's [blk_q,Sk] score temporaries at a time (the loop over
#     the cell's heads is unrolled, a head's temporaries die with its
#     iteration): 24 bytes an element, the backward's six
#     source-level arrays (s, e/p, dp, the generator's bits, pd, ds)
#     with no reuse assumed; twice that for float32 operands, whose
#     exact products split every operand into bf16 parts (BERT's
#     S=512 at G=6 in float32: 13.5 MB by the single count, 17 MB by
#     the compiler for the described v5e; PR 29);
#   - the bias block(s), double-buffered, and the s + b f32 addend;
#   - with more than one q-block, the backward's two [Sk, G*Dh] f32
#     dk/dv accumulators (scratch: resident once).
# Budget 15 MB of the 16 MB v5e scoped limit. tests/test_pallas_vmem.py
# replays this model at every _1k_applicable corner and pins the two
# benchmark geometries' G; tests/test_tpu_aot_kernels.py has the
# chip's compiler accept them.
_1K_TEMP_BYTES = 24
_1K_VMEM_BUDGET = 15 << 20
_1K_MAX_G = 8

# Blocked-path tile targets, env-tunable for on-chip sweeps
# (tools/blocked_sweep.py): PALLAS_BLK_Q / PALLAS_BLK_K. The blocked
# path runs only under FLAGS_op_library=pallas and only outside
# _1k_applicable (Sk > 512, or a ragged Sq): no default program
# dispatches it, so any change must be chip-measured in-model at
# S>=1024 first.
_BLK_Q_TARGET = int(os.environ.get("PALLAS_BLK_Q", "256"))
_BLK_K_TARGET = int(os.environ.get("PALLAS_BLK_K", "512"))


def _1k_cell_bytes(G, itemsize, Sq, Sk, Dh, n_sq_ops, n_sk_ops,
                   bias_itemsize=0, per_head=False, accumulates=False):
    """Modeled VMEM of one grid cell of G heads; ``bias_itemsize`` 0
    without a bias, whose slabs are one a cell or, ``per_head``, G."""
    lanes = -(-G * Dh // 128) * 128
    blk_q = _1k_blk_q(Sq)
    total = (n_sq_ops * blk_q + n_sk_ops * Sk) * lanes * itemsize * 2
    total += blk_q * Sk * _1K_TEMP_BYTES * (2 if itemsize > 2 else 1)
    if bias_itemsize:
        slabs = G if per_head else 1
        total += blk_q * Sk * (slabs * bias_itemsize * 2 + 4)
    if accumulates and Sq > blk_q:
        total += 2 * Sk * lanes * 4
    return total


def _1k_G(H, Dh, *model, **kw):
    """Heads to a cell: the most (up to _1K_MAX_G) whose
    ``_1k_cell_bytes(G, *model, **kw)`` fit the budget, of the divisors
    of H whose lanes tile: G*Dh a multiple of 128, or the whole width.
    Nothing fitting, the fewest that tile."""
    tiling = [g for g in range(H, 0, -1)
              if H % g == 0 and (g == H or g * Dh % 128 == 0)]
    capped = [g for g in tiling if g <= _1K_MAX_G] or tiling[-1:]
    for g in capped:
        if _1k_cell_bytes(g, *model, **kw) <= _1K_VMEM_BUDGET:
            return g
    return capped[-1]


def _1k_bwd_G(H, itemsize, Sq, Sk, Dh, bias_itemsize=0, per_head=False):
    """The backward's heads per grid cell, capped by the VMEM model
    (streams: q,do,o,dq + k,v,dk,dv; the dk/dv accumulators)."""
    return _1k_G(H, Dh, itemsize, Sq, Sk, Dh, 4, 4, bias_itemsize,
                 per_head, accumulates=True)


def _1k_fwd_G(H, itemsize, rate, Sq, Sk, Dh, bias_itemsize=0,
              per_head=False):
    """The forward's heads per grid cell. With dropout it MUST equal
    the backward's G (a cell's heads draw their masks one after
    another from the cell's seed; blk_q is _1k_blk_q(Sq) on both
    sides); without dropout the forward only needs its own streams
    (q,o + k,v) to fit."""
    if rate > 0.0:
        return _1k_bwd_G(H, itemsize, Sq, Sk, Dh, bias_itemsize,
                         per_head)
    return _1k_G(H, Dh, itemsize, Sq, Sk, Dh, 2, 2, bias_itemsize,
                 per_head)


def _blocked_G(H):
    """(batch, head) rows per grid cell of the blocked kernels — ONE
    choice shared by forward and both backward kernels.

    The in-kernel dropout mask is seeded per grid CELL and q-/k-block
    (_dropout_keep), so the (batch, head) -> cell mapping and the
    block sizes MUST be identical in the kernels that generate and
    regenerate it: a fwd G=8 / bwd G=4 split silently regenerates
    different masks for every head the two groupings assign to
    different cells. (The single-k-block pair keeps the same
    invariant through _1k_fwd_G / _1k_bwd_G / _1k_blk_q.)

    2 is what Mosaic accepts on a v5e at the 256x512 tiles (chip runs,
    PR 21): G=8 ran out of the 16 MB scoped VMEM in the f32 forward,
    the biased bf16 forward and the bf16 backward (Dh<=64 blocks are
    lane-padded to 128, the statistics ride 128 lanes wide, and the
    [G, blk_q, blk_k] f32 score temporaries sit beside them); G=2
    compiled and matched the reference at f32, of which bf16 is the
    smaller case in every term."""
    return blk(H, 2)


def _seed_smem(seed_f, G):
    """int32[2] for SMEM from the float32[2] the custom_vjp carries
    (float32 so no int-cotangent dance): the step's PRNG seed, and
    this call's first grid cell — ``seed_f[1]`` is its first
    (batch, head) row in the one-device numbering, G rows to a cell."""
    s = seed_f.astype(jnp.int32)
    return jnp.stack([s[0], s[1] // G])


def _1k_specs_args(q, k, v, bias, per_head, seed, G, H):
    """Shared in_specs/args plumbing for the single-k-block kernels:
    grid (batch rows b, cells c of G heads, q-blocks j). Returns
    (in_specs, args, the spec of a q-side block, the spec of a k-side
    block)."""
    Sq, Sk = q.shape[1], k.shape[1]
    width = G * (q.shape[2] // H)
    hb = H // G                    # cells per batch row
    blk_q = _1k_blk_q(Sq)
    q_spec = pl.BlockSpec((1, blk_q, width), lambda b, c, j: (b, j, c))
    k_spec = pl.BlockSpec((1, Sk, width), lambda b, c, j: (b, 0, c))
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM), q_spec, k_spec,
                k_spec]
    args = [seed, q, k, v]
    if bias is not None:
        if per_head:
            in_specs.append(pl.BlockSpec(
                (G, 1, blk_q, Sk), lambda b, c, j: (b * hb + c, 0, j, 0)))
        else:
            in_specs.append(pl.BlockSpec(
                (1, 1, blk_q, Sk), lambda b, c, j: (b, 0, j, 0)))
        args.append(bias)
    return in_specs, args, q_spec, k_spec


# The two wrappers are jitted so that the sites of a program that
# present one signature (BERT-base: 12, transformer-base: 6 + 6 + 6)
# share ONE trace and ONE lowered Mosaic body, called from each site
# (XLA inlines the calls). Two things hang on it (my chip runs, PR 27):
# lowered per site, BERT's 36 bodies added 5.5 s to every start of its
# S=512 step (`trace_lower_s` 16.3 s against 10.8 s); and the executor
# lowers a differentiated op twice (forward, then again under
# jax.vjp), whose two bodies, lowered apart, differ in the call-stack
# locations a Mosaic body serializes, so XLA cannot merge the two
# forward calls and runs both. One body makes them identical and CSE
# drops one: 11 ms of BERT's 189 ms step, 8 ms of transformer-base's.
@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _flash_fwd_1k(q, k, v, bias, seed_f, H, scale, rate, causal):
    """q [B,Sq,H*Dh], k and v [B,Sk,H*Dh] -> out [B,Sq,H*Dh]."""
    B, Sq, width = q.shape
    Sk, Dh = k.shape[1], width // H
    n_q = Sq // _1k_blk_q(Sq)
    bias, per_head = _prep_bias(bias, B, H, Sq, Sk)
    G = _1k_fwd_G(H, q.dtype.itemsize, rate, Sq, Sk, Dh,
                  bias.dtype.itemsize if bias is not None else 0,
                  per_head)
    seed = _seed_smem(seed_f, G)

    in_specs, args, q_spec, _ = _1k_specs_args(q, k, v, bias, per_head,
                                               seed, G, H)
    if bias is not None:
        kernel = _fwd_kernel_1k
    else:
        kernel = (lambda sr, qr, kr, vr, orf, **kw:
                  _fwd_kernel_1k(sr, qr, kr, vr, None, orf, **kw))

    return pl.pallas_call(
        functools.partial(kernel, Dh=Dh, scale=scale, rate=rate,
                          causal=causal, n_q=n_q),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(B, H // G, n_q),
        in_specs=in_specs,
        out_specs=q_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        interpret=interpret_mode(),
    )(*args)


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10))
def _flash_bwd_1k(q, k, v, bias, seed_f, o, g, H, scale, rate, causal):
    B, Sq, width = q.shape
    Sk, Dh = k.shape[1], width // H
    n_q = Sq // _1k_blk_q(Sq)
    bias, per_head = _prep_bias(bias, B, H, Sq, Sk)
    G = _1k_bwd_G(H, q.dtype.itemsize, Sq, Sk, Dh,
                  bias.dtype.itemsize if bias is not None else 0,
                  per_head)
    seed = _seed_smem(seed_f, G)

    in_specs, args, q_spec, k_spec = _1k_specs_args(
        q, k, v, bias, per_head, seed, G, H)
    if bias is not None:
        kernel = _bwd_kernel_1k
    else:
        kernel = (lambda sr, qr, kr, vr, dor, orf, *outs, **kw:
                  _bwd_kernel_1k(sr, qr, kr, vr, None, dor, orf,
                                 *outs, **kw))
    in_specs += [q_spec, q_spec]
    args += [g, o]

    return pl.pallas_call(
        functools.partial(kernel, Dh=Dh, scale=scale, rate=rate,
                          causal=causal, n_q=n_q),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        grid=(B, H // G, n_q),
        in_specs=in_specs,
        out_specs=[q_spec, k_spec, k_spec],
        # one q-block: nothing is carried from one grid step to the
        # next; more: dk/dv accumulate across j
        scratch_shapes=[] if n_q == 1 else
        [pltpu.VMEM((Sk, G * Dh), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel",
                "parallel" if n_q == 1 else "arbitrary")),
        interpret=interpret_mode(),
    )(*args)


# ---------------------------------------------------------------------------
# blocked kernels: any Sk, causal k-block skipping, a sliding window,
# grouped queries
# ---------------------------------------------------------------------------
#
# Geometry shared by the three kernels. A grid cell holds G query heads
# and the kv rows they read: G kv heads where every query head has its
# own (group == 1), ONE where ``group`` query heads share a kv head
# (G divides group, so a cell never straddles two kv heads; the kv
# block is indexed by ``cell // (group // G)`` and never repeated in
# HBM). In the shared case the G heads' query rows fold into the
# matmuls' M dimension ([G*blk_q, Dh] x [Dh, blk_k]), and dk / dv sum
# over them in the same product.
#
# Which k-blocks a q-block reads: causal, the blocks up to the one
# holding its last row; with a window, from the block holding key
# ``first row - window + 1`` on. The k axis of the grid is as long as
# the most any q-block reads (``_n_steps``), step ``kk`` of q-block
# ``j`` is k-block ``k_lo(j) + kk``, and the index map clamps it to
# ``k_hi(j)``: a block outside the band is neither computed (pl.when)
# nor fetched (an unchanged block index is not fetched again). The
# dk / dv kernel walks the q-blocks of a k-block the same way.


def _band(blk_q, blk_k, n_q, n_k, causal, window):
    """(k_lo, k_hi, j_lo, j_hi): the first and last k-block q-block j
    reads, the first and last q-block that reads k-block kk; each a
    function of a (traced) block index."""
    def k_lo(j):
        if not window:
            return 0 * j
        return jnp.maximum(j * blk_q - (window - 1), 0) // blk_k

    def k_hi(j):
        if not causal:
            return 0 * j + (n_k - 1)
        return jnp.minimum((j * blk_q + blk_q - 1) // blk_k, n_k - 1)

    def j_lo(kk):
        if not causal:
            return 0 * kk
        return jnp.minimum((kk * blk_k) // blk_q, n_q - 1)

    def j_hi(kk):
        if not window:
            return 0 * kk + (n_q - 1)
        return jnp.minimum(
            (kk * blk_k + blk_k - 1 + window - 1) // blk_q, n_q - 1)

    return k_lo, k_hi, j_lo, j_hi


def _n_steps(blk_a, blk_b, n_b, window):
    """Most blocks of size ``blk_b`` that the band of one block of size
    ``blk_a`` touches: all ``n_b`` without a window."""
    if not window:
        return n_b
    return min(n_b, (blk_a + window - 2) // blk_b + 2)


def _qk(q, k):
    """[G,bq,Dh] x [Gk,bk,Dh] -> [G,bq,bk] float32; Gk is G or 1."""
    if k.shape[0] == q.shape[0]:
        return lax.dot_general(q, k, _QK,
                               preferred_element_type=jnp.float32)
    G, bq, dh = q.shape
    s = lax.dot_general(q.reshape(G * bq, dh), k[0],
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    return s.reshape(G, bq, s.shape[-1])


def _pv(p, v):
    """[G,bq,bk] x [Gk,bk,Dh] -> [G,bq,Dh] float32."""
    if v.shape[0] == p.shape[0]:
        return lax.dot_general(p, v, _PV,
                               preferred_element_type=jnp.float32)
    G, bq, bk = p.shape
    o = lax.dot_general(p.reshape(G * bq, bk), v[0],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
    return o.reshape(G, bq, o.shape[-1])


def _tt(p, x, gk):
    """[G,bq,bk] x [G,bq,Dh] -> [gk,bk,Dh] float32: per row where gk
    is G, summed over the G rows where gk is 1."""
    G, bq, bk = p.shape
    if gk == G:
        return lax.dot_general(p, x, _TT,
                               preferred_element_type=jnp.float32)
    return lax.dot_general(p.reshape(G * bq, bk),
                           x.reshape(G * bq, x.shape[-1]),
                           (((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)[None]


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, b_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, blk_q, blk_k, n_q,
                n_k, n_steps, rate, causal, window):
    i = pl.program_id(0)
    j = pl.program_id(1)
    step = pl.program_id(2)
    k_lo, k_hi, _, _ = _band(blk_q, blk_k, n_q, n_k, causal, window)
    kk = k_lo(j) + step

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(kk <= k_hi(j))
    def _step():
        s = _qk(q_ref[...], k_ref[...]) * scale         # [G, bq, bk]
        if b_ref is not None:
            # per-head: [G,1,bq,bk] -> [G,bq,bk]; per-batch:
            # [1,1,bq,bk] broadcasts over G
            s = s + b_ref[:, 0].astype(jnp.float32)
        if causal:
            s = _causal_mask(s, j, kk, blk_q, blk_k, window)
        m_prev = m_ref[..., :1]                         # [G, bq, 1]
        l_prev = l_ref[..., :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, -1, keepdims=True)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        if rate > 0.0:
            keep = _dropout_keep(seed_ref, i, j, kk, n_q, n_k,
                                 p.shape, rate)
            p = jnp.where(keep, p / (1.0 - rate), 0.0)
        acc_ref[...] = acc_ref[...] * alpha \
            + _pv(p.astype(v_ref.dtype), v_ref[...])

    @pl.when(step == n_steps - 1)
    def _finish():
        l_safe = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[...] = (acc_ref[...] / l_safe[..., :1]).astype(
            o_ref.dtype)
        # lane-replicated [G, blk_q, 128] (the TPU min-tile layout);
        # the wrapper slices lane 0 out for the residual
        lse_ref[...] = m_ref[...] + jnp.log(l_safe)


def _prep_bias(bias, B, H, Sq, Sk):
    """Normalize an additive mask for the kernels. Returns
    (bias array, per_head): per-BATCH biases stay [B, 1, Sq, Sk] and a
    grid cell of G rows indexes batch (i*G)//H; a per-HEAD bias
    [B, H, Sq, Sk] reshapes to [B*H, 1, Sq, Sk] and blocks G rows
    directly — both paths are G-consistent because G divides H."""
    if bias is None:
        return None, False
    if bias.ndim == 4 and bias.shape[1] == H and H > 1:
        return (jnp.broadcast_to(bias, (B, H, Sq, Sk))
                .reshape(B * H, 1, Sq, Sk)), True
    return jnp.broadcast_to(bias, (B, 1, Sq, Sk)), False


def _blocked_geometry(q, k):
    """(G, gk, reps, blk_q, blk_k, n_q, n_k) of the blocked kernels for
    q [B,H,Sq,Dh] and k [B,Hkv,Sk,Dh]: G query heads and gk kv heads
    to a cell, ``reps`` cells of query heads to a kv head."""
    H, Hkv = q.shape[1], k.shape[1]
    group = H // Hkv
    G = _blocked_G(H if group == 1 else group)
    gk = G if group == 1 else 1
    blk_q = blk(q.shape[2], _BLK_Q_TARGET)
    blk_k = blk(k.shape[2], _BLK_K_TARGET)
    return (G, gk, group // G if group > 1 else 1, blk_q, blk_k,
            q.shape[2] // blk_q, k.shape[2] // blk_k)


# Jitted for what the 1k wrappers are jitted for: the sites of one
# signature share one lowered Mosaic body, so the forward the executor
# lowers twice (once more under jax.vjp) is one call after XLA's CSE.
@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _flash_fwd(q, k, v, bias, seed_f, scale, rate, causal, window=0):
    B, H, Sq, Dh = q.shape
    Hkv, Sk, Dv = k.shape[1], k.shape[2], v.shape[3]
    BH = B * H
    bias, per_head = _prep_bias(bias, B, H, Sq, Sk)
    G, gk, reps, blk_q, blk_k, n_q, n_k = _blocked_geometry(q, k)
    hb = H // G                    # cells per batch row
    q3 = q.reshape(BH, Sq, Dh)
    k3 = k.reshape(B * Hkv, Sk, Dh)
    v3 = v.reshape(B * Hkv, Sk, Dv)
    k_lo, k_hi, _, _ = _band(blk_q, blk_k, n_q, n_k, causal, window)
    n_steps = _n_steps(blk_q, blk_k, n_k, window)
    grid = (BH // G, n_q, n_steps)
    seed = _seed_smem(seed_f, G)

    def kb(j, step):
        return jnp.minimum(k_lo(j) + step, k_hi(j))

    kv_spec = pl.BlockSpec((gk, blk_k, Dh),
                           lambda i, j, st: (i // reps, kb(j, st), 0))
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((G, blk_q, Dh), lambda i, j, st: (i, j, 0)),
        kv_spec, pl.BlockSpec((gk, blk_k, Dv), kv_spec.index_map),
    ]
    args = [seed, q3, k3, v3]
    if bias is not None:
        if per_head:
            bspec = pl.BlockSpec(
                (G, 1, blk_q, blk_k),
                lambda i, j, st: (i, 0, j, kb(j, st)))
        else:
            bspec = pl.BlockSpec(
                (1, 1, blk_q, blk_k),
                lambda i, j, st: (i // hb, 0, j, kb(j, st)))
        in_specs.append(bspec)
        args.append(bias)
        kernel = _fwd_kernel
    else:
        kernel = (lambda sr, qr, kr, vr, orf, lr, ar, mr, llr, **kw:
                  _fwd_kernel(sr, qr, kr, vr, None, orf, lr, ar, mr,
                              llr, **kw))

    out, lse = pl.pallas_call(
        functools.partial(kernel, scale=scale, blk_q=blk_q,
                          blk_k=blk_k, n_q=n_q, n_k=n_k,
                          n_steps=n_steps, rate=rate, causal=causal,
                          window=window),
        out_shape=[jax.ShapeDtypeStruct((BH, Sq, Dv), q.dtype),
                   jax.ShapeDtypeStruct((BH, Sq, 128), jnp.float32)],
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((G, blk_q, Dv), lambda i, j, st: (i, j, 0)),
            pl.BlockSpec((G, blk_q, 128), lambda i, j, st: (i, j, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, blk_q, Dv), jnp.float32),
            pltpu.VMEM((G, blk_q, 128), jnp.float32),
            pltpu.VMEM((G, blk_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(),
    )(*args)
    return out.reshape(B, H, Sq, Dv), lse[:, :, 0]


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _recompute_p(q_ref, k_ref, b_ref, lse_ref, *, scale, j, kk, blk_q,
                 blk_k, causal, window):
    s = _qk(q_ref[...], k_ref[...]) * scale
    if b_ref is not None:
        s = s + b_ref[:, 0].astype(jnp.float32)
    if causal:
        s = _causal_mask(s, j, kk, blk_q, blk_k, window)
    return jnp.exp(s - lse_ref[..., :1])          # [G, blk_q, blk_k]


def _dq_kernel(seed_ref, q_ref, k_ref, v_ref, b_ref, do_ref, lse_ref,
               dl_ref, dq_ref, dq_acc, *, scale, blk_q, blk_k, n_q,
               n_k, n_steps, rate, causal, window):
    i = pl.program_id(0)
    j = pl.program_id(1)
    step = pl.program_id(2)
    k_lo, k_hi, _, _ = _band(blk_q, blk_k, n_q, n_k, causal, window)
    kk = k_lo(j) + step

    @pl.when(step == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(kk <= k_hi(j))
    def _step():
        p = _recompute_p(q_ref, k_ref, b_ref, lse_ref, scale=scale,
                         j=j, kk=kk, blk_q=blk_q, blk_k=blk_k,
                         causal=causal, window=window)
        dp = _qk(do_ref[...], v_ref[...])             # [G, bq, bk]
        if rate > 0.0:
            keep = _dropout_keep(seed_ref, i, j, kk, n_q, n_k,
                                 dp.shape, rate)
            dp = jnp.where(keep, dp / (1.0 - rate), 0.0)
        delta = dl_ref[..., :1]                       # [G, bq, 1]
        ds = (p * (dp - delta) * scale).astype(k_ref.dtype)
        dq_acc[...] += _pv(ds, k_ref[...])

    @pl.when(step == n_steps - 1)
    def _finish():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(seed_ref, q_ref, k_ref, v_ref, b_ref, do_ref, lse_ref,
                dl_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, scale,
                blk_q, blk_k, n_q, n_k, n_steps, reps, rate, causal,
                window):
    c = pl.program_id(0)
    kk = pl.program_id(1)
    u = pl.program_id(2)
    # the inner axis walks the kv head's ``reps`` cells of query heads,
    # and in each the q-blocks of this k-block's band
    i = c * reps + u // n_steps
    _, _, j_lo, j_hi = _band(blk_q, blk_k, n_q, n_k, causal, window)
    j = j_lo(kk) + u % n_steps
    gk = k_ref.shape[0]

    @pl.when(u == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(j <= j_hi(kk))
    def _step():
        p = _recompute_p(q_ref, k_ref, b_ref, lse_ref, scale=scale,
                         j=j, kk=kk, blk_q=blk_q, blk_k=blk_k,
                         causal=causal, window=window)
        do = do_ref[...]
        if rate > 0.0:
            keep = _dropout_keep(seed_ref, i, j, kk, n_q, n_k,
                                 p.shape, rate)
            pd = jnp.where(keep, p / (1.0 - rate), 0.0)
        else:
            pd = p
        # dv += Pd^T @ dO (per row, or over the rows that share the
        # kv head)
        dv_acc[...] += _tt(pd.astype(do.dtype), do, gk)
        dp = _qk(do, v_ref[...])
        if rate > 0.0:
            dp = jnp.where(keep, dp / (1.0 - rate), 0.0)
        delta = dl_ref[..., :1]
        ds = (p * (dp - delta) * scale).astype(q_ref.dtype)
        # dk += dS^T @ Q
        dk_acc[...] += _tt(ds, q_ref[...], gk)

    @pl.when(u == reps * n_steps - 1)
    def _finish():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnums=(8, 9, 10, 11))
def _flash_bwd(q, k, v, bias, seed_f, o, lse, g, scale, rate, causal,
               window=0):
    B, H, Sq, Dh = q.shape
    Hkv, Sk, Dv = k.shape[1], k.shape[2], v.shape[3]
    BH, BHkv = B * H, B * Hkv
    bias, per_head = _prep_bias(bias, B, H, Sq, Sk)
    G, gk, reps, blk_q, blk_k, n_q, n_k = _blocked_geometry(q, k)
    hb = H // G
    q3 = q.reshape(BH, Sq, Dh)
    k3 = k.reshape(BHkv, Sk, Dh)
    v3 = v.reshape(BHkv, Sk, Dv)
    do3 = g.reshape(BH, Sq, Dv)
    k_lo, k_hi, j_lo, j_hi = _band(blk_q, blk_k, n_q, n_k, causal,
                                   window)
    nk_steps = _n_steps(blk_q, blk_k, n_k, window)
    nj_steps = _n_steps(blk_k, blk_q, n_q, window)
    seed = _seed_smem(seed_f, G)
    # delta_i = rowsum(dO * O): O(S*Dh) elementwise work, XLA fuses it.
    # lse/delta enter the kernels lane-replicated to the 128-lane
    # min-tile (the layout the fwd kernel produced them in).
    delta = jnp.sum(do3.astype(jnp.float32) * o.reshape(BH, Sq, Dv)
                    .astype(jnp.float32), axis=-1)
    lse128 = jnp.broadcast_to(lse[:, :, None], (BH, Sq, 128))
    delta128 = jnp.broadcast_to(delta[:, :, None], (BH, Sq, 128))

    def specs(order):
        """order: 'dq' grid (BH/G, n_q, k steps) or 'dkv' (kv cells,
        n_k, reps x q steps). Each index map goes through (q cell, q
        block, k block) of the grid point."""
        if order == "dq":
            def at(i, j, st):
                return i, j, jnp.minimum(k_lo(j) + st, k_hi(j))
        else:
            def at(c, kk, u):
                return (c * reps + u // nj_steps,
                        jnp.minimum(j_lo(kk) + u % nj_steps, j_hi(kk)),
                        kk)

        def qi(*g3):
            i, j, _ = at(*g3)
            return i, j, 0

        def ki(*g3):
            i, _, kk = at(*g3)
            return i // reps, kk, 0

        def bi(*g3):
            i, j, kk = at(*g3)
            return (i if per_head else i // hb), 0, j, kk

        sp = [pl.BlockSpec(memory_space=pltpu.SMEM),
              pl.BlockSpec((G, blk_q, Dh), qi),
              pl.BlockSpec((gk, blk_k, Dh), ki),
              pl.BlockSpec((gk, blk_k, Dv), ki)]
        ar = [seed, q3, k3, v3]
        if bias is not None:
            gb = G if per_head else 1
            sp.append(pl.BlockSpec((gb, 1, blk_q, blk_k), bi))
            ar.append(bias)
        sp += [pl.BlockSpec((G, blk_q, Dv), qi),
               pl.BlockSpec((G, blk_q, 128), qi),
               pl.BlockSpec((G, blk_q, 128), qi)]
        ar += [do3, lse128, delta128]
        return sp, ar

    def with_bias(kern):
        if bias is not None:
            return kern
        return functools.partial(
            lambda f, sr, qr, kr, vr, *rest, **kw:
            f(sr, qr, kr, vr, None, *rest, **kw), kern)

    common = dict(scale=scale, blk_q=blk_q, blk_k=blk_k, n_q=n_q,
                  n_k=n_k, rate=rate, causal=causal, window=window)
    sp, ar = specs("dq")
    dq = pl.pallas_call(
        functools.partial(with_bias(_dq_kernel), n_steps=nk_steps,
                          **common),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, Dh), q.dtype),
        grid=(BH // G, n_q, nk_steps),
        in_specs=sp,
        out_specs=pl.BlockSpec((G, blk_q, Dh),
                               lambda i, j, st: (i, j, 0)),
        scratch_shapes=[pltpu.VMEM((G, blk_q, Dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(),
    )(*ar)

    sp, ar = specs("dkv")
    dk, dv = pl.pallas_call(
        functools.partial(with_bias(_dkv_kernel), n_steps=nj_steps,
                          reps=reps, **common),
        out_shape=[jax.ShapeDtypeStruct((BHkv, Sk, Dh), k.dtype),
                   jax.ShapeDtypeStruct((BHkv, Sk, Dv), v.dtype)],
        grid=(BHkv // gk, n_k, reps * nj_steps),
        in_specs=sp,
        out_specs=[
            pl.BlockSpec((gk, blk_k, Dh), lambda c, kk, u: (c, kk, 0)),
            pl.BlockSpec((gk, blk_k, Dv), lambda c, kk, u: (c, kk, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((gk, blk_k, Dh), jnp.float32),
                        pltpu.VMEM((gk, blk_k, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(),
    )(*ar)

    dq = dq.reshape(B, H, Sq, Dh)
    dk = dk.reshape(B, Hkv, Sk, Dh)
    dv = dv.reshape(B, Hkv, Sk, Dv)
    return dq, dk, dv


def _takes_1k(H, Hkv, Sq, Sk, window):
    """The single-k-block pair serves equal q and kv heads with no
    window inside its envelope; the blocked kernels everything else."""
    return not window and H == Hkv and _1k_applicable(Sq, Sk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _sdpa_flash(q, k, v, bias, seed_f, scale, rate, causal, window=0,
                num_heads=0):
    """The kernels behind one differentiable call. The layout names
    the family: rank 3 ``[B,S,H*Dh]`` with ``num_heads`` is the 1k
    pair's, rank 4 ``[B,H,S,Dh]`` the blocked kernels' (sdpa_pallas
    hands each the layout it reads)."""
    if q.ndim == 3:
        return _flash_fwd_1k(q, k, v, bias, seed_f, num_heads, scale,
                             rate, causal)
    out, _lse = _flash_fwd(q, k, v, bias, seed_f, scale, rate, causal,
                           window)
    return out


def _sdpa_flash_fwd(q, k, v, bias, seed_f, scale, rate, causal,
                    window=0, num_heads=0):
    if q.ndim == 3:
        out = _flash_fwd_1k(q, k, v, bias, seed_f, num_heads, scale,
                            rate, causal)
        # the single-block backward re-derives lse in-kernel: the
        # forward output is the only tensor residual
        return out, (q, k, v, bias, seed_f, out, None)
    out, lse = _flash_fwd(q, k, v, bias, seed_f, scale, rate, causal,
                          window)
    return out, (q, k, v, bias, seed_f, out, lse)


def _sdpa_flash_bwd(scale, rate, causal, window, num_heads, res, g):
    q, k, v, bias, seed_f, out, lse = res
    if lse is None:
        dq, dk, dv = _flash_bwd_1k(q, k, v, bias, seed_f, out, g,
                                   num_heads, scale, rate, causal)
    else:
        dq, dk, dv = _flash_bwd(q, k, v, bias, seed_f, out, lse, g,
                                scale, rate, causal, window)
    dbias = None if bias is None else jnp.zeros_like(bias)
    return dq, dk, dv, dbias, jnp.zeros_like(seed_f)


_sdpa_flash.defvjp(_sdpa_flash_fwd, _sdpa_flash_bwd)


@register_variant("scaled_dot_product_attention", "pallas")
def sdpa_pallas(q, k, v, bias, *, scale=1.0, dropout_rate=0.0,
                causal=False, is_test=False, window=0, num_heads=0,
                rng=None):
    rate = 0.0 if is_test else float(dropout_rate)
    window = int(window)
    num_heads = int(num_heads)
    # per-head bias [B, H, Sq, Sk] is handled natively: _prep_bias
    # flattens it to one slab per (batch, head) grid row
    if rate > 0.0 and (rng is None or interpret_mode()):
        # the TPU PRNG has no interpreter emulation; CPU tests take the
        # reference path (dropout masks differ across libraries anyway)
        _count_lowering("xla")
        return _sdpa_reference(q, k, v, bias, scale=scale,
                               dropout_rate=rate, causal=causal,
                               window=window, num_heads=num_heads,
                               rng=rng)
    _, H, hkv, sq, sk, dh = _geometry(q, k, num_heads)
    heads_last = q.ndim == 3
    takes_1k = _takes_1k(H, hkv, sq, sk, window) and _same_widths(k, v)
    _count_lowering("flash_1k" if takes_1k else _blocked_name(k, v))
    # each family reads one layout; the other entry layout is adapted
    # by the transposes its model would otherwise have built
    if takes_1k:
        _count_lowering("flash_1k_transposed", 0.0 if heads_last else 1.0)
        if not heads_last:
            q, k, v = (_merge_heads(x) for x in (q, k, v))
    elif heads_last:
        q, k, v = (_split_heads(x, dh) for x in (q, k, v))
    if rate > 0.0:
        # fold the step key into a scalar TPU PRNG seed; float32 carries
        # it through custom_vjp without an int-cotangent (float0) dance
        seed = jax.random.randint(rng, (), 0, 1 << 23).astype(
            jnp.float32)
    else:
        seed = jnp.float32(0)
    from ...parallel import mesh as mesh_lib
    from ...parallel.ulysses import in_sp_body
    mesh = mesh_lib.current_mesh()
    if mesh is not None and mesh.size > 1 and not in_sp_body():
        out = _flash_over_mesh(mesh, q, k, v, bias, seed, H, hkv,
                               float(scale), rate, bool(causal), window)
    else:
        out = _sdpa_flash(q, k, v, bias,
                          jnp.stack([seed, jnp.float32(0)]),
                          float(scale), rate, bool(causal), window, H)
    if takes_1k == heads_last:
        return out
    return _split_heads(out, dh) if takes_1k else _merge_heads(out)


def _flash_over_mesh(mesh, q, k, v, bias, seed, H, Hkv, scale, rate,
                     causal, window=0):
    """The kernel under a multi-device mesh. Mosaic kernels are not
    partitioned automatically — jax's lowering rule refuses one inside
    a multi-device jit (jax/_src/tpu_custom_call.py) — so it runs per
    shard under shard_map: batch over ``dp`` and heads over ``tp``
    where the mesh has those axes and they divide (the kv heads too,
    where there are fewer of them), every other axis computing
    replicated. Heads are axis 1 of the blocked kernels' rank 4 and
    contiguous in the last axis of the pair's rank 3. Each shard
    numbers its dropout cells from its first (batch, head) row, so
    under dp the masks are the ones a single device draws and the loss
    trace is the single-device one."""
    from jax import shard_map
    from jax.sharding import PartitionSpec

    B = q.shape[0]

    def axis(name, *ns):
        if name in mesh.axis_names and mesh.shape[name] > 1 \
                and all(n % mesh.shape[name] == 0 for n in ns):
            return name
        return None

    b_ax, h_ax = axis("dp", B), axis("tp", H, Hkv)
    h_loc = H // mesh.shape[h_ax] if h_ax else H
    spec = PartitionSpec(b_ax, None, h_ax) if q.ndim == 3 \
        else PartitionSpec(b_ax, h_ax, None, None)
    args, specs = [seed, q, k, v], [PartitionSpec(), spec, spec, spec]
    if bias is not None:
        bias = bias.reshape((1,) * (4 - bias.ndim) + bias.shape)
        args.append(bias)
        specs.append(PartitionSpec(
            b_ax if bias.shape[0] == B else None,
            h_ax if bias.shape[1] == H else None, None, None))

    def body(seed_, q_, k_, v_, bias_=None):
        shard = jnp.int32(0)
        for ax in (b_ax, h_ax):
            if ax is not None:
                shard = shard * mesh.shape[ax] + lax.axis_index(ax)
        row0 = shard * (q_.shape[0] * h_loc)
        return _sdpa_flash(
            q_, k_, v_, bias_,
            jnp.stack([seed_, row0.astype(jnp.float32)]),
            scale, rate, causal, window, h_loc)

    return shard_map(body, mesh=mesh, in_specs=tuple(specs),
                     out_specs=spec, check_vma=False)(*args)


def _same_widths(k, v):
    """Whether values are as wide as keys: both are [B,Hkv,Sk,D] or
    both [B,Sk,Hkv*D], so their last axes tell. Latent attention's keys
    (and queries) are 192 wide beside 128-wide values; rank 4 only."""
    return k.shape[-1] == v.shape[-1]


def _blocked_name(k, v):
    """The blocked kernels' counter: ``flash_blocked``, and
    ``flash_blocked_mla`` where the qk width differs from v's (the
    kernels take the two widths as they come: q, k, dq, dk blocks and
    accumulators at one, v, o, do, dv at the other)."""
    return "flash_blocked" if _same_widths(k, v) else "flash_blocked_mla"
