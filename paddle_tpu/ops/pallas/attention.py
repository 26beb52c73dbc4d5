"""Flash attention: fused scaled-dot-product attention pallas kernels.

The analog of the reference's fused attention path — the 2019 reference
composes attention op-by-op (matmul/softmax/matmul through separate
kernels, e.g. the benchmark transformer), which round-trips the
[B,H,Sq,Sk] score matrix through HBM twice in the forward and again in
the backward. On TPU this kernel family never materializes the score
matrix in HBM in either direction:

- **Forward**: k-blocked online softmax. Running max ``m``, normalizer
  ``l`` and the output accumulator live in VMEM scratch; the softmax
  statistics ``lse = m + log(l)`` are saved for the backward, a row
  of lanes a head.
- **Backward**: per-block recompute of ``p = exp(s - lse)`` from q/k
  and the saved statistics; only O(seq * head_dim) residuals (out,
  lse) ever hit HBM. ONE kernel with one recompute wherever K, V, dK
  and dV of a kv head fit VMEM (the three 8k benchmark cells), else
  two, ``dq`` (scanning k-blocks) and ``dk/dv`` (scanning q-blocks).
- **Dropout** runs in-kernel with the TPU PRNG
  (``pltpu.prng_seed``/``prng_random_bits``), seeded per
  (grid cell, q-block, k-block) so the backward regenerates the exact
  forward mask without storing it.
- **Causal** masking skips fully-masked k-blocks (roughly halves the
  decoder self-attention work).
- **The blocked kernels' schedule is read off the site's shape** by a
  VMEM model (``_blocked_schedule``): K and V of a kv head resident
  for its whole q sweep, the k-blocks walked by a loop inside the
  body that masks at the band's edges only; a cell holds one head's
  q-block, or those of the query heads that share a kv head (batched
  over the leading dim: G divides the group, so a cell never
  straddles a batch row and per-BATCH bias blocks stay well-defined).
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
  Everything else — Sk > 512, grouped queries, a window, two widths —
  takes the blocked kernels below: with no dropout by default (the
  three 8k cells' 5 + 5 + 1 sites), with dropout under
  ``FLAGS_op_library=pallas``; a ragged shape keeps XLA's chain.
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

import collections
import functools

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
    kernels by itself: keys past the single-k-block envelope, in the
    tiles the schedule takes to the chip (_blocked_tiles): whole
    128-lane groups to a q-block, whose statistics travel a row of
    lanes, and to a k-block."""
    _, blk_q, blk_k = _blocked_tiles(1, Sq, Sk)
    return Sk > 512 and blk_q % 128 == 0 and blk_k % 128 == 0


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
# exp recompute (as the blocked path's is wherever a kv head's K, V,
# dK and dV fit VMEM), with lse and delta = rowsum(dO*O) derived
# in-kernel so the only HBM residual is the forward output itself.
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
# grouped queries, a qk width beside a v width
# ---------------------------------------------------------------------------
#
# A grid cell holds G query heads and the kv rows they read: G kv heads
# where every query head has its own (group == 1), ONE where ``group``
# query heads share a kv head (G divides group, so a cell never
# straddles two kv heads; the kv block is indexed by
# ``cell // (group // G)`` and never repeated in HBM). In the shared
# case the G heads' query rows fold into the matmuls' M dimension
# ([G*blk_q, Dh] x [Dh, blk_k]), and dk / dv sum over them in the same
# product.
#
# The schedule (_blocked_geometry: ONE function of the site's shape for
# the forward and every backward kernel, since the in-kernel dropout
# mask is seeded by (cell, q-block, k-block) of the [blk_q, blk_k]
# score tile):
#
# - A grid step holds one q-block of blk_q rows against a MAJOR block
#   of keys, ``k_major`` rows of K and V, and walks the blk_k-row
#   k-blocks of it that the q-block's band touches in a loop INSIDE the
#   body (``_walk``): a k-block outside the band is no grid step, and a
#   loop step costs no pipeline bookkeeping. Where K and V of the
#   cell's kv head(s) fit the VMEM model the major block is the whole
#   key range (``flash_schedule.kv_resident``): its block index does
#   not move across the q-blocks of a cell (nor across the cells that
#   share a kv head), so K and V are fetched once a head, where a
#   256-row q-block re-read every k-block up to its diagonal, 17 times
#   a head at 8,192 keys. Where they do not fit, the largest major
#   block that does is streamed along a third grid axis, clamped to the
#   band as before (``kv_streamed``).
# - The walk is cut at the band's edges: the k-blocks wholly inside the
#   band run a body without ``_causal_mask`` (two iotas, a compare, two
#   with a window, and a select a score); only the blocks that the
#   diagonal or, with a window, the trailing edge crosses keep it.
# - The statistics enter and leave as ``[BH, 1, S]`` float32, a row of
#   lanes a head, and are spread to the 128-lane columns the score
#   tile reads them in by one transpose a q-block in VMEM
#   (``_columns``); no ``[BH, S, 128]`` array exists around the kernels.
# - The backward is ONE kernel with one recompute of s, p, dp and ds
#   wherever K, V, dK, dV of a kv head and the float32 sums of dK and
#   dV fit (``flash_backward.fused``): it walks the q-blocks of the
#   kv head's query heads, writes dq a block and adds into the
#   resident sums (832 lanes of products a pair at 192 / 128 and one
#   exp, where two kernels need 1,152 and two). Where they do not fit
#   (``split``), a dq kernel scheduled like the forward and a dk / dv
#   kernel that is its mirror image: a k-block and its sums resident
#   against a major block of q, dO and the statistics, the q-blocks
#   walked inside.
#
# Which k-blocks a q-block reads: causal, the blocks up to the one
# holding its last row; with a window, from the block holding key
# ``first row - window + 1`` on (``_band``, at the tile's and at the
# major block's size alike).

# The VMEM model of the blocked kernels: what a grid step holds, in the
# (sublane, 128-lane) tiles VMEM keeps it in.
#   - streamed blocks, double-buffered: q, o / dO, dq rows of blk_q;
#     the major block of K and V (and dK, dV where the backward is
#     fused) at ``_lanes`` of the two widths (192 lanes fill 256);
#     the statistics, a row of lanes a head (8 sublanes a row);
#   - scratch, resident once: the float32 accumulators, the lane-wide
#     m / l or lse / delta columns, fused dK / dV sums of the whole key
#     range;
#   - the [G, blk_q, blk_k] score temporaries of ONE loop step
#     (s, p, dp, ds in float32 and the two casts: 24 bytes a score in
#     the backward, 16 in the forward, the generator's bits of a
#     dropout site among them; twice for float32 operands, whose exact
#     products split each into bf16 parts);
#   - a bias block beside the scores' in every schedule: blk_q rows of
#     the major block's keys, double-buffered, and its float32 addend.
# The kernels ask Mosaic for _BLOCKED_VMEM_LIMIT (of the v5e's 128 MiB;
# the default scoped limit is 16 MB) and the model plans with
# _BLOCKED_VMEM_BUDGET of it: the rest is the compiler's own scratch
# and what the model does not see. tests/test_pallas_vmem.py replays
# the model at the three 8k cells' sites and at a 32k-key corner and
# pins each one's schedule; tests/test_tpu_aot_kernels.py has the
# chip's compiler accept them.
_BLOCKED_VMEM_LIMIT = 100 << 20
_BLOCKED_VMEM_BUDGET = 80 << 20
_BLOCKED_FWD_TEMP_BYTES = 16
_BLOCKED_BWD_TEMP_BYTES = 24
# Rows of scores a loop step holds (G x blk_q) and keys a k-block: the
# tiles the chip ran fastest at the three cells' sites, forward and
# backward together (tools/blocked_sweep.py; PERF.md section 5, PR 35):
# one head's 512 rows where every query head has its own kv head
# (256 or 1,024 rows 3-5% slower, 256 or 1,024 keys 4-16%), four
# heads' 256 where they share one (a k-block is then the matrix unit's
# stationary operand for twice the rows: 3-5% faster than two heads').
_BLOCKED_ROWS = 512
_BLOCKED_ROWS_SHARED = 1024
_BLOCKED_BLK_K = 512

_Schedule = collections.namedtuple("_Schedule", [
    "G", "gk", "reps",          # q heads, kv heads a cell; cells a kv head
    "blk_q", "blk_k",           # the score tile of a loop step
    "k_major",                  # keys a forward / dq grid step holds
    "fused",                    # one backward kernel
    "q_major",                  # query rows a dk / dv grid step holds
    "kv_resident"])             # k_major is the whole key range


def _tile(n, target):
    """The largest divisor of ``n`` that is whole 128-lane groups and at
    most ``target``; of a ragged ``n`` (which the envelope keeps from
    the chip), common.blk's."""
    for b in range(min(n, target) // 128 * 128, 0, -128):
        if n % b == 0:
            return b
    return blk(n, target)


def _blocked_tiles(group, Sq, Sk):
    """(G, blk_q, blk_k): one head's _BLOCKED_ROWS rows of scores to a
    loop step where every query head has its own kv head; where
    ``group`` of them share one, 256 rows of as many as make
    _BLOCKED_ROWS_SHARED."""
    blk_k = _tile(Sk, _BLOCKED_BLK_K)
    if group == 1:
        return 1, _tile(Sq, _BLOCKED_ROWS), blk_k
    blk_q = _tile(Sq, 256)
    return blk(group, max(1, _BLOCKED_ROWS_SHARED // blk_q)), blk_q, blk_k


def _lanes(d):
    return -(-d // 128) * 128


def _blocked_bytes(kernel, G, gk, blk_q, blk_k, major, Sk, Dh, Dv,
                   itemsize, bias_itemsize=0, per_head=False):
    """Modeled VMEM of one grid step of ``kernel``: "fwd", "dq" (each
    against ``major`` keys), "dkv" (a k-block against ``major`` query
    rows) or "fused" (the whole key range, ``major`` is Sk)."""
    dh, dv = _lanes(Dh), _lanes(Dv)
    wide = 2 if itemsize > 2 else 1
    stat = 2 * G * 8 * 4                 # a double-buffered row, a lane
    col = G * blk_q * 128 * 4            # a lane-wide column scratch
    temps = G * blk_q * blk_k * wide * (
        _BLOCKED_FWD_TEMP_BYTES if kernel == "fwd"
        else _BLOCKED_BWD_TEMP_BYTES)
    slabs = G if per_head else 1
    if kernel == "dkv":
        total = 2 * G * major * (dh + dv) * itemsize       # q, dO
        total += 2 * stat * major                          # lse, delta
        total += 2 * 2 * gk * blk_k * (dh + dv) * itemsize  # k v dk dv
        total += gk * blk_k * (dh + dv) * 4 + 2 * col
        bias = 2 * slabs * major * blk_k * bias_itemsize
    else:
        total = 2 * G * blk_q * (dh + dv) * itemsize       # q, o / dO
        total += 2 * gk * major * (dh + dv) * itemsize     # k, v
        total += 2 * col
        if kernel == "fwd":
            total += stat * blk_q + G * blk_q * dv * 4     # lse; acc
        else:
            total += 2 * stat * blk_q                      # lse, delta
            total += 2 * G * blk_q * dh * itemsize + G * blk_q * dh * 4
        if kernel == "fused":
            total += 2 * gk * Sk * (dh + dv) * itemsize    # dk, dv
            total += gk * Sk * (dh + dv) * 4               # their sums
        bias = 2 * slabs * blk_q * major * bias_itemsize
    if bias_itemsize:
        total += bias + G * blk_q * blk_k * 4
    return total + temps


def _largest_major(n, tile, fits):
    """The most rows (whole tiles, a divisor of ``n``) that ``fits``;
    one tile where nothing does."""
    for parts in range(1, n // tile + 1):
        if (n // tile) % parts == 0 and fits(n // parts):
            return n // parts
    return tile


def _blocked_schedule(H, Hkv, Sq, Sk, Dh, Dv, itemsize, bias_itemsize=0,
                      per_head=False):
    """The schedule of a site, read off its shape alone: key length,
    the two widths, query heads a kv head, operand and bias types."""
    group = H // Hkv
    G, blk_q, blk_k = _blocked_tiles(group, Sq, Sk)
    gk = G if group == 1 else 1

    def fits(kernel):
        return lambda major: _blocked_bytes(
            kernel, G, gk, blk_q, blk_k, major, Sk, Dh, Dv, itemsize,
            bias_itemsize, per_head) <= _BLOCKED_VMEM_BUDGET

    # the dq kernel holds more beside the major block than the forward:
    # one size that both fit
    k_major = _largest_major(Sk, blk_k, lambda m: fits("fwd")(m)
                             and fits("dq")(m))
    return _Schedule(
        G, gk, group // G if group > 1 else 1, blk_q, blk_k, k_major,
        fits("fused")(Sk), _largest_major(Sq, blk_q, fits("dkv")),
        k_major == Sk)


def _blocked_geometry(q, k, v, bias=None, per_head=False):
    """The _Schedule of q [B,H,Sq,Dh], k [B,Hkv,Sk,Dh], v [B,Hkv,Sk,Dv]
    and a bias as _prep_bias left it."""
    return _blocked_schedule(
        q.shape[1], k.shape[1], q.shape[2], k.shape[2], q.shape[3],
        v.shape[3], q.dtype.itemsize,
        bias.dtype.itemsize if bias is not None else 0, per_head)


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


def _inside(blk_q, blk_k, causal, window):
    """(first, end) of the k-blocks wholly inside q-block j's band, and
    of the q-blocks wholly inside k-block kk's: every score of such a
    tile is kept, so it needs no mask. ``None`` where the band has no
    edge on that side."""
    def k_first(j):             # past the window's trailing edge
        return (jnp.maximum(j * blk_q + blk_q - 1 - window, -1)
                + blk_k) // blk_k

    def k_end(j):               # before the diagonal
        return (j * blk_q + 1) // blk_k

    def j_first(kk):            # past the diagonal
        return (kk * blk_k + blk_k + blk_q - 2) // blk_q

    def j_end(kk):              # before the window's trailing edge
        return (jnp.maximum(window + kk * blk_k - blk_q, -1)
                + blk_q) // blk_q

    return (k_first if window else None, k_end if causal else None,
            j_first if causal else None, j_end if window else None)


def _walk(lo, hi, first_in, end_in, step):
    """``step(t, masked)`` for the blocks lo..hi of a band, in up to
    three loops: the edge blocks before ``first_in`` masked, the blocks
    inside bare, the edge blocks from ``end_in`` on masked; an edge the
    band does not have (``None``) is no loop. The loops are
    ``fori_loop``s, not unrolled: one body each in the Mosaic text."""
    stop = hi + 1
    a = lo if first_in is None else jnp.clip(first_in, lo, stop)
    b = stop if end_in is None else jnp.clip(end_in, a, stop)

    def loop(start, end, masked):
        def body(t, carry):
            step(t, masked)
            return carry
        lax.fori_loop(start, end, body, 0)

    if first_in is not None:
        loop(lo, a, True)
    loop(a, b, False)
    if end_in is not None:
        loop(b, stop, True)


def _rows(ref, start, size):
    """``size`` rows of a resident block from row ``start`` (a multiple
    of ``size``), every cell and lane of it."""
    return ref[:, pl.ds(start, size), :]


def _columns(row_ref, col_ref, start=0):
    """Spread a statistic that arrives a row of lanes a head,
    ``row_ref`` [G, 1, n], to the lane-wide columns ``col_ref``
    [G, blk_q, 128] the score tile reads (rows ``start`` on)."""
    blk_q = col_ref.shape[1]
    for g in range(col_ref.shape[0]):
        row = row_ref[g, :, pl.ds(start, blk_q)]            # [1, blk_q]
        col_ref[g] = jnp.transpose(
            jnp.broadcast_to(row, (128, blk_q)))


def _qk(q, k):
    """[G,bq,Dh] x [Gk,bk,Dh] -> [G,bq,bk] float32; Gk is G or 1."""
    if k.shape[0] == q.shape[0] and k.shape[0] > 1:
        return lax.dot_general(q, k, _QK,
                               preferred_element_type=jnp.float32)
    G, bq, dh = q.shape
    s = lax.dot_general(q.reshape(G * bq, dh), k[0], _NT,
                        preferred_element_type=jnp.float32)
    return s.reshape(G, bq, s.shape[-1])


def _pv(p, v):
    """[G,bq,bk] x [Gk,bk,Dh] -> [G,bq,Dh] float32."""
    if v.shape[0] == p.shape[0] and v.shape[0] > 1:
        return lax.dot_general(p, v, _PV,
                               preferred_element_type=jnp.float32)
    G, bq, bk = p.shape
    o = lax.dot_general(p.reshape(G * bq, bk), v[0], _NN,
                        preferred_element_type=jnp.float32)
    return o.reshape(G, bq, o.shape[-1])


def _tt(p, x, gk):
    """[G,bq,bk] x [G,bq,Dh] -> [gk,bk,Dh] float32: per row where gk
    is G, summed over the G rows where gk is 1."""
    G, bq, bk = p.shape
    if gk == G and G > 1:
        return lax.dot_general(p, x, _TT,
                               preferred_element_type=jnp.float32)
    return lax.dot_general(p.reshape(G * bq, bk),
                           x.reshape(G * bq, x.shape[-1]), _TN,
                           preferred_element_type=jnp.float32)[None]


def _scores(q, k, b, j, kk, *, scale, blk_q, blk_k, window, masked):
    """One tile's float32 scores: scaled, the bias added, and masked
    where the band's edge crosses the tile."""
    s = _qk(q, k) * scale                               # [G, bq, bk]
    if b is not None:
        # per-head: [G,bq,bk]; per-batch: [1,bq,bk] broadcasts over G
        s = s + b.astype(jnp.float32)
    if masked:
        s = _causal_mask(s, j, kk, blk_q, blk_k, window)
    return s


def _bias_tile(b_ref, rows, cols):
    """The bias block's [gb, rows, cols] tile; ``rows`` and ``cols``
    are slices of the block's last two axes."""
    return None if b_ref is None else b_ref[:, 0, rows, cols]


def _k_walk(j, major, *, blk_q, blk_k, k_major, n_q, n_k, causal,
            window):
    """(lo, hi, first_in, end_in) of the k-blocks that q-block ``j``
    walks in major block ``major`` of its keys."""
    per = k_major // blk_k
    k_lo, k_hi, _, _ = _band(blk_q, blk_k, n_q, n_k, causal, window)
    k_first, k_end, _, _ = _inside(blk_q, blk_k, causal, window)
    return (jnp.maximum(k_lo(j), major * per),
            jnp.minimum(k_hi(j), major * per + per - 1),
            k_first and k_first(j), k_end and k_end(j))


def _lane_tile(x, n):
    """``x`` [G, rows, 128], every lane of a row alike, as [G, rows, n]:
    whole copies side by side where n is whole lane groups (no lane is
    moved), one lane spread otherwise."""
    if n % 128 == 0:
        return x if n == 128 else jnp.concatenate([x] * (n // 128), -1)
    return jnp.broadcast_to(x[..., :1], x.shape[:-1] + (n,))


def _lane_fold(p):
    """[G, rows, n] -> [G, rows, 128] whose lanes sum to the rows' sums:
    the n / 128 lane groups added onto each other, vector adds that
    move no lane; of a ragged n, the rows' sums in lane 0."""
    n = p.shape[-1]
    if n % 128 == 0:
        return functools.reduce(
            lambda a, b: a + b,
            [p[..., g:g + 128] for g in range(0, n, 128)])
    lane = lax.broadcasted_iota(jnp.int32, p.shape[:-1] + (128,), 2)
    return jnp.where(lane == 0, jnp.sum(p, -1, keepdims=True), 0.0)


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, b_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, blk_q, blk_k, k_major,
                n_q, n_k, n_steps, rate, causal, window):
    """m rides lane-replicated [G, blk_q, 128] and meets the scores by
    whole copies (_lane_tile); l rides as 128 partial sums a row
    (_lane_fold) that alpha, lane-replicated, rescales as it would
    their sum, and is summed over the lanes once a q-block. So a step
    reduces over the lanes once (its max) and spreads one column.
    Spreading m, l and alpha from one lane each, every step, cost more
    than the step's products (13.1 -> 7.3 ms a call at 32 x 8,192 x
    192 / 128: my chip runs, PR 35, calls 1 and 2)."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    st = pl.program_id(2)
    K_lo, _, _, _ = _band(blk_q, k_major, n_q, n_k * blk_k // k_major,
                          causal, window)
    major = K_lo(j) + st

    @pl.when(st == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(kk, masked):
        at = pl.multiple_of((kk - major * (k_major // blk_k)) * blk_k,
                            blk_k)
        k, v = _rows(k_ref, at, blk_k), _rows(v_ref, at, blk_k)
        s = _scores(q_ref[...], k,
                    _bias_tile(b_ref, slice(None), pl.ds(at, blk_k)),
                    j, kk, scale=scale, blk_q=blk_q, blk_k=blk_k,
                    window=window, masked=masked)
        m_prev = m_ref[...]                             # [G, bq, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _lane_tile(m_new, blk_k))
        l_ref[...] = alpha * l_ref[...] + _lane_fold(p)
        m_ref[...] = m_new
        if rate > 0.0:
            keep = _dropout_keep(seed_ref, i, j, kk, n_q, n_k,
                                 p.shape, rate)
            p = jnp.where(keep, p / (1.0 - rate), 0.0)
        acc_ref[...] = acc_ref[...] * _lane_tile(
            alpha, acc_ref.shape[-1]) + _pv(p.astype(v.dtype), v)

    _walk(*_k_walk(j, major, blk_q=blk_q, blk_k=blk_k, k_major=k_major,
                   n_q=n_q, n_k=n_k, causal=causal, window=window),
          step)

    @pl.when(st == n_steps - 1)
    def _finish():
        l = jnp.sum(l_ref[...], -1, keepdims=True)      # [G, bq, 1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        # the columns are lane-replicated [G, blk_q, 128]; what leaves
        # is one row of lanes a head
        lse = m_ref[...] + jnp.log(l_safe)
        for g in range(lse.shape[0]):
            lse_ref[g] = jnp.transpose(lse[g])[:1]


def _prep_bias(bias, B, H, Sq, Sk):
    """Normalize an additive mask for the kernels. Returns
    (bias array, per_head): per-BATCH biases stay [B, 1, Sq, Sk] and a
    grid cell of G rows indexes batch (i*G)//H; a per-HEAD bias
    [B, H, Sq, Sk] reshapes to [B*H, 1, Sq, Sk] and blocks G rows
    directly — both paths are G-consistent because G divides H."""
    if bias is None:
        return None, False
    if _per_head(bias, H):
        return (jnp.broadcast_to(bias, (B, H, Sq, Sk))
                .reshape(B * H, 1, Sq, Sk)), True
    return jnp.broadcast_to(bias, (B, 1, Sq, Sk)), False


def _per_head(bias, H):
    """Whether a bias has a slab for every head."""
    return bias is not None and bias.ndim == 4 and bias.shape[1] == H \
        and H > 1


def _without_bias(kernel, bias):
    """``kernel`` with ``None`` for its bias block where there is no
    bias operand (the fifth: after the seed, q, k and v)."""
    if bias is not None:
        return kernel
    return lambda sr, qr, kr, vr, *rest, **kw: kernel(
        sr, qr, kr, vr, None, *rest, **kw)


def _mosaic(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_BLOCKED_VMEM_LIMIT)


def _k_major_specs(sched, q, k, v, bias, per_head, causal, window):
    """What the forward and the dq kernel share: grid (cells, q-blocks,
    major blocks of a q-block's band), and the specs and operands of
    the seed's successors q, k, v and the bias. Returns (grid axes'
    lengths, specs, operands, the index map of a q-side block)."""
    B, H, Sq, Dh = q.shape
    Hkv, Sk, Dv = k.shape[1], k.shape[2], v.shape[3]
    G, gk, reps, blk_q, _, k_major = sched[:6]
    hb = H // G                    # cells per batch row
    n_q, n_major = Sq // blk_q, Sk // k_major
    K_lo, K_hi, _, _ = _band(blk_q, k_major, n_q, n_major, causal,
                             window)
    n_steps = _n_steps(blk_q, k_major, n_major, window)

    def mj(j, st):
        return jnp.minimum(K_lo(j) + st, K_hi(j))

    def qi(i, j, st):
        return i, j, 0

    def ki(i, j, st):
        return i // reps, mj(j, st), 0

    specs = [pl.BlockSpec((G, blk_q, Dh), qi),
             pl.BlockSpec((gk, k_major, Dh), ki),
             pl.BlockSpec((gk, k_major, Dv), ki)]
    args = [q.reshape(B * H, Sq, Dh), k.reshape(B * Hkv, Sk, Dh),
            v.reshape(B * Hkv, Sk, Dv)]
    if bias is not None:
        specs.append(pl.BlockSpec(
            (G if per_head else 1, 1, blk_q, k_major),
            lambda i, j, st: (i if per_head else i // hb, 0, j,
                              mj(j, st))))
        args.append(bias)
    return (B * H // G, n_q, n_steps), specs, args, qi


# Jitted for what the 1k wrappers are jitted for: the sites of one
# signature share one lowered Mosaic body, so the forward the executor
# lowers twice (once more under jax.vjp) is one call after XLA's CSE.
@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _flash_fwd(q, k, v, bias, seed_f, scale, rate, causal, window=0):
    """q [B,H,Sq,Dh], k [B,Hkv,Sk,Dh], v [B,Hkv,Sk,Dv] -> out
    [B,H,Sq,Dv] and the rows' lse [B*H,1,Sq] float32."""
    B, H, Sq, _ = q.shape
    Sk, Dv = k.shape[2], v.shape[3]
    bias, per_head = _prep_bias(bias, B, H, Sq, Sk)
    sched = _blocked_geometry(q, k, v, bias, per_head)
    G, blk_q, blk_k = sched.G, sched.blk_q, sched.blk_k
    grid, specs, args, qi = _k_major_specs(sched, q, k, v, bias,
                                           per_head, causal, window)

    out, lse = pl.pallas_call(
        functools.partial(
            _without_bias(_fwd_kernel, bias), scale=scale, blk_q=blk_q,
            blk_k=blk_k, k_major=sched.k_major, n_q=Sq // blk_q,
            n_k=Sk // blk_k, n_steps=grid[2], rate=rate, causal=causal,
            window=window),
        out_shape=[jax.ShapeDtypeStruct((B * H, Sq, Dv), q.dtype),
                   jax.ShapeDtypeStruct((B * H, 1, Sq), jnp.float32)],
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + specs,
        out_specs=[
            pl.BlockSpec((G, blk_q, Dv), qi),
            pl.BlockSpec((G, 1, blk_q), lambda i, j, st: (i, 0, j)),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, blk_q, Dv), jnp.float32),
            pltpu.VMEM((G, blk_q, 128), jnp.float32),
            pltpu.VMEM((G, blk_q, 128), jnp.float32),
        ],
        compiler_params=_mosaic("parallel", "parallel", "arbitrary"),
        interpret=interpret_mode(),
    )(_seed_smem(seed_f, G), *args)
    return out.reshape(B, H, Sq, Dv), lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _bwd_tile(seed_ref, i, j, kk, q, k, v, b, do, lse, delta, *, scale,
              blk_q, blk_k, n_q, n_k, rate, window, masked):
    """The one recompute of a [G, blk_q, blk_k] tile (``lse`` and
    ``delta`` as wide, by whole copies of their lane-replicated
    columns): (pd, ds) in the operands' dtype, pd the probabilities as
    the forward's PV product saw them (dropped and rescaled under
    dropout) for dV, ds the scores' gradient for dQ and dK."""
    s = _scores(q, k, b, j, kk, scale=scale, blk_q=blk_q, blk_k=blk_k,
                window=window, masked=masked)
    p = jnp.exp(s - lse)                          # [G, blk_q, blk_k]
    dp = _qk(do, v)
    pd = p
    if rate > 0.0:
        keep = _dropout_keep(seed_ref, i, j, kk, n_q, n_k, p.shape,
                             rate)
        pd = jnp.where(keep, p / (1.0 - rate), 0.0)
        dp = jnp.where(keep, dp / (1.0 - rate), 0.0)
    ds = (p * (dp - delta) * scale).astype(q.dtype)
    return pd.astype(do.dtype), ds


def _q_block_backward(seed_ref, i, j, major, q_ref, k_ref, v_ref, b_ref,
                      do_ref, lse_ref, dl_ref, dq_acc, lse_col, dl_col,
                      sums, *, k_major, **kw):
    """One q-block against the k-blocks of its band in the major block
    ``k_ref`` / ``v_ref`` hold: dq into ``dq_acc`` and, where ``sums``
    = (dk, dv) float32 sums of the major block's rows, dk and dv into
    them (the fused kernel)."""
    blk_k = kw["blk_k"]
    _columns(lse_ref, lse_col)
    _columns(dl_ref, dl_col)
    tile = {n: kw[n] for n in ("scale", "blk_q", "blk_k", "n_q", "n_k",
                               "rate", "window")}

    def step(kk, masked):
        at = pl.multiple_of((kk - major * (k_major // blk_k)) * blk_k,
                            blk_k)
        rows = pl.ds(at, blk_k)
        k, v = _rows(k_ref, at, blk_k), _rows(v_ref, at, blk_k)
        q, do = q_ref[...], do_ref[...]
        pd, ds = _bwd_tile(
            seed_ref, i, j, kk, q, k, v,
            _bias_tile(b_ref, slice(None), rows),
            do, _lane_tile(lse_col[...], blk_k),
            _lane_tile(dl_col[...], blk_k), masked=masked, **tile)
        dq_acc[...] += _pv(ds, k)
        if sums is not None:
            dk_acc, dv_acc = sums
            gk = k.shape[0]
            dv_acc[:, rows, :] += _tt(pd, do, gk)
            dk_acc[:, rows, :] += _tt(ds, q, gk)

    _walk(*_k_walk(j, major, blk_q=kw["blk_q"], blk_k=blk_k,
                   k_major=k_major, n_q=kw["n_q"], n_k=kw["n_k"],
                   causal=kw["causal"], window=kw["window"]), step)


def _dq_kernel(seed_ref, q_ref, k_ref, v_ref, b_ref, do_ref, lse_ref,
               dl_ref, dq_ref, dq_acc, lse_col, dl_col, *, n_steps,
               **kw):
    i = pl.program_id(0)
    j = pl.program_id(1)
    st = pl.program_id(2)
    K_lo, _, _, _ = _band(
        kw["blk_q"], kw["k_major"], kw["n_q"],
        kw["n_k"] * kw["blk_k"] // kw["k_major"], kw["causal"],
        kw["window"])

    @pl.when(st == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    _q_block_backward(seed_ref, i, j, K_lo(j) + st, q_ref, k_ref, v_ref,
                      b_ref, do_ref, lse_ref, dl_ref, dq_acc, lse_col,
                      dl_col, None, **kw)

    @pl.when(st == n_steps - 1)
    def _finish():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_fused_kernel(seed_ref, q_ref, k_ref, v_ref, b_ref, do_ref,
                      lse_ref, dl_ref, dq_ref, dk_ref, dv_ref, dq_acc,
                      lse_col, dl_col, dk_acc, dv_acc, *, reps, **kw):
    """Grid (kv cells, the q-blocks of the kv head's ``reps`` cells of
    query heads): K, V and the float32 sums of dK and dV of the whole
    key range stay; each step writes one block of dq."""
    n_q = kw["n_q"]
    c = pl.program_id(0)
    u = pl.program_id(1)

    @pl.when(u == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    dq_acc[...] = jnp.zeros_like(dq_acc)
    _q_block_backward(seed_ref, c * reps + u // n_q, u % n_q, 0, q_ref,
                      k_ref, v_ref, b_ref, do_ref, lse_ref, dl_ref,
                      dq_acc, lse_col, dl_col, (dk_acc, dv_acc), **kw)
    dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)

    @pl.when(u == reps * n_q - 1)
    def _finish():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _dkv_kernel(seed_ref, q_ref, k_ref, v_ref, b_ref, do_ref, lse_ref,
                dl_ref, dk_ref, dv_ref, dk_acc, dv_acc, lse_col, dl_col,
                *, scale, blk_q, blk_k, q_major, n_q, n_k, n_steps,
                reps, rate, causal, window):
    """The mirror image: grid (kv cells, k-blocks, the major blocks of
    query rows of the kv head's ``reps`` cells): a k-block and its
    sums stay, the q-blocks of its band in the major block are walked
    inside."""
    c = pl.program_id(0)
    kk = pl.program_id(1)
    u = pl.program_id(2)
    per = q_major // blk_q
    _, _, J_lo, _ = _band(q_major, blk_k, n_q // per, n_k, causal,
                          window)
    _, _, j_lo, j_hi = _band(blk_q, blk_k, n_q, n_k, causal, window)
    _, _, j_first, j_end = _inside(blk_q, blk_k, causal, window)
    i = c * reps + u // n_steps
    major = J_lo(kk) + u % n_steps
    gk = k_ref.shape[0]

    @pl.when(u == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(j, masked):
        at = pl.multiple_of((j - major * per) * blk_q, blk_q)
        rows = pl.ds(at, blk_q)
        _columns(lse_ref, lse_col, at)
        _columns(dl_ref, dl_col, at)
        q, do = q_ref[:, rows, :], do_ref[:, rows, :]
        pd, ds = _bwd_tile(
            seed_ref, i, j, kk, q, k_ref[...], v_ref[...],
            _bias_tile(b_ref, rows, slice(None)), do,
            _lane_tile(lse_col[...], blk_k),
            _lane_tile(dl_col[...], blk_k), scale=scale, blk_q=blk_q,
            blk_k=blk_k, n_q=n_q, n_k=n_k, rate=rate, window=window,
            masked=masked)
        dv_acc[...] += _tt(pd, do, gk)
        dk_acc[...] += _tt(ds, q, gk)

    _walk(jnp.maximum(j_lo(kk), major * per),
          jnp.minimum(j_hi(kk), major * per + per - 1),
          j_first and j_first(kk), j_end and j_end(kk), step)

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
    sched = _blocked_geometry(q, k, v, bias, per_head)
    G, gk, reps, blk_q, blk_k = sched[:5]
    hb = H // G
    n_q, n_k = Sq // blk_q, Sk // blk_k
    seed = _seed_smem(seed_f, G)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    do3 = g.reshape(BH, Sq, Dv)
    # delta_i = rowsum(dO * O): O(S*Dh) elementwise work, XLA fuses
    # it. lse and delta enter the kernels as they are, a row a head.
    delta = jnp.sum(do3.astype(jnp.float32) * o.reshape(BH, Sq, Dv)
                    .astype(jnp.float32), axis=-1)[:, None, :]
    common = dict(scale=scale, blk_q=blk_q, blk_k=blk_k, n_q=n_q,
                  n_k=n_k, rate=rate, causal=causal, window=window)
    cols = [pltpu.VMEM((G, blk_q, 128), jnp.float32)] * 2
    kv_shapes = [jax.ShapeDtypeStruct((BHkv, Sk, Dh), k.dtype),
                 jax.ShapeDtypeStruct((BHkv, Sk, Dv), v.dtype)]
    back = lambda dq, dk, dv: (                         # noqa: E731
        dq.reshape(B, H, Sq, Dh), dk.reshape(B, Hkv, Sk, Dh),
        dv.reshape(B, Hkv, Sk, Dv))

    if sched.fused:
        def at(c, u):
            return c * reps + u // n_q, u % n_q

        def qi(c, u):
            return (*at(c, u), 0)

        def si(c, u):
            i, j = at(c, u)
            return i, 0, j

        def ki(c, u):
            return c, 0, 0

        kspec = [pl.BlockSpec((gk, Sk, Dh), ki),
                 pl.BlockSpec((gk, Sk, Dv), ki)]
        specs = [smem, pl.BlockSpec((G, blk_q, Dh), qi)] + kspec
        args = [seed, q.reshape(BH, Sq, Dh), k.reshape(BHkv, Sk, Dh),
                v.reshape(BHkv, Sk, Dv)]
        if bias is not None:
            specs.append(pl.BlockSpec(
                (G if per_head else 1, 1, blk_q, Sk),
                lambda c, u: (at(c, u)[0] if per_head
                              else at(c, u)[0] // hb, 0, at(c, u)[1], 0)))
            args.append(bias)
        specs += [pl.BlockSpec((G, blk_q, Dv), qi),
                  pl.BlockSpec((G, 1, blk_q), si),
                  pl.BlockSpec((G, 1, blk_q), si)]
        return back(*pl.pallas_call(
            functools.partial(_without_bias(_bwd_fused_kernel, bias),
                              reps=reps, k_major=Sk, **common),
            out_shape=[jax.ShapeDtypeStruct((BH, Sq, Dh), q.dtype)]
            + kv_shapes,
            grid=(BHkv // gk, reps * n_q),
            in_specs=specs,
            out_specs=[pl.BlockSpec((G, blk_q, Dh), qi)] + kspec,
            scratch_shapes=[pltpu.VMEM((G, blk_q, Dh), jnp.float32)]
            + cols + [pltpu.VMEM((gk, Sk, Dh), jnp.float32),
                      pltpu.VMEM((gk, Sk, Dv), jnp.float32)],
            compiler_params=_mosaic("parallel", "arbitrary"),
            interpret=interpret_mode(),
        )(*args, do3, lse, delta))

    grid, specs, args, qi = _k_major_specs(sched, q, k, v, bias,
                                           per_head, causal, window)
    stat = pl.BlockSpec((G, 1, blk_q), lambda i, j, st: (i, 0, j))
    dq = pl.pallas_call(
        functools.partial(_without_bias(_dq_kernel, bias),
                          k_major=sched.k_major, n_steps=grid[2],
                          **common),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, Dh), q.dtype),
        grid=grid,
        in_specs=[smem] + specs
        + [pl.BlockSpec((G, blk_q, Dv), qi), stat, stat],
        out_specs=pl.BlockSpec((G, blk_q, Dh), qi),
        scratch_shapes=[pltpu.VMEM((G, blk_q, Dh), jnp.float32)] + cols,
        compiler_params=_mosaic("parallel", "parallel", "arbitrary"),
        interpret=interpret_mode(),
    )(seed, *args, do3, lse, delta)

    q_major = sched.q_major
    n_major = Sq // q_major
    _, _, J_lo, J_hi = _band(q_major, blk_k, n_major, n_k, causal,
                             window)
    nj_steps = _n_steps(blk_k, q_major, n_major, window)

    def at(c, kk, u):
        return (c * reps + u // nj_steps,
                jnp.minimum(J_lo(kk) + u % nj_steps, J_hi(kk)))

    def qi(c, kk, u):
        return (*at(c, kk, u), 0)

    def si(c, kk, u):
        i, mj = at(c, kk, u)
        return i, 0, mj

    def ki(c, kk, u):
        return c, kk, 0

    kspec = [pl.BlockSpec((gk, blk_k, Dh), ki),
             pl.BlockSpec((gk, blk_k, Dv), ki)]
    specs = [smem, pl.BlockSpec((G, q_major, Dh), qi)] + kspec
    if bias is not None:
        specs.append(pl.BlockSpec(
            (G if per_head else 1, 1, q_major, blk_k),
            lambda c, kk, u: (at(c, kk, u)[0] if per_head
                              else at(c, kk, u)[0] // hb, 0,
                              at(c, kk, u)[1], kk)))
    specs += [pl.BlockSpec((G, q_major, Dv), qi),
              pl.BlockSpec((G, 1, q_major), si),
              pl.BlockSpec((G, 1, q_major), si)]
    dk, dv = pl.pallas_call(
        functools.partial(_without_bias(_dkv_kernel, bias),
                          q_major=q_major, n_steps=nj_steps, reps=reps,
                          **common),
        out_shape=kv_shapes,
        grid=(BHkv // gk, n_k, reps * nj_steps),
        in_specs=specs,
        out_specs=kspec,
        scratch_shapes=[pltpu.VMEM((gk, blk_k, Dh), jnp.float32),
                        pltpu.VMEM((gk, blk_k, Dv), jnp.float32)] + cols,
        compiler_params=_mosaic("parallel", "parallel", "arbitrary"),
        interpret=interpret_mode(),
    )(seed, *args, do3, lse, delta)
    return back(dq, dk, dv)


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
    else:
        if heads_last:
            q, k, v = (_split_heads(x, dh) for x in (q, k, v))
        _count_schedule(_blocked_geometry(q, k, v, bias,
                                          _per_head(bias, H)))
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


def _count_schedule(sched):
    """``flash_schedule.kv_resident`` or ``.kv_streamed`` and
    ``flash_backward.fused`` or ``.split`` beside a blocked site's
    ``sdpa_lowering.*``: what _blocked_schedule read off its shape, the
    one not taken listed with 0."""
    count_lowering("flash_schedule.kv_resident",
                   float(sched.kv_resident))
    count_lowering("flash_schedule.kv_streamed",
                   float(not sched.kv_resident))
    count_lowering("flash_backward.fused", float(sched.fused))
    count_lowering("flash_backward.split", float(not sched.fused))


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
