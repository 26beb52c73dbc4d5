"""Per-hop flash kernels for ring attention (sequence parallelism).

The jnp ring body (parallel/ring_attention.py) materializes a
[B, H, Sq_loc, Sk_loc] score tensor in HBM on EVERY ring hop — at pod
scale (S=32k, sp=32 -> 1k x 1k blocks x n hops) that is the whole HBM
bandwidth budget. These kernels compute one hop's block-attention
partials with the scores living only in VMEM:

  forward:  (pv, m, l) = softmax-partials(q, k_blk, v_blk)
            — unnormalized p@v plus the row max/sum, combined across
            hops by the caller's online-softmax rescale (the O(Sq*Dh)
            rescale stays in jnp: it is tiny next to the O(Sq*Sk)
            scores the kernel keeps on-chip);
  backward: (dq_blk, dk_blk, dv_blk) from a single in-kernel exp
            recompute against the saved global lse and delta =
            rowsum(do * out) — the flash backward identity, per hop.

Absolute q/k sequence offsets ride in SMEM so the causal mask works on
the global positions of the local shards (they are traced values —
lax.axis_index under shard_map).

No reference analog (SURVEY §5 long-context exceeds the 2019
reference); kernel discipline follows ops/pallas/attention.py: VMEM
budget model chooses the row group G and q block, with a plain-jnp
fallback when no geometry fits (caller checks ``applicable``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import interpret_mode

_NEG = -1.0e30

_QK = (((2,), (2,)), ((0,), (0,)))   # [G,q,d]x[G,k,d] -> [G,q,k]
_PV = (((2,), (1,)), ((0,), (0,)))   # [G,q,k]x[G,k,d] -> [G,q,d]
_TT = (((1,), (1,)), ((0,), (0,)))   # [G,q,k]^T contractions

# Same modeling constants as the 1k kernels (attention.py): ~2 f32
# score temporaries live after Mosaic reuse, 15 MB of the 16 MB v5e
# scoped limit.
_TEMP_BYTES = 8
_VMEM_BUDGET = 15 << 20


def _row_bytes(itemsize, blk_q, Sk, Dh, bwd):
    lanes = max(Dh, 128)
    # fwd streams: q,pv rows of blk_q; k,v rows of Sk
    # bwd streams: q,do,dq rows of blk_q; k,v rows of Sk; PLUS the
    # RESIDENT dk/dv f32 accumulator blocks (revisited across q-steps)
    if bwd:
        stream = (3 * blk_q + 2 * Sk) * lanes * itemsize * 2
        stream += 2 * Sk * lanes * 4 * 2
    else:
        stream = (2 * blk_q + 2 * Sk) * lanes * itemsize * 2
    return stream + blk_q * Sk * _TEMP_BYTES


def _pick_geometry(BH, Sq, Sk, Dh, itemsize, bwd):
    """(G, blk_q) fitting the VMEM budget, or None. The row statistics
    (m, l, lse, delta) ride as (G, blk_q) blocks of (BH, Sq) arrays,
    and Mosaic takes a block only when its last two dims divide by
    (8, 128) or span the array: G is 8 (or all of BH), blk_q a
    multiple of 128 (or all of Sq) — so what shrinks to fit is the q
    block, never G below 8 (a (4, 256) block is refused, PR 21)."""
    G = 8 if BH % 8 == 0 else BH
    for blk_q in [b for b in (256, 128) if Sq % b == 0] or [Sq]:
        if G * _row_bytes(itemsize, blk_q, Sk, Dh, bwd) <= _VMEM_BUDGET:
            return G, blk_q
    return None


def applicable(B, H, Sq, Sk, Dh, itemsize):
    """True when both hop kernels have a fitting geometry AND the
    shapes land on natural TPU tiles (no padding logic in the
    kernels)."""
    if Sq % 8 != 0 or Sk % 128 != 0 or Dh % 8 != 0:
        return False
    bh = B * H
    return (_pick_geometry(bh, Sq, Sk, Dh, itemsize, False) is not None
            and _pick_geometry(bh, Sq, Sk, Dh, itemsize, True)
            is not None)


def _causal_mask_s(s, offs_ref, j, blk_q, Sk):
    q_pos = offs_ref[0] + j * blk_q + lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    k_pos = offs_ref[1] + lax.broadcasted_iota(jnp.int32, s.shape, 2)
    return jnp.where(k_pos <= q_pos, s, _NEG)


def _fwd_kernel(offs_ref, q_ref, k_ref, v_ref, pv_ref, m_ref, l_ref,
                *, scale, causal, blk_q, Sk):
    j = pl.program_id(1)
    s = lax.dot_general(q_ref[...].astype(jnp.float32) * scale,
                        k_ref[...].astype(jnp.float32), _QK,
                        preferred_element_type=jnp.float32)
    if causal:
        s = _causal_mask_s(s, offs_ref, j, blk_q, Sk)
    m = jnp.max(s, -1)                                # [G, blk_q]
    p = jnp.exp(s - m[:, :, None])
    p = jnp.where(s <= _NEG / 2, 0.0, p)              # fully-masked rows
    l = jnp.sum(p, -1)
    pv_ref[...] = lax.dot_general(
        p.astype(v_ref.dtype), v_ref[...], _PV,
        preferred_element_type=jnp.float32)
    m_ref[...] = m
    l_ref[...] = l


def _bwd_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, dq_ref, dk_ref, dv_ref, *, scale, causal,
                blk_q, Sk):
    j = pl.program_id(1)
    s = lax.dot_general(q_ref[...].astype(jnp.float32) * scale,
                        k_ref[...].astype(jnp.float32), _QK,
                        preferred_element_type=jnp.float32)
    if causal:
        s = _causal_mask_s(s, offs_ref, j, blk_q, Sk)
    p = jnp.exp(s - lse_ref[...][:, :, None])
    p = jnp.where(s <= _NEG / 2, 0.0, p)
    do = do_ref[...]
    dp = lax.dot_general(do, v_ref[...], _QK,
                         preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[...][:, :, None]) * scale
    dq_ref[...] = lax.dot_general(
        ds.astype(q_ref.dtype), k_ref[...], _PV,
        preferred_element_type=jnp.float32)
    dk = lax.dot_general(ds.astype(q_ref.dtype), q_ref[...], _TT,
                         preferred_element_type=jnp.float32)
    dv = lax.dot_general(p.astype(do.dtype), do, _TT,
                         preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _init():
        dk_ref[...] = dk
        dv_ref[...] = dv

    @pl.when(j > 0)
    def _acc():
        dk_ref[...] += dk
        dv_ref[...] += dv


def fwd_block(q, k, v, q_off, k_off, scale, causal):
    """One ring hop's attention partials. q [B,H,Sq,Dh]; k,v
    [B,H,Sk,Dh]; q_off/k_off traced int32 global offsets. Returns
    (pv [B,H,Sq,Dh] f32 unnormalized, m [B,H,Sq] f32, l [B,H,Sq]
    f32)."""
    B, H, Sq, Dh = q.shape
    Sk = k.shape[2]
    BH = B * H
    geo = _pick_geometry(BH, Sq, Sk, Dh, q.dtype.itemsize, False)
    if geo is None or not applicable(B, H, Sq, Sk, Dh,
                                     q.dtype.itemsize):
        raise ValueError(
            "ring flash kernel has no fitting geometry for "
            "B=%d H=%d Sq=%d Sk=%d Dh=%d itemsize=%d — check "
            "ring.applicable() before forcing use_flash=True"
            % (B, H, Sq, Sk, Dh, q.dtype.itemsize))
    G, blk_q = geo
    n_q = Sq // blk_q
    offs = jnp.stack([jnp.asarray(q_off, jnp.int32),
                      jnp.asarray(k_off, jnp.int32)])
    pv, m, l = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          blk_q=blk_q, Sk=Sk),
        out_shape=(jax.ShapeDtypeStruct((BH, Sq, Dh), jnp.float32),
                   jax.ShapeDtypeStruct((BH, Sq), jnp.float32),
                   jax.ShapeDtypeStruct((BH, Sq), jnp.float32)),
        grid=(BH // G, n_q),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((G, blk_q, Dh), lambda i, j: (i, j, 0)),
            pl.BlockSpec((G, Sk, Dh), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((G, Sk, Dh), lambda i, j: (i, 0, 0)),
        ],
        out_specs=(pl.BlockSpec((G, blk_q, Dh), lambda i, j: (i, j, 0)),
                   pl.BlockSpec((G, blk_q), lambda i, j: (i, j)),
                   pl.BlockSpec((G, blk_q), lambda i, j: (i, j))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret_mode(),
    )(offs, q.reshape(BH, Sq, Dh), k.reshape(BH, Sk, Dh),
      v.reshape(BH, Sk, Dh))
    return (pv.reshape(B, H, Sq, Dh), m.reshape(B, H, Sq),
            l.reshape(B, H, Sq))


def bwd_block(q, k, v, do, lse, delta, q_off, k_off, scale, causal):
    """One ring hop's backward: (dq_blk, dk_blk, dv_blk) f32 from the
    saved lse/delta — the flash backward identity, scores recomputed
    in VMEM."""
    B, H, Sq, Dh = q.shape
    Sk = k.shape[2]
    BH = B * H
    geo = _pick_geometry(BH, Sq, Sk, Dh, q.dtype.itemsize, True)
    if geo is None:
        raise ValueError(
            "ring flash backward has no fitting geometry for "
            "B=%d H=%d Sq=%d Sk=%d Dh=%d itemsize=%d"
            % (B, H, Sq, Sk, Dh, q.dtype.itemsize))
    G, blk_q = geo
    n_q = Sq // blk_q
    offs = jnp.stack([jnp.asarray(q_off, jnp.int32),
                      jnp.asarray(k_off, jnp.int32)])
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, causal=causal,
                          blk_q=blk_q, Sk=Sk),
        out_shape=(jax.ShapeDtypeStruct((BH, Sq, Dh), jnp.float32),
                   jax.ShapeDtypeStruct((BH, Sk, Dh), jnp.float32),
                   jax.ShapeDtypeStruct((BH, Sk, Dh), jnp.float32)),
        grid=(BH // G, n_q),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((G, blk_q, Dh), lambda i, j: (i, j, 0)),
            pl.BlockSpec((G, Sk, Dh), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((G, Sk, Dh), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((G, blk_q, Dh), lambda i, j: (i, j, 0)),
            pl.BlockSpec((G, blk_q), lambda i, j: (i, j)),
            pl.BlockSpec((G, blk_q), lambda i, j: (i, j)),
        ],
        out_specs=(
            pl.BlockSpec((G, blk_q, Dh), lambda i, j: (i, j, 0)),
            pl.BlockSpec((G, Sk, Dh), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((G, Sk, Dh), lambda i, j: (i, 0, 0))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(),
    )(offs, q.reshape(BH, Sq, Dh), k.reshape(BH, Sk, Dh),
      v.reshape(BH, Sk, Dh), do.reshape(BH, Sq, Dh),
      lse.reshape(BH, Sq), delta.reshape(BH, Sq))
    return (dq.reshape(B, H, Sq, Dh), dk.reshape(B, H, Sk, Dh),
            dv.reshape(B, H, Sk, Dh))
