"""Fused vocabulary-projection + softmax cross-entropy pallas kernel.

The analog of the reference's fused logits-loss chain
(operators/math/cross_entropy.cu + the operators/fused/ pattern): every
NMT/LM model ends in ``fc(d_model -> V) + label_smooth + softmax_xent``
whose [N, V] logits (N = batch*seq, V ~ 30k) are by far the largest
activation in the model — at transformer-base flagship shape the bf16
logits alone are ~1 GB/step of HBM writes that XLA then re-reads for
the log-softmax. This kernel streams vocabulary blocks through VMEM and
reduces them online (flash-attention-style running logsumexp), so the
logits never reach HBM at all. Only the per-row logsumexp ([N, 1]) is
saved for the backward, which recomputes the logits blockwise — XLA
fuses the softmax-minus-target epilogue into the recompute matmul, so
the backward materializes exactly one [N, V] bf16 array (the scaled
gradient) instead of logits + softmax + dlogits.

Grid layout: vocab-major ``(nvj, ni)`` so each W block ([D, bv]) loads
once total while X row blocks re-stream per vocab block — W is the
big operand (D*V), X the small one (N*D), so this order minimizes HBM
traffic.

Rows ride the LANE axis everywhere outside the matmul: TPU VMEM tiles
are (8, 128), so a ``[N, 1]`` f32 buffer is lane-padded 128x (8 MB at
N=16k — the scoped-VMEM OOM observed on chip in round 4). Running
statistics therefore live in ``(ni, bn)`` scratch indexed ``(1, bn)``
per row block, the logits block is computed TRANSPOSED ``[bv, bn]``
(``dot_general`` contracting D on both operands), and all row
reductions are axis-0 — lane-major stats with no relayouts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..registry import get, register_variant
from .common import blk, interpret_mode


def _fwd_kernel(x_ref, w_ref, lab_ref, loss_ref, lse_ref,
                m_sc, z_sc, s_sc, p_sc, *, V, eps, nvj):
    j = pl.program_id(0)
    i = pl.program_id(1)
    row = (pl.ds(i, 1), slice(None))     # (1, bn) stats slice

    # transposed block [bv, bn]: contract D of w [D, bv] with D of
    # x [bn, D] so rows land on lanes and every reduction is axis-0
    logits = jax.lax.dot_general(
        w_ref[:], x_ref[:], (((0,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                # [bv, bn]
    bv = logits.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0) + j * bv
    valid = col < V                      # mask the padded vocab tail

    @pl.when(j == 0)
    def _init():
        bn = logits.shape[1]
        m_sc[row] = jnp.full((1, bn), -jnp.inf, jnp.float32)
        z_sc[row] = jnp.zeros((1, bn), jnp.float32)
        s_sc[row] = jnp.zeros((1, bn), jnp.float32)
        p_sc[row] = jnp.zeros((1, bn), jnp.float32)

    m_old = m_sc[row]
    blk_max = jnp.max(jnp.where(valid, logits, -jnp.inf), axis=0,
                      keepdims=True)
    m_new = jnp.maximum(m_old, blk_max)
    e = jnp.where(valid, jnp.exp(logits - m_new), 0.0)
    z_sc[row] = z_sc[row] * jnp.exp(m_old - m_new) \
        + jnp.sum(e, axis=0, keepdims=True)
    m_sc[row] = m_new
    s_sc[row] = s_sc[row] + jnp.sum(jnp.where(valid, logits, 0.0),
                                    axis=0, keepdims=True)
    lab = lab_ref[:]                                       # [1, bn]
    p_sc[row] = p_sc[row] + jnp.sum(
        jnp.where(col == lab, logits, 0.0), axis=0, keepdims=True)

    @pl.when(j == nvj - 1)
    def _finish():
        lse = m_sc[row] + jnp.log(z_sc[row])
        lse_ref[:] = lse
        # loss = lse - (1-eps)*logit[y] - eps/V * sum(logits)
        loss_ref[:] = (lse - (1.0 - eps) * p_sc[row]
                       - (eps / V) * s_sc[row])


def _fwd_call(x2, w, lab2, eps):
    N, D = x2.shape
    V = w.shape[-1]
    bn = blk(N, 512)
    ni = N // bn
    # bv=1024: the 2048 block's f32 working set (double-buffered W
    # block + transposed logits + exp) hit 16.11M scoped VMEM on chip,
    # 112K over the 16M stack limit
    bv = min(1024, -(-V // 128) * 128)
    nvj = -(-V // bv)
    Vp = nvj * bv
    if Vp > V:
        w = jnp.pad(w, ((0, 0), (0, Vp - V)))
    lab_row = lab2.reshape(1, N)
    kernel = functools.partial(_fwd_kernel, V=V, eps=eps, nvj=nvj)
    # outputs are lane-major [1, N]: a (1, bn) block over an (ni, bn)
    # array is ILLEGAL on the TPU lowering (sublane block dim 1 is
    # neither 8-divisible nor the full dim); over (1, N) it is exact in
    # the sublane and 128-divisible in the lane
    loss, lse = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((1, N), jnp.float32),
                   jax.ShapeDtypeStruct((1, N), jnp.float32)),
        grid=(nvj, ni),
        in_specs=[pl.BlockSpec((bn, D), lambda j, i: (i, 0),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((D, bv), lambda j, i: (0, j),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((1, bn), lambda j, i: (0, i),
                               memory_space=pltpu.VMEM)],
        out_specs=(pl.BlockSpec((1, bn), lambda j, i: (0, i),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((1, bn), lambda j, i: (0, i),
                                memory_space=pltpu.VMEM)),
        scratch_shapes=[pltpu.VMEM((ni, bn), jnp.float32)] * 4,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * N * D * Vp, transcendentals=N * Vp,
            bytes_accessed=(N * D * nvj + D * Vp) * x2.dtype.itemsize),
        interpret=interpret_mode(),
    )(x2, w, lab_row)
    return loss.reshape(N, 1), lse.reshape(N, 1)  # [1, N] -> [N, 1]


@functools.lru_cache(maxsize=None)
def _fused(eps):
    @jax.custom_vjp
    def f(x2, w, lab2):
        return _fwd_call(x2, w, lab2, eps)[0]

    def fwd(x2, w, lab2):
        loss, lse = _fwd_call(x2, w, lab2, eps)
        return loss, (x2, w, lab2, lse)

    def bwd(res, g):
        # Recompute the logits blockwise-in-XLA: the exp/subtract
        # epilogue fuses into the matmul, so only the scaled gradient
        # G ([N, V], input dtype) is ever materialized.
        x2, w, lab2, lse = res
        V = w.shape[-1]
        logits = jnp.dot(x2, w, preferred_element_type=jnp.float32)
        y = jax.nn.one_hot(lab2[:, 0], V, dtype=jnp.float32)
        p = jnp.exp(logits - lse)
        G = ((p - eps / V - (1.0 - eps) * y)
             * g.astype(jnp.float32)).astype(x2.dtype)
        dx = jnp.dot(G, w.T)
        dw = jnp.dot(x2.T, G)
        return dx, dw, None

    f.defvjp(fwd, bwd)
    return f


@register_variant("fused_linear_xent", "pallas")
def fused_linear_xent_pallas(x, w, label, *, epsilon=0.0):
    N = 1
    for d in x.shape[:-1]:
        N *= d
    # four (ni, bn) f32 running-stat buffers (N packed along lanes,
    # 4 bytes/row each) must fit VMEM scratch
    if N * 16 > (2 << 20):
        return get("fused_linear_xent").fn(x, w, label,
                                           epsilon=epsilon)
    x2 = x.reshape(N, x.shape[-1])
    lab2 = label.reshape(N, 1).astype(jnp.int32)
    loss = _fused(float(epsilon))(x2, w, lab2)
    return loss.reshape(x.shape[:-1] + (1,))
