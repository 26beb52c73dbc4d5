"""Ring attention: sequence/context parallelism over the ``sp`` mesh
axis.

Not present in the 2019 reference (SURVEY §5 "long-context") — this is
a new TPU-first capability: sequences longer than one chip's HBM are
sharded over the mesh's ``sp`` axis; each device holds a query block
and the key/value blocks rotate around the ring with
``lax.ppermute`` (one ICI hop per step) while a numerically-stable
online softmax accumulates the attention output. Compute for block i
overlaps the transfer of block i+1 (XLA schedules the ppermute ahead),
so the ring cost hides behind the matmuls at transformer scale.

Composable three ways:
  - pure function ``ring_attention(q, k, v, ...)`` over globally
    sharded arrays (shard_map under the hood);
  - registered op ``ring_attention`` for static Programs (falls back
    to single-device fused attention when no sp axis is in scope);
  - inside user shard_map code via ``ring_attention_inner``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..ops.registry import register
from . import mesh as mesh_lib

_NEG = -1.0e30


def ring_attention_inner(q, k, v, *, axis_name, n_blocks, scale=1.0,
                         causal=False, bias_blk=None):
    """Per-shard body (call inside shard_map/pmap). q,k,v: local
    [B, H, S_loc, Dh] blocks of the sequence-sharded arrays."""
    B, H, Sq, Dh = q.shape
    Sk = k.shape[2]
    my = jax.lax.axis_index(axis_name)

    m = jnp.full((B, H, Sq, 1), _NEG, jnp.float32)
    l = jnp.zeros((B, H, Sq, 1), jnp.float32)
    acc = jnp.zeros((B, H, Sq, Dh), jnp.float32)
    perm = [(j, (j + 1) % n_blocks) for j in range(n_blocks)]

    q32 = q.astype(jnp.float32)
    for step in range(n_blocks):
        src = (my - step) % n_blocks  # whose k/v block we hold now
        s = jnp.einsum("bhqd,bhkd->bhqk", q32,
                       k.astype(jnp.float32)) * scale
        if bias_blk is not None:
            s = s + bias_blk
        if causal:
            q_pos = my * Sq + jax.lax.broadcasted_iota(
                jnp.int32, (Sq, Sk), 0)
            k_pos = src * Sk + jax.lax.broadcasted_iota(
                jnp.int32, (Sq, Sk), 1)
            s = jnp.where(k_pos <= q_pos, s, _NEG)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
        m = m_new
        if step != n_blocks - 1:
            k = jax.lax.ppermute(k, axis_name, perm)
            v = jax.lax.ppermute(v, axis_name, perm)
    out = acc / jnp.maximum(l, 1e-20)
    return out.astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def ring_attention_inner_flash(q, k, v, axis_name, n_blocks, scale,
                               causal):
    """Flash ring body: per-hop scores stay in VMEM (ops/pallas/
    ring.py kernels); only the O(Sq*Dh) online-softmax rescale and
    the [.., Sq] stats touch HBM per hop."""
    out, _ = _ring_flash_fwd(q, k, v, axis_name, n_blocks, scale,
                             causal)
    return out


def _ring_flash_fwd(q, k, v, axis_name, n_blocks, scale, causal):
    from ..ops.pallas import ring as R
    from .zigzag import online_merge_nk

    B, H, Sq, Dh = q.shape
    Sk = k.shape[2]
    my = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n_blocks) for j in range(n_blocks)]
    k0, v0 = k, v

    m = jnp.full((B, H, Sq), -1.0e30, jnp.float32)
    l = jnp.zeros((B, H, Sq), jnp.float32)
    acc = jnp.zeros((B, H, Sq, Dh), jnp.float32)
    for step in range(n_blocks):
        src = (my - step) % n_blocks
        pv, mb, lb = R.fwd_block(q, k, v, my * Sq, src * Sk, scale,
                                 causal)
        acc, m, l = online_merge_nk(acc, m, l, pv, mb, lb)
        if step != n_blocks - 1:
            k = jax.lax.ppermute(k, axis_name, perm)
            v = jax.lax.ppermute(v, axis_name, perm)
    l_safe = jnp.maximum(l, 1e-20)
    out = (acc / l_safe[..., None]).astype(q.dtype)
    lse = m + jnp.log(l_safe)
    return out, (q, k0, v0, out, lse)


def _ring_flash_bwd(axis_name, n_blocks, scale, causal, res, g):
    from ..ops.pallas import ring as R

    q, k, v, out, lse = res
    B, H, Sq, Dh = q.shape
    Sk = k.shape[2]
    my = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n_blocks) for j in range(n_blocks)]
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    dq = jnp.zeros((B, H, Sq, Dh), jnp.float32)
    dk_acc = jnp.zeros((B, H, Sk, Dh), jnp.float32)
    dv_acc = jnp.zeros((B, H, Sk, Dh), jnp.float32)
    # dk/dv accumulators TRAVEL WITH their k/v block: each device adds
    # its hop's contribution, then the 4-tuple rotates. After n
    # permutes (one per hop, INCLUDING the last) block b's accumulator
    # has every device's contribution and is back home at device b.
    for step in range(n_blocks):
        src = (my - step) % n_blocks
        dq_b, dk_b, dv_b = R.bwd_block(q, k, v, g, lse, delta,
                                       my * Sq, src * Sk, scale,
                                       causal)
        dq = dq + dq_b
        dk_acc = dk_acc + dk_b
        dv_acc = dv_acc + dv_b
        if step != n_blocks - 1:
            # k/v are never read after the last hop — only the
            # accumulators need the final rotation home
            k = jax.lax.ppermute(k, axis_name, perm)
            v = jax.lax.ppermute(v, axis_name, perm)
        dk_acc = jax.lax.ppermute(dk_acc, axis_name, perm)
        dv_acc = jax.lax.ppermute(dv_acc, axis_name, perm)
    return (dq.astype(q.dtype), dk_acc.astype(k.dtype),
            dv_acc.astype(v.dtype))


ring_attention_inner_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_attention(q, k, v, mesh=None, axis="sp", scale=1.0,
                   causal=False, use_flash=None):
    """Global-view entry: q,k,v [B, H, S, Dh] (sharded or not — the
    shard_map in_specs place them on the sp axis). use_flash:
    None = auto (pallas hop kernels when the geometry fits and
    FLAGS.ring_flash is on); False forces the jnp body."""
    from jax import shard_map

    from ..core.flags import FLAGS
    from ..ops.pallas import ring as R
    from .ulysses import _full_attention

    mesh = mesh or mesh_lib.current_mesh()
    if mesh is None or axis not in mesh.axis_names \
            or mesh.shape[axis] == 1:
        # no sequence axis in scope: plain fused attention (shared
        # with the ulysses fallback so the numerics can't diverge)
        return _full_attention(q, k, v, scale, causal)

    n = mesh.shape[axis]
    B, H, S, Dh = q.shape
    if use_flash is None:
        use_flash = (FLAGS.ring_flash
                     and S % n == 0
                     and R.applicable(B, H, S // n, S // n, Dh,
                                      q.dtype.itemsize))
    spec = PartitionSpec(None, None, axis, None)
    if use_flash:
        # custom_vjp nondiff args must be POSITIONAL
        def body(q_, k_, v_):
            return ring_attention_inner_flash(q_, k_, v_, axis, n,
                                              scale, causal)
    else:
        body = functools.partial(ring_attention_inner, axis_name=axis,
                                 n_blocks=n, scale=scale,
                                 causal=causal)
    f = shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return f(q, k, v)


@register("ring_attention", ["Q", "K", "V"], ["Out"])
def ring_attention_op(q, k, v, *, scale=1.0, causal=False,
                      axis="sp"):
    """Static-graph op: uses the ambient mesh (set by
    CompiledProgram.run / mesh_guard)."""
    return ring_attention(q, k, v, axis=axis, scale=scale,
                          causal=causal)
