"""Zigzag (load-balanced) ring attention for CAUSAL long-context.

The plain ring (ring_attention.py) is causally imbalanced: with
contiguous sequence shards, device 0 finds every rotated K/V block
masked while device n-1 attends them all — per-tick wall-clock is
gated by the busiest device, so the causal FLOP savings never
materialize. The zigzag layout fixes the balance:

  split S into 2n chunks; device d holds chunks (d, 2n-1-d).

Per ring hop against source s (holding chunks s, 2n-1-s), the four
chunk-pairs classify STATICALLY-BY-COMPARISON:

  (q_a=d,     k_a=s)      full if d>s, diagonal if d==s, empty if d<s
  (q_a=d,     k_b=2n-1-s) always empty   (d < n <= 2n-1-s)
  (q_b=2n-1-d, k_a=s)     always full    (2n-1-d >= n > s)
  (q_b=2n-1-d, k_b=2n-1-s) full if s>d, diagonal if s==d, empty if s<d

so EVERY device computes exactly two chunk-blocks per hop (one
always-full, one full-or-diagonal) — half the naive work, perfectly
balanced, with `lax.switch` on sign(d-s) selecting the live pair.
Chunks are contiguous in the ORIGINAL positions, so diagonal blocks
use the ordinary causal iota mask; the global entry permutes the
sequence in and inverse-permutes the output.

Online-softmax partials (m, l, acc per q-chunk) merge the sub-blocks
exactly as the plain ring does; gradients flow by autodiff through the
schedule (ppermute/switch/scan-free loop all have transposes).

No reference analog (SURVEY §5 long-context exceeds the 2019
reference); the layout is the zigzag/striped schedule of
llama3-style context parallelism, built on the same mesh machinery
as ring/Ulysses.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

from . import mesh as mesh_lib

_NEG = -1.0e30


def _block_partial(q, k, v, scale, q_off, k_off, diagonal):
    """One chunk-pair's attention partials in f32: returns
    (pv [B,H,c,Dh], m [B,H,c,1], l [B,H,c,1]). diagonal=True applies
    the causal mask on absolute positions (chunks are contiguous
    spans, so iota + offsets suffice)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if diagonal:
        c, ck = q.shape[2], k.shape[2]
        q_pos = q_off + lax.broadcasted_iota(jnp.int32, (c, ck), 0)
        k_pos = k_off + lax.broadcasted_iota(jnp.int32, (c, ck), 1)
        s = jnp.where(k_pos <= q_pos, s, _NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(s <= _NEG / 2, 0.0, p)
    l = jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum("bhqk,bhkd->bhqd", p,
                    v.astype(jnp.float32))
    return pv, m, l


def online_merge(acc, m, l, pv, mb, lb):
    """Online-softmax merge of one block's partials into the running
    (acc, m, l) — the ring/zigzag-shared rescale (numerics notes: the
    _NEG sentinel makes the neutral element (0, _NEG, 0) exact, since
    exp(_NEG - m) underflows to 0 for any real m)."""
    m_new = jnp.maximum(m, mb)
    c0 = jnp.exp(m - m_new)
    c1 = jnp.exp(mb - m_new)
    return acc * c0 + pv * c1, m_new, l * c0 + lb * c1


def online_merge_nk(acc, m, l, pv, mb, lb):
    """No-keepdims variant of online_merge (stats [..., Sq] — the
    flash hop kernels' convention); the ONE copy both the flash ring
    and flash zigzag bodies should use."""
    m_new = jnp.maximum(m, mb)
    c0 = jnp.exp(m - m_new)
    c1 = jnp.exp(mb - m_new)
    return (acc * c0[..., None] + pv * c1[..., None], m_new,
            l * c0 + lb * c1)


def _neutral(pv, m, l):
    return jnp.zeros_like(pv), jnp.full_like(m, _NEG), jnp.zeros_like(l)


def zigzag_attention_inner(q, k, v, *, axis_name, n_blocks, scale=1.0):
    """Per-shard body. q,k,v local [B, H, 2c, Dh] in zigzag layout:
    rows [:c] are chunk d, rows [c:] are chunk 2n-1-d. Causal only
    (the balance problem this schedule solves is causal)."""
    n = n_blocks
    d = lax.axis_index(axis_name)
    c = q.shape[2] // 2
    qa, qb = q[:, :, :c], q[:, :, c:]
    perm = [(j, (j + 1) % n) for j in range(n)]

    def offs(chunk_idx):
        return chunk_idx * c

    B, H, _, Dh = q.shape
    zero = (jnp.zeros((B, H, c, Dh), jnp.float32),
            jnp.full((B, H, c, 1), _NEG, jnp.float32),
            jnp.zeros((B, H, c, 1), jnp.float32))
    state = [list(zero), list(zero)]

    for step in range(n):
        s_idx = (d - step) % n              # source device of k/v
        ka, kb = k[:, :, :c], k[:, :, c:]
        va, vb = v[:, :, :c], v[:, :, c:]
        qa_chunk, qb_chunk = d, 2 * n - 1 - d
        ka_chunk, kb_chunk = s_idx, 2 * n - 1 - s_idx

        # always-live pair: (q_b, k_a) — full, no mask
        pv, mb, lb = _block_partial(qb, ka, va, scale, None, None,
                                    diagonal=False)
        state[1] = list(online_merge(state[1][0], state[1][1],
                                     state[1][2], pv, mb, lb))

        # the comparison pair: exactly one of (qa,ka) / (qb,kb) is
        # live (full), or both are diagonal when d == s
        def qa_ka_full(_):
            pv, mb, lb = _block_partial(qa, ka, va, scale, None, None,
                                        diagonal=False)
            nb = _neutral(pv, mb, lb)
            return (pv, mb, lb) + nb

        def qb_kb_full(_):
            pv, mb, lb = _block_partial(qb, kb, vb, scale, None, None,
                                        diagonal=False)
            na = _neutral(pv, mb, lb)
            return na + (pv, mb, lb)

        def both_diag(_):
            pva, ma, la = _block_partial(
                qa, ka, va, scale, offs(qa_chunk), offs(ka_chunk),
                diagonal=True)
            pvb, mb_, lb_ = _block_partial(
                qb, kb, vb, scale, offs(qb_chunk), offs(kb_chunk),
                diagonal=True)
            return (pva, ma, la, pvb, mb_, lb_)

        # sign(d - s): -1 -> qb_kb full (s > d), 0 -> diagonals,
        # +1 -> qa_ka full (d > s)
        branch = jnp.sign(d - s_idx) + 1    # 0, 1, 2
        pva, ma, la, pvb, mb_, lb_ = lax.switch(
            branch, [qb_kb_full, both_diag, qa_ka_full], None)
        state[0] = list(online_merge(state[0][0], state[0][1],
                                     state[0][2], pva, ma, la))
        state[1] = list(online_merge(state[1][0], state[1][1],
                                     state[1][2], pvb, mb_, lb_))

        if step != n - 1:
            k = lax.ppermute(k, axis_name, perm)
            v = lax.ppermute(v, axis_name, perm)

    outs = []
    for acc, m, l in state:
        outs.append(acc / jnp.maximum(l, 1e-20))
    return jnp.concatenate(outs, axis=2).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def zigzag_attention_inner_flash(q, k, v, axis_name, n_blocks, scale):
    """Flash zigzag body: each chunk-pair runs the pallas hop kernels
    (ops/pallas/ring.py) so scores stay in VMEM — the balanced
    schedule AND the flash memory profile together."""
    out, _ = _zz_flash_fwd(q, k, v, axis_name, n_blocks, scale)
    return out


def _zz_pair_neutral(B, H, c, Dh):
    return (jnp.zeros((B, H, c, Dh), jnp.float32),
            jnp.full((B, H, c), _NEG, jnp.float32),
            jnp.zeros((B, H, c), jnp.float32))


def _zz_flash_fwd(q, k, v, axis_name, n_blocks, scale):
    from ..ops.pallas import ring as R

    n = n_blocks
    d = lax.axis_index(axis_name)
    B, H, S2, Dh = q.shape
    c = S2 // 2
    qa, qb = q[:, :, :c], q[:, :, c:]
    k0, v0 = k, v
    perm = [(j, (j + 1) % n) for j in range(n)]

    # running per-chunk stats (m, l WITHOUT keepdims — fwd_block's
    # convention)
    acc_a = jnp.zeros((B, H, c, Dh), jnp.float32)
    m_a = jnp.full((B, H, c), _NEG, jnp.float32)
    l_a = jnp.zeros((B, H, c), jnp.float32)
    acc_b, m_b, l_b = acc_a, m_a, l_a

    merge = online_merge_nk

    for step in range(n):
        s_idx = (d - step) % n
        ka, kb = k[:, :, :c], k[:, :, c:]
        va, vb = v[:, :, :c], v[:, :, c:]
        off_qa, off_qb = d * c, (2 * n - 1 - d) * c
        off_ka, off_kb = s_idx * c, (2 * n - 1 - s_idx) * c

        # always-live full pair (q_b, k_a)
        pv, mb_, lb_ = R.fwd_block(qb, ka, va, off_qb, off_ka, scale,
                                   False)
        acc_b, m_b, l_b = merge(acc_b, m_b, l_b, pv, mb_, lb_)

        def qa_ka_full(_):
            pv, mm, ll = R.fwd_block(qa, ka, va, off_qa, off_ka,
                                     scale, False)
            return (pv, mm, ll) + _zz_pair_neutral(B, H, c, Dh)

        def qb_kb_full(_):
            pv, mm, ll = R.fwd_block(qb, kb, vb, off_qb, off_kb,
                                     scale, False)
            return _zz_pair_neutral(B, H, c, Dh) + (pv, mm, ll)

        def both_diag(_):
            pva, ma, la = R.fwd_block(qa, ka, va, off_qa, off_ka,
                                      scale, True)
            pvb, mb2, lb2 = R.fwd_block(qb, kb, vb, off_qb, off_kb,
                                        scale, True)
            return (pva, ma, la, pvb, mb2, lb2)

        branch = jnp.sign(d - s_idx) + 1
        pva, ma, la, pvb, mb2, lb2 = lax.switch(
            branch, [qb_kb_full, both_diag, qa_ka_full], None)
        acc_a, m_a, l_a = merge(acc_a, m_a, l_a, pva, ma, la)
        acc_b, m_b, l_b = merge(acc_b, m_b, l_b, pvb, mb2, lb2)

        if step != n - 1:
            k = lax.ppermute(k, axis_name, perm)
            v = lax.ppermute(v, axis_name, perm)

    l_a_s = jnp.maximum(l_a, 1e-20)
    l_b_s = jnp.maximum(l_b, 1e-20)
    out = jnp.concatenate(
        [acc_a / l_a_s[..., None], acc_b / l_b_s[..., None]],
        axis=2).astype(q.dtype)
    lse = jnp.concatenate([m_a + jnp.log(l_a_s),
                           m_b + jnp.log(l_b_s)], axis=2)
    return out, (q, k0, v0, out, lse)


def _zz_flash_bwd(axis_name, n_blocks, scale, res, g):
    from ..ops.pallas import ring as R

    q, k, v, out, lse = res
    n = n_blocks
    d = lax.axis_index(axis_name)
    B, H, S2, Dh = q.shape
    c = S2 // 2
    qa, qb = q[:, :, :c], q[:, :, c:]
    ga, gb = g[:, :, :c], g[:, :, c:]
    lse_a, lse_b = lse[:, :, :c], lse[:, :, c:]
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    del_a, del_b = delta[:, :, :c], delta[:, :, c:]
    perm = [(j, (j + 1) % n) for j in range(n)]

    dqa = jnp.zeros((B, H, c, Dh), jnp.float32)
    dqb = jnp.zeros((B, H, c, Dh), jnp.float32)
    dk_acc = jnp.zeros_like(k, dtype=jnp.float32)
    dv_acc = jnp.zeros_like(v, dtype=jnp.float32)

    zero_q = jnp.zeros((B, H, c, Dh), jnp.float32)
    zero_k = jnp.zeros((B, H, c, Dh), jnp.float32)

    for step in range(n):
        s_idx = (d - step) % n
        ka, kb = k[:, :, :c], k[:, :, c:]
        va, vb = v[:, :, :c], v[:, :, c:]
        off_qa, off_qb = d * c, (2 * n - 1 - d) * c
        off_ka, off_kb = s_idx * c, (2 * n - 1 - s_idx) * c

        # always-live pair (q_b, k_a)
        dq_b1, dk_a1, dv_a1 = R.bwd_block(
            qb, ka, va, gb, lse_b, del_b, off_qb, off_ka, scale,
            False)

        def qa_ka_full(_):
            dq, dk, dv = R.bwd_block(qa, ka, va, ga, lse_a, del_a,
                                     off_qa, off_ka, scale, False)
            return (dq, zero_q, dk, zero_k, dv, zero_k)

        def qb_kb_full(_):
            dq, dk, dv = R.bwd_block(qb, kb, vb, gb, lse_b, del_b,
                                     off_qb, off_kb, scale, False)
            return (zero_q, dq, zero_k, dk, zero_k, dv)

        def both_diag(_):
            dqa_, dka_, dva_ = R.bwd_block(
                qa, ka, va, ga, lse_a, del_a, off_qa, off_ka, scale,
                True)
            dqb_, dkb_, dvb_ = R.bwd_block(
                qb, kb, vb, gb, lse_b, del_b, off_qb, off_kb, scale,
                True)
            return (dqa_, dqb_, dka_, dkb_, dva_, dvb_)

        branch = jnp.sign(d - s_idx) + 1
        dq_a2, dq_b2, dk_a2, dk_b2, dv_a2, dv_b2 = lax.switch(
            branch, [qb_kb_full, both_diag, qa_ka_full], None)

        dqa = dqa + dq_a2
        dqb = dqb + dq_b1 + dq_b2
        dk_hop = jnp.concatenate([dk_a1 + dk_a2, dk_b2], axis=2)
        dv_hop = jnp.concatenate([dv_a1 + dv_a2, dv_b2], axis=2)
        dk_acc = dk_acc + dk_hop
        dv_acc = dv_acc + dv_hop

        # k/v are not read after the last hop, but the accumulators
        # need every rotation to land home after n permutes
        if step != n - 1:
            k = lax.ppermute(k, axis_name, perm)
            v = lax.ppermute(v, axis_name, perm)
        dk_acc = lax.ppermute(dk_acc, axis_name, perm)
        dv_acc = lax.ppermute(dv_acc, axis_name, perm)

    dq = jnp.concatenate([dqa, dqb], axis=2)
    return (dq.astype(q.dtype), dk_acc.astype(k.dtype),
            dv_acc.astype(v.dtype))


zigzag_attention_inner_flash.defvjp(_zz_flash_fwd, _zz_flash_bwd)


def _zigzag_perm(S, n):
    """Global position permutation: device-major concat of each
    device's (d, 2n-1-d) chunks. Returns (perm, inv) index arrays."""
    import numpy as np
    c = S // (2 * n)
    order = []
    for d in range(n):
        order.extend(range(d * c, (d + 1) * c))
        order.extend(range((2 * n - 1 - d) * c, (2 * n - d) * c))
    perm = np.asarray(order, np.int32)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(S, dtype=np.int32)
    return perm, inv


def zigzag_attention(q, k, v, mesh=None, axis="sp", scale=1.0,
                     use_flash=None):
    """Global-view causal attention in the zigzag schedule: q,k,v
    [B, H, S, Dh] in NATURAL sequence order; the permutation in/out is
    internal. S must divide by 2*sp. use_flash: None = auto (pallas
    chunk-pair kernels when the geometry fits and FLAGS.ring_flash is
    on); False forces the jnp body."""
    from jax import shard_map

    from ..core.flags import FLAGS
    from ..ops.pallas import ring as R
    from .ulysses import _full_attention

    mesh = mesh or mesh_lib.current_mesh()
    if mesh is None or axis not in mesh.axis_names \
            or mesh.shape[axis] == 1:
        return _full_attention(q, k, v, scale, True)
    n = mesh.shape[axis]
    B, H, S, Dh = q.shape
    if S % (2 * n) != 0:
        raise ValueError("S=%d must divide by 2*sp=%d" % (S, 2 * n))
    c = S // (2 * n)
    if use_flash is None:
        use_flash = (FLAGS.ring_flash
                     and R.applicable(B, H, c, c, Dh,
                                      q.dtype.itemsize))
    perm, inv = _zigzag_perm(S, n)
    qz = jnp.take(q, perm, axis=2)
    kz = jnp.take(k, perm, axis=2)
    vz = jnp.take(v, perm, axis=2)
    spec = PartitionSpec(None, None, axis, None)

    if use_flash:
        def body(q_, k_, v_):
            return zigzag_attention_inner_flash(q_, k_, v_, axis, n,
                                                scale)
    else:
        def body(q_, k_, v_):
            return zigzag_attention_inner(q_, k_, v_, axis_name=axis,
                                          n_blocks=n, scale=scale)

    f = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                  out_specs=spec, check_vma=False)
    out = f(qz, kz, vz)
    return jnp.take(out, inv, axis=2)


from ..ops.registry import register  # noqa: E402


@register("zigzag_attention", ["Q", "K", "V"], ["Out"])
def zigzag_attention_op(q, k, v, *, scale=1.0, axis="sp"):
    """Static-graph op twin (the ring_attention_op pattern): uses the
    ambient mesh; without an sp axis it falls back to full causal
    attention."""
    return zigzag_attention(q, k, v, axis=axis, scale=scale)
