"""Ulysses-style sequence parallelism: all-to-all head/sequence
re-sharding around full attention.

Like ring attention (ring_attention.py), this is a new TPU-first
capability with no 2019-reference counterpart (SURVEY §5
"long-context"). Where the ring rotates K/V blocks with ppermute (N-1
ICI hops, compute overlapped), Ulysses re-shards ONCE each way:

    [B, H, S/n, Dh]  --all_to_all-->  [B, H/n, S, Dh]
       (sequence-sharded)                (head-sharded)

each device then runs ordinary full attention for its heads (any
kernel — including the pallas flash path — since the sequence is whole
again), and a second all-to-all restores sequence sharding. Two
collectives total, so it wins over the ring when heads divide evenly
and S^2/n attention fits per device; the ring wins for extreme S.
Both compose with dp/tp via the mesh axes.

Requires num_heads % sp == 0 (the classic Ulysses constraint).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

from ..core.enforce import enforce
from ..ops.registry import register
from . import mesh as mesh_lib

_NEG = -1.0e30


def _full_attention(q, k, v, scale, causal, bias=None):
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if bias is not None:
        s = s + lax.stop_gradient(bias).astype(jnp.float32)
    if causal:
        Sq, Sk = q.shape[2], k.shape[2]
        q_pos = lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0)
        k_pos = lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
        s = jnp.where(k_pos <= q_pos, s, _NEG)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w,
                      v.astype(jnp.float32)).astype(q.dtype)


import threading

# recursion guard: _attend re-enters the scaled_dot_product_attention
# lowering INSIDE the shard_map body; that lowering's sp routing must
# see it is already under a sequence-parallel schedule (the local
# H/n, S shapes can look routable again) and keep its per-device path
_SP_BODY = threading.local()


def in_sp_body() -> bool:
    return getattr(_SP_BODY, "active", False)


def _attend(q, k, v, bias, scale, causal):
    """Per-device attention after the re-shard — dispatched through
    the op registry so FLAGS_op_library=pallas gets the FLASH kernel
    (O(S*Dh) residuals, no S^2 score matrix in HBM) exactly as the
    module docstring promises; the base library takes the jnp path."""
    from ..core.flags import FLAGS
    from ..ops.registry import get as get_op
    opdef = get_op("scaled_dot_product_attention")
    fn = opdef.pick(FLAGS.op_library or None)
    _SP_BODY.active = True
    try:
        return fn(q, k, v, bias, scale=scale, causal=causal,
                  is_test=True)
    finally:
        _SP_BODY.active = False


def ulysses_attention_inner(q, k, v, bias=None, *, axis_name,
                            scale=1.0, causal=False):
    """Per-shard body (inside shard_map): q,k,v local
    [B, H, S/n, Dh] → all-to-all → full attention on H/n heads →
    all-to-all back. ``bias`` (additive attention bias, replicated —
    every device holds the full [B, 1|H, Sq, Sk]) slices its HEAD dim
    when it carries one, since after the re-shard each device attends
    H/n heads against the whole sequence."""
    # seq-sharded → head-sharded: split heads across the axis, gather
    # the full sequence
    q = lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2,
                       tiled=True)
    k = lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2,
                       tiled=True)
    v = lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2,
                       tiled=True)
    if bias is not None and bias.shape[1] > 1:
        # per-head bias: this device now holds heads
        # [idx*H/n, (idx+1)*H/n) — slice the matching bias rows
        h_loc = q.shape[1]
        idx = lax.axis_index(axis_name)
        bias = lax.dynamic_slice_in_dim(bias, idx * h_loc, h_loc,
                                        axis=1)
    out = _attend(q, k, v, bias, scale, causal)
    # head-sharded → seq-sharded
    return lax.all_to_all(out, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)


def ulysses_attention(q, k, v, mesh=None, axis="sp", scale=1.0,
                      causal=False, bias=None):
    """Global-view entry: q,k,v [B, H, S, Dh]; the shard_map in_specs
    shard the sequence over ``axis``. ``bias``: optional additive
    attention bias [B, 1|H, Sq, Sk] (pad masks, ALiBi) — replicated
    across the axis, exactly once per device, so the per-head math is
    identical to full attention. Falls back to plain fused attention
    when no sp axis is in scope (same contract as ring_attention)."""
    from jax import shard_map

    mesh = mesh or mesh_lib.current_mesh()
    if mesh is None or axis not in mesh.axis_names \
            or mesh.shape[axis] == 1:
        return _full_attention(q, k, v, scale, causal, bias=bias)
    n = mesh.shape[axis]
    enforce(q.shape[1] % n == 0,
            "ulysses needs num_heads (%d) divisible by the sp degree "
            "(%d); use ring_attention otherwise", q.shape[1], n)
    spec = PartitionSpec(None, None, axis, None)
    body = functools.partial(ulysses_attention_inner, axis_name=axis,
                             scale=scale, causal=causal)
    if bias is None:
        f = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                      out_specs=spec, check_vma=False)
        return f(q, k, v)
    bias = lax.stop_gradient(bias)
    f = shard_map(body, mesh=mesh,
                  in_specs=(spec, spec, spec, PartitionSpec()),
                  out_specs=spec, check_vma=False)
    return f(q, k, v, bias)


@register("ulysses_attention", ["Q", "K", "V", "Bias"], ["Out"],
          nondiff=("Bias",))
def ulysses_attention_op(q, k, v, bias=None, *, scale=1.0,
                         causal=False, axis="sp"):
    """Static-graph op twin (uses the ambient mesh, like the
    ring_attention op)."""
    return ulysses_attention(q, k, v, axis=axis, scale=scale,
                             causal=causal, bias=bias)


# ---------------------------------------------------------------------------
# production routing: the compiler's sp dispatch
# ---------------------------------------------------------------------------

def sequence_parallel_attention(q, k, v, bias=None, scale=1.0,
                                causal=False, mesh=None, axis="sp"):
    """Route one attention through the sequence-parallel schedule the
    geometry admits, or return None when no sp path applies (the
    caller keeps its replicated lowering).

    This is the ONE routing decision `CompiledProgram` mesh runs make:
    the `scaled_dot_product_attention` base lowering calls it under the
    ambient mesh (`mesh_guard` installed by CompiledProgram.run), so a
    model built from ordinary layers engages zigzag/Ulysses the moment
    its BuildStrategy mesh carries an sp axis — no model changes.

      - causal, no bias, S divisible by 2·sp → **zigzag ring**
        (balanced causal schedule, flash chunk-pair kernels when the
        geometry fits);
      - heads divisible by sp, S divisible by sp → **Ulysses**
        all-to-all head re-sharding (bias rides replicated);
      - anything else → None (replicated full attention stays
        correct; GSPMD places it).

    Dropout never routes: the sp bodies run their per-device kernels
    with ``is_test=True``, and a mask drawn per-shard would break the
    dp-equality contract (docs/parallel.md)."""
    if in_sp_body():
        return None
    mesh = mesh or mesh_lib.current_mesh()
    if mesh is None or axis not in mesh.axis_names \
            or mesh.shape[axis] == 1:
        return None
    if getattr(q, "ndim", 0) != 4 or k.ndim != 4:
        return None
    n = mesh.shape[axis]
    B, H, Sq, Dh = q.shape
    Sk = k.shape[2]
    if causal and bias is None and Sq == Sk and Sq % (2 * n) == 0:
        from .zigzag import zigzag_attention
        return zigzag_attention(q, k, v, mesh=mesh, axis=axis,
                                scale=scale)
    if H % n == 0 and Sq % n == 0 and Sk % n == 0:
        if bias is not None and bias.ndim == 4 \
                and bias.shape[1] not in (1, H):
            return None
        return ulysses_attention(q, k, v, mesh=mesh, axis=axis,
                                 scale=scale, causal=causal, bias=bias)
    return None
