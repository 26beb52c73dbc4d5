"""Expert parallelism: Switch-style mixture-of-experts FFN over an
``ep`` mesh axis.

Not in the 2019 reference — the last cell of this framework's
parallelism matrix (dp x tp x sp x pp x ep), built the TPU way
(GShard/Switch): static shapes throughout (capacity buckets, no
data-dependent shapes under jit), expert weights sharded over ``ep``,
tokens data-sharded over the SAME axis, and ONE ``lax.all_to_all``
each way moving only the capacity buckets across ICI.

Routing (``top_k``): 1 = Switch (default), 2 = GShard top-2 with
renormalized gates, secondaries queueing behind all primaries of the
same expert. Capacity C = ceil(n * top_k * capacity_factor / E);
tokens beyond it are DROPPED (zero contribution) — the standard
static-shape trade; callers size capacity_factor accordingly. The
aux balancing loss is returned so training can regularize routing
(Switch Transformer recipe).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

from ..ops.registry import register
from . import mesh as mesh_lib


def _route(x, gate_w, n_experts, capacity, top_k):
    """Shared routing math, identical on the sharded and reference
    paths (determinism is the equality test's foundation). top_k=1 is
    Switch (raw top-1 gate prob); top_k=2 is GShard (gates
    renormalized over the two chosen experts, secondary tokens
    queueing behind ALL primary tokens of the same expert so the
    second choice drops first under pressure). Returns
    (dispatch [E, C, D], combines: list of (gate, idx, pos, keep),
    f [E] primary routed fraction, p [E] mean router prob). The aux
    loss is E * sum(f * p) — composed by the CALLER so the sharded
    path can pmean f and p across shards BEFORE the product."""
    n, d = x.shape
    logits = x @ gate_w
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    idx1 = jnp.argmax(probs, axis=-1)
    p1 = jnp.max(probs, axis=-1)
    oh1 = jax.nn.one_hot(idx1, n_experts, dtype=jnp.float32)
    pos1 = (jnp.cumsum(oh1, axis=0) * oh1).sum(-1) - 1.0
    if top_k == 2:
        masked = probs - oh1 * probs
        idx2 = jnp.argmax(masked, axis=-1)
        p2 = jnp.max(masked, axis=-1)
        oh2 = jax.nn.one_hot(idx2, n_experts, dtype=jnp.float32)
        denom = jnp.maximum(p1 + p2, 1e-9)
        pos2 = ((jnp.cumsum(oh2, axis=0) * oh2).sum(-1) - 1.0
                + oh1.sum(0)[idx2])
        choices = [(p1 / denom, idx1, pos1), (p2 / denom, idx2, pos2)]
    else:
        choices = [(p1, idx1, pos1)]
    combines = []
    dispatch = jnp.zeros((n_experts, capacity, d), x.dtype)
    for g, idx, posf in choices:
        pos = posf.astype(jnp.int32)
        keep = (pos < capacity) & (pos >= 0)
        contrib = jnp.where(keep[:, None], x, 0.0)
        dispatch = dispatch.at[
            idx, jnp.clip(pos, 0, capacity - 1)].add(contrib)
        combines.append((g, idx, pos, keep))
    return dispatch, combines, oh1.mean(0), probs.mean(0)


def _expert_ffn(w1, b1, w2, b2, h):
    """Batched per-expert FFN: h [E_loc, T, D] -> [E_loc, T, D]."""
    y = jnp.einsum("etd,edf->etf", h, w1) + b1[:, None, :]
    y = jax.nn.relu(y)
    return jnp.einsum("etf,efd->etd", y, w2) + b2[:, None, :]


def _combine2(expert_out, combines, capacity):
    """Gather each choice's expert output, scale by its gate, sum;
    dropped tokens contribute zero."""
    out = 0.0
    for g, idx, pos, keep in combines:
        out = out + jnp.where(
            keep[:, None],
            expert_out[idx, jnp.clip(pos, 0, capacity - 1)]
            * g[:, None].astype(expert_out.dtype), 0.0)
    return out


def moe_ffn_reference(x, gate_w, w1, b1, w2, b2, *,
                      capacity_factor=1.25, top_k=1):
    """Single-device reference semantics (the equality oracle): same
    routing, all experts local."""
    if top_k not in (1, 2):
        raise ValueError("top_k must be 1 (Switch) or 2 (GShard), "
                         "got %r" % (top_k,))
    n = x.shape[0]
    E = w1.shape[0]
    capacity = int(-(-n * top_k * capacity_factor // E))
    dispatch, combines, f, p = _route(x, gate_w, E, capacity, top_k)
    aux = E * jnp.sum(f * p)
    expert_out = _expert_ffn(w1, b1, w2, b2, dispatch)
    return _combine2(expert_out, combines, capacity), aux


def moe_ffn(x, gate_w, w1, b1, w2, b2, *, mesh=None, axis="ep",
            capacity_factor=1.25, top_k=1):
    """Expert-parallel MoE FFN. x [N, D] tokens (sharded over the ep
    axis by the shard_map in_specs); gate_w [D, E] replicated; expert
    weights w1 [E, D, F], b1 [E, F], w2 [E, F, D], b2 [E, D] sharded
    over ep on their leading E axis. Returns ([N, D], aux_loss).

    Per shard: route local tokens to ALL experts into capacity
    buckets, all_to_all the buckets so each device holds ITS experts'
    tokens from every shard, run the batched expert FFN, all_to_all
    back, combine. The aux loss is the GLOBAL Switch loss (fractions
    pmean'd across shards before the product).

    Capacity semantics under pressure: buckets are sized and filled
    PER TOKEN SHARD (C = ceil(N/ep * cf / E), the GShard/Switch
    static-shape discipline — dropping is a local decision, no global
    sort). A skewed shard can therefore drop tokens the single-device
    reference (global buckets) would keep: with no drops the two
    paths are exactly equal (the tested contract); under capacity
    pressure they legitimately differ. Size capacity_factor for the
    no-drop regime or accept shard-local dropping, as on any ep
    pod."""
    from jax import shard_map

    if top_k not in (1, 2):
        raise ValueError("top_k must be 1 (Switch) or 2 (GShard), "
                         "got %r" % (top_k,))
    mesh = mesh or mesh_lib.current_mesh()
    if mesh is None or axis not in mesh.axis_names \
            or mesh.shape[axis] == 1:
        return moe_ffn_reference(x, gate_w, w1, b1, w2, b2,
                                 capacity_factor=capacity_factor,
                                 top_k=top_k)

    ep = mesh.shape[axis]
    E = w1.shape[0]
    if E % ep != 0:
        raise ValueError("num experts %d not divisible by ep=%d"
                         % (E, ep))
    if x.shape[0] % ep != 0:
        raise ValueError("token count %d not divisible by ep=%d"
                         % (x.shape[0], ep))
    n_loc = x.shape[0] // ep
    capacity = int(-(-n_loc * top_k * capacity_factor // E))

    def body(x_l, gate_w, w1_l, b1_l, w2_l, b2_l):
        dispatch, combines, f, p = _route(
            x_l, gate_w, E, capacity, top_k)          # [E, C, D]
        # [E, C, D] -> [E/ep, ep*C, D]: each device receives its
        # experts' buckets from every token shard
        h = lax.all_to_all(dispatch, axis, split_axis=0,
                           concat_axis=1, tiled=True)
        out = _expert_ffn(w1_l, b1_l, w2_l, b2_l, h)
        # route the processed buckets back to their token shards
        back = lax.all_to_all(out, axis, split_axis=1, concat_axis=0,
                              tiled=True)             # [E, C, D]
        y = _combine2(back, combines, capacity)
        # GLOBAL Switch loss: average the fractions across shards
        # first, then take the product (shards are equal-sized, so
        # pmean(f) is the global routed fraction exactly)
        aux = E * jnp.sum(lax.pmean(f, axis) * lax.pmean(p, axis))
        return y, aux

    tok = PartitionSpec(axis)
    exp = PartitionSpec(axis)
    f = shard_map(
        body, mesh=mesh,
        in_specs=(tok, PartitionSpec(), exp, exp, exp, exp),
        out_specs=(tok, PartitionSpec()),
        check_vma=False)
    return f(x, gate_w, w1, b1, w2, b2)


@register("moe_ffn", ["X", "GateW", "W1", "B1", "W2", "B2"],
          ["Out", "AuxLoss"])
def moe_ffn_op(x, gate_w, w1, b1, w2, b2, *, capacity_factor=1.25,
               axis="ep", top_k=1):
    """Static-graph op twin (the ring_attention_op pattern): uses the
    ambient mesh set by CompiledProgram.run / mesh_guard; without an
    ep axis in scope it falls back to the single-device reference."""
    return moe_ffn(x, gate_w, w1, b1, w2, b2, axis=axis,
                   capacity_factor=capacity_factor, top_k=top_k)
