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
(Switch Transformer recipe). ``moe_ffn`` appears in no model.

**The held experts' part of a sigmoid top-k layer** (below
``moe_ffn``; what ``models/afmoe.py`` builds through
``layers.moe_sigmoid_router`` and ``layers.moe_held_experts``) is the
other kind of expert layer, and shares nothing with the first:
sigmoid scores over the PUBLISHED width, the top k on score plus a
load-balancing bias buffer, weights renormalised and scaled
(``sigmoid_topk_route``, ``balance_bias_update``); then the part of the
output that the experts HELD HERE give (``held_experts_ffn``): the
assignments to them stable-sorted by expert into a static row buffer,
the buffer walked in chunks up to the rows the router sent (three
grouped matrix products a chunk, ops/pallas/grouped_matmul.py),
scattered back times the weights: the buffer's size is what may be
addressed, the step's load is what is computed and moved.
Nothing is dropped: an assignment past the buffer makes the output NaN
and is counted. It runs on one chip with no exchange; what the experts
held elsewhere would add is left out (ROADMAP R1 is the exchange).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

from ..ops.pallas import grouped_matmul as gmm_lib
from ..ops.registry import register
from . import mesh as mesh_lib

# The step's own counts, as one persistable the ops add to. Each is a
# total since the startup program: assignments (tokens x top_k, every
# layer), those to held experts, rows the grouped product computed
# (tile-rounded) and rows that found no room in the buffer, per layer
# and step the busiest held expert's tokens and the held mean, and the
# rows of the buffer that the held experts' chunk loops walked.
COUNTERS_VAR = "__moe_counters__"
COUNTER_NAMES = ("assignments_total", "assignments_held_total",
                 "rows_computed_total", "rows_over_capacity_total",
                 "held_load_max_total", "held_load_mean_total",
                 "rows_passed_total")


def read_counters(scope):
    """{name: total} from ``scope``, or None where no program with a
    held-experts layer has run in it."""
    import numpy as np
    v = scope.find_var(COUNTERS_VAR) if scope.has_var(COUNTERS_VAR) \
        else None
    if v is None:
        return None
    return dict(zip(COUNTER_NAMES, np.asarray(v, np.float64).tolist()))


def _add_counts(counters, **counts):
    add = jnp.stack([jnp.asarray(counts.get(n, 0.0), jnp.float32)
                     for n in COUNTER_NAMES])
    return counters + add


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


# ---------------------------------------------------------------------------
# sigmoid top-k routing over the published width, the held experts' part
# ---------------------------------------------------------------------------

def sigmoid_topk_route(x, router_w, bias, *, top_k, route_scale=1.0,
                       route_norm=True):
    """x [T, D], router_w [D, E], bias [E] -> (sel [T, k] int32,
    weight [T, k] float32, load [E] float32). Scores are sigmoids in
    float32 (the product at full precision: a bf16 pass would flip
    the last of the k where two scores lie close); the k are chosen on
    ``score + bias`` and weighted by the score alone; ``load`` counts
    the step's assignments to each expert."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, sel = lax.top_k(scores + lax.stop_gradient(bias), top_k)
    weight = jnp.take_along_axis(scores, sel, axis=-1)
    if route_norm:
        weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-20)
    load = jnp.zeros((router_w.shape[1],), jnp.float32).at[
        sel.reshape(-1)].add(1.0)
    return sel.astype(jnp.int32), weight * route_scale, load


def balance_bias_update(bias, load, coeff):
    """The auxiliary-loss-free balancing step on the bias buffer:
    towards the mean load by ``coeff`` a step, then centred."""
    bias = bias + coeff * jnp.sign(jnp.mean(load) - load)
    return bias - jnp.mean(bias)


def _chunk_rows(n_tokens, capacity, n_held=8):
    """Rows a chunk of the sorted buffer, from the shapes alone: three
    for every two tokens of the step, or an eighth of the tokens for
    every expert held where that is more (the whole buffer where it is
    less), in whole row tiles. Swept on the v5e (PERF.md section 6). A
    row of slack in a chunk costs 0.15-0.18 us of passes, a real row
    0.2-0.45 us, and a chunk of its own about 1 ms more (the held
    matrices fetched again, the float32 sums of their gradients read
    and written), so a chunk should take a layer's load whole nearly
    always and not be much longer. PR 31, T = 8,192 tokens and 8
    experts of 2048 x 1024 holding 0.7 to 1.5 rows a token: a step
    255.0 / 258.1 ms at 8,192 rows a chunk (two chunks in half the
    layers), 251.7 / 252.8 at 12,288, 255.6 / 256.6 at 16,384. PR 34,
    16 experts of 2048 x 768 holding 8,900 to 10,000 rows a layer with
    a tail past 12,288: 8 steps 2.808 / 2.832 s at 12,288 (two seeds:
    the pace moved with the seed's second chunks), 2.828 / 2.835 at
    16,384, 2.844 / 2.851 at 18,432, 2.895 / 2.902 at 24,576."""
    rows = min(n_tokens * max(12, n_held) // 8, capacity)
    return -(-rows // gmm_lib.TILE_M) * gmm_lib.TILE_M


def _chunk(i, x, weight, rows, ends):
    """Chunk ``i`` of the sorted rows: its tokens' activations and its
    weights [R], the tokens themselves, and how many of its rows each
    expert has (a group that straddles a chunk's end is split there: R
    is whole row tiles, so at a tile's end, and the tiles the products
    visit are the unchunked ones). The slack of the last chunk needs
    no mask: the products ignore what the rows past the sizes' sum
    hold and zero what they give."""
    n = rows.shape[1]
    rows_c = lax.dynamic_index_in_dim(rows, i, keepdims=False)
    tok = rows_c // weight.shape[1]
    starts = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])
    sizes = jnp.clip(ends, i * n, (i + 1) * n) \
        - jnp.clip(starts, i * n, (i + 1) * n)
    return x[tok], weight.reshape(-1)[rows_c], rows_c, tok, sizes


def _n_chunks(ends, rows):
    return (ends[-1] + rows - 1) // rows


@jax.custom_vjp
def _experts(x, weight, w_gate, w_up, w_down, rows, sizes):
    """The sorted rows through their experts and back: x [T, D];
    weight [T, k]; rows [chunks, R] the assignment (token x k + choice)
    of each buffer row; sizes [n_held] the rows of each expert -> [T,
    D] float32.

    The buffer is walked in chunks of R rows by a loop whose trip count
    is the step's own ``ceil(sum(sizes) / R)``: every array between the
    sort and the combine is R rows long, so the gathers, the
    elementwise passes, the scatter-adds and the products alike cost
    what the step's load costs and not what the buffer could hold.

    Its own backward pass, the same loop over the same chunks, which
    keeps the op's inputs ALONE: the gathered rows and the first two
    products are made again a chunk at a time (two products more a
    chunk), so no array of the buffer's length lives between the
    passes, and the executor's second lowering of the forward pass
    (under ``jax.vjp``) feeds nothing and is dropped as dead code,
    whatever XLA's CSE makes of two loops. The matrices' gradients are
    summed across chunks in float32 and rounded once."""
    return _experts_fwd(x, weight, w_gate, w_up, w_down, rows, sizes)[0]


def _experts_fwd(x, weight, w_gate, w_up, w_down, rows, sizes):
    ends = jnp.cumsum(sizes)

    def chunk(i, out):
        xs, wr, _, tok, sizes_c = _chunk(i, x, weight, rows, ends)
        gate = gmm_lib.grouped_matmul(xs, w_gate, sizes_c)
        up = gmm_lib.grouped_matmul(xs, w_up, sizes_c)
        y = gmm_lib.grouped_matmul(jax.nn.silu(gate) * up, w_down, sizes_c)
        return out.at[tok].add(y.astype(jnp.float32) * wr[:, None])

    out = lax.fori_loop(0, _n_chunks(ends, rows.shape[1]), chunk,
                        jnp.zeros(x.shape, jnp.float32))
    return out, (x, weight, w_gate, w_up, w_down, rows, sizes)


def _experts_bwd(res, d_out):
    x, weight, w_gate, w_up, w_down, rows, sizes = res
    ends = jnp.cumsum(sizes)
    # the output was x's type, so its cotangent holds no more than that
    d_out = d_out.astype(x.dtype)

    def chunk(i, carry):
        d_x, d_weight, d_w_gate, d_w_up, d_w_down = carry
        xs, wr, rows_c, tok, sizes_c = _chunk(i, x, weight, rows, ends)
        gate = gmm_lib.grouped_matmul(xs, w_gate, sizes_c)
        up = gmm_lib.grouped_matmul(xs, w_up, sizes_c)
        h, gated_vjp = jax.vjp(lambda g, u: jax.nn.silu(g) * u, gate, up)
        # out = sum over rows of wr x (h @ w_down): with the weight
        # moved onto h, one pullback gives the matrix's gradient and
        # the unweighted gradient of h, and the weight's own is a row
        # sum of that; the product's output is not needed again
        d_hw, d_w_down = gmm_lib.grouped_matmul_pullback(
            h * wr[:, None].astype(h.dtype), w_down, sizes_c, d_out[tok],
            d_w_down)
        d_hw = d_hw.astype(jnp.float32)
        d_weight = d_weight.at[rows_c].add(
            jnp.sum(h.astype(jnp.float32) * d_hw, -1))
        d_gate, d_up = gated_vjp((d_hw * wr[:, None]).astype(h.dtype))
        d_xs_gate, d_w_gate = gmm_lib.grouped_matmul_pullback(
            xs, w_gate, sizes_c, d_gate, d_w_gate)
        d_xs_up, d_w_up = gmm_lib.grouped_matmul_pullback(
            xs, w_up, sizes_c, d_up, d_w_up)
        d_x = d_x.at[tok].add(d_xs_gate.astype(jnp.float32)
                              + d_xs_up.astype(jnp.float32))
        return d_x, d_weight, d_w_gate, d_w_up, d_w_down

    zeros = lambda like: jnp.zeros(like.shape, jnp.float32)  # noqa: E731
    d_x, d_weight, d_w_gate, d_w_up, d_w_down = lax.fori_loop(
        0, _n_chunks(ends, rows.shape[1]), chunk,
        (zeros(x), jnp.zeros((weight.size,), jnp.float32), zeros(w_gate),
         zeros(w_up), zeros(w_down)))
    return (d_x.astype(x.dtype),
            d_weight.reshape(weight.shape).astype(weight.dtype),
            d_w_gate.astype(w_gate.dtype), d_w_up.astype(w_up.dtype),
            d_w_down.astype(w_down.dtype), None, None)


_experts.defvjp(_experts_fwd, _experts_bwd)


def held_experts_ffn(x, sel, weight, w_gate, w_up, w_down, *,
                     first_held=0, row_capacity=0):
    """The held experts' part of a top-k layer's output.

    x [T, D]; sel [T, k] expert ids over the published width, weight
    [T, k]; w_gate, w_up [n_held, D, F], w_down [n_held, F, D]: expert
    ``first_held + e`` is ``w_*[e]``, a gated-SiLU MLP. Returns (out
    [T, D] in x's type, rows_computed, rows_passed, rows_over): the
    sum over each token's chosen experts THAT ARE HELD of weight x
    expert(x); the rows the grouped products computed, tile-rounded;
    the rows the chunk loop walked (``_experts``); and how many
    assignments found no room in the ``row_capacity`` rows (0: a row
    for every assignment), in which case ``out`` is NaN throughout.
    ``row_capacity`` is what may be addressed; what is computed and
    moved follows the rows the router sent."""
    T, _ = x.shape
    k = sel.shape[1]
    n_held = w_gate.shape[0]
    n_assign = T * k
    cap = int(row_capacity) or n_assign
    chunk = _chunk_rows(T, cap, n_held)
    padded = -(-cap // chunk) * chunk           # whole chunks
    local = sel.reshape(-1) - first_held
    held = jnp.logical_and(local >= 0, local < n_held)
    key = jnp.where(held, local, n_held)        # the others sort last
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[None, :] == jnp.arange(n_held)[:, None], axis=1,
                    dtype=jnp.int32)
    n_rows = jnp.sum(sizes)
    if padded > n_assign:
        order = jnp.pad(order, (0, padded - n_assign))
    ends = jnp.minimum(jnp.cumsum(sizes), cap)
    sizes_in = ends - jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                       ends[:-1]])
    out = _experts(x, weight, w_gate, w_up, w_down,
                   order[:padded].reshape(-1, chunk), sizes_in)
    over = jnp.maximum(n_rows - cap, 0)
    out = jnp.where(over > 0, jnp.nan, out)
    return (out.astype(x.dtype),
            gmm_lib.tile_rounded_rows(sizes_in).astype(jnp.float32),
            (_n_chunks(ends, chunk) * chunk).astype(jnp.float32),
            over.astype(jnp.float32))


@register("moe_sigmoid_router", ["X", "W", "Bias", "Counters"],
          ["TopkIdx", "TopkWeight", "BiasOut", "CountersOut"],
          nondiff=("Bias", "Counters"))
def moe_sigmoid_router_op(x, w, bias, counters, *, top_k,
                          route_scale=1.0, route_norm=True,
                          balance_coeff=0.0, first_held=0, n_held=0):
    """Static-graph twin of ``sigmoid_topk_route`` with the bias
    buffer's update and the step's routing counts. ``BiasOut`` and
    ``CountersOut`` are the inputs' own variables (written in place,
    as batch_norm's moving mean is)."""
    sel, weight, load = sigmoid_topk_route(
        x, w, bias, top_k=top_k, route_scale=route_scale,
        route_norm=route_norm)
    held = lax.dynamic_slice_in_dim(load, first_held, n_held) \
        if n_held else jnp.zeros((1,), jnp.float32)
    counters = _add_counts(
        counters, assignments_total=jnp.sum(load),
        assignments_held_total=jnp.sum(held),
        held_load_max_total=jnp.max(held),
        held_load_mean_total=jnp.mean(held))
    if balance_coeff:
        bias = balance_bias_update(bias, load, balance_coeff)
    return sel, weight, bias, counters


@register("moe_held_experts",
          ["X", "TopkIdx", "Weight", "WGate", "WUp", "WDown",
           "Counters"],
          ["Out", "CountersOut"], nondiff=("TopkIdx", "Counters"))
def moe_held_experts_op(x, sel, weight, w_gate, w_up, w_down, counters,
                        *, first_held=0, row_capacity=0):
    out, computed, passed, over = held_experts_ffn(
        x, sel, weight, w_gate, w_up, w_down, first_held=first_held,
        row_capacity=row_capacity)
    return out, _add_counts(counters, rows_computed_total=computed,
                            rows_passed_total=passed,
                            rows_over_capacity_total=over)
