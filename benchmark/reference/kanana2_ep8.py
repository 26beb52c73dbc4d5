"""Plain float32 reference of the DeepSeek-V3 block at Kanana-2-30B-A3B's
sizes (kakaocorp/kanana-2-30b-a3b-instruct-2601: public ``config.json``,
``model_type`` ``deepseek_v3``; the block is arXiv:2412.19437's) as a
causal language model, with one chip's share of the routed experts.

Per layer, ``h`` the residual stream, every norm an RMSNorm with a
weight, no post-norms: ``h = h + mla(norm_in(h))``,
``h = h + ffn(norm_mlp(h))``; a final norm, an untied head, the mean
cross-entropy over the vocabulary slice.

**MLA with a decoupled rotary part** (every layer; H heads, position t
the row index):

  q = a Wq  [H, dn + dr] = [q_n | q_r]      (no query compression)
  [c | k_r] = a Wkva       (kv_lora_rank + dr)
  [k_n | v] = rms(c) Wkvb  [H, dn + dv]
  rot(u)_t: with w_i = theta^(-2i/dr), i = 0..dr/2-1, the pair
    (u_2i, u_2i+1) -> (u_2i cos(t w_i) - u_2i+1 sin(t w_i),
                       u_2i+1 cos(t w_i) + u_2i sin(t w_i)),
    the interleaved pairs turned IN PLACE (``rope_interleave``; the
    public code moves them to the half layout first, q and k alike, so
    its scores are these), angles and products in float32
  k = [k_n | rot(k_r)], rot(k_r) ONE vector a token for every head;
  causal softmax of [q_n | rot(q_r)] k^T / sqrt(dn + dr) in blocks of
  query rows (``rope_scaling`` null: no further scale); y = (P v) Wout.

The turn is written as ``u * cos + (u J) * sin`` with J the constant
signed permutation that sends (u_2i, u_2i+1) to (-u_2i+1, u_2i): a
product with J at ``Precision.HIGHEST`` is exact, and it keeps the 64
lanes whole where a reshape to pairs would pad every pair to a tile on
the TPU. It is NOT one of the model's contractions: the int8 control
leaves it in float32.

**FFN**: the leading ``first_k_dense_replace`` layers a gated-SiLU MLP;
then s = sigmoid(m Wr) over the published width in float32,
sel = top_k(s + b) (``topk_method`` ``noaux_tc``; ``n_group`` =
``topk_group`` = 1, so the grouped top-k is the plain one),
w = s[sel] / (sum + 1e-20) * routed_scaling_factor (``norm_topk_prob``),
f = shared(m) + sum over the e in sel THAT ARE HELD of w_e expert_e(m);
shared ONE gated-SiLU MLP of width moe_intermediate_size x
n_shared_experts; the experts held are ``first_held_expert .. +
n_routed_experts - 1`` of the ``num_experts_published`` the router
scores, and what the others would add is left out, as in the program.
The bias buffer ``b`` moves after each step as in
``reference/trinity_mini_ep16.py``, whose host-side mechanism
(``_STATE``, ``_report_load``, ``normalizers``) this module uses as it
stands.

Memory: ``common.train`` holds five float32 copies of the 576M
parameters (10.7 GiB of the chip's 15.75), so a row's float32
activations get what is left: each layer's two halves are
``jax.checkpoint``-ed, and inside a half the work goes in recomputed
pieces -- the mixer in groups of ``HEAD_GROUP`` heads (each group's
projections, attention in blocks of ``Q_ROWS`` query rows and its rows
of the output projection, summed over the groups), the MLPs and experts
in blocks of ``MLP_ROWS`` tokens, the head in blocks of rows. In
``int8`` mode a tensor is rounded piece by piece (one scale a group or
block, not one a tensor).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import io_callback

from . import common as C
from . import trinity_mini_ep16 as T

Q_ROWS = 256          # query rows to a block of attention
HEAD_ROWS = 1024      # rows to a block of the vocabulary head
HEAD_GROUP = 4        # heads to a recomputed piece of the mixer
MLP_ROWS = 1024       # tokens to a recomputed piece of an MLP half

normalizers = T.normalizers
rms_norm = T.rms_norm


def param_spec(cfg):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    f = cfg["moe_intermediate_size"]
    draw = "tnormal%g" % cfg["initializer_range"]
    spec = [("embed_tokens", (cfg["vocab_size"], d), draw)]

    def mat(name, *shape):
        spec.append((name, shape, draw))

    def norm(name, n=d):
        spec.append((name + ".w_0", (n,), "ones"))

    def mlp(p, width):
        mat(p + "_gate.w_0", d, width)
        mat(p + "_up.w_0", d, width)
        mat(p + "_down.w_0", width, d)

    for i in range(cfg["num_hidden_layers"]):
        p = "layer%d" % i
        norm(p + "_input_norm")
        mat(p + "_mla_q.w_0", d, h * (dn + dr))
        mat(p + "_mla_kv_a.w_0", d, cfg["kv_lora_rank"] + dr)
        norm(p + "_mla_kv_a_norm", cfg["kv_lora_rank"])
        mat(p + "_mla_kv_b.w_0", cfg["kv_lora_rank"], h * (dn + dv))
        mat(p + "_mla_out.w_0", h * dv, d)
        norm(p + "_mlp_norm")
        if i < cfg["first_k_dense_replace"]:
            mlp(p + "_mlp", cfg["intermediate_size"])
        else:
            mat(p + "_router.w_0", d, cfg["num_experts_published"])
            n = cfg["n_routed_experts"]
            mat(p + "_experts.w_gate", n, d, f)
            mat(p + "_experts.w_up", n, d, f)
            mat(p + "_experts.w_down", n, f, d)
            mlp(p + "_shared", f * cfg["n_shared_experts"])
    norm("final_norm")
    mat("lm_head.w_0", d, cfg["vocab_size"])
    # the bias buffers' sizes, for trinity_mini_ep16.normalizers
    T._STATE["sizes"] = {
        "layers": list(range(cfg["first_k_dense_replace"],
                             cfg["num_hidden_layers"])),
        "width": cfg["num_experts_published"]}
    return spec


def rotate_pairs(u, theta, axis):
    """u [..., w] with its positions along ``axis``: each interleaved
    pair (u_2i, u_2i+1) of the last axis turned in place by
    ``t * theta^(-2i/w)``, t the index along ``axis``."""
    w, s = u.shape[-1], u.shape[axis]
    i = np.arange(w // 2)
    freq = jnp.float32(theta) ** (-2.0 * jnp.asarray(i, jnp.float32) / w)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    # [s, w]: each pair's angle under both of its lanes
    cos = jnp.repeat(jnp.cos(ang), 2, axis=1)
    sin = jnp.repeat(jnp.sin(ang), 2, axis=1)
    swap = np.zeros((w, w), np.float32)     # u J = (-u_1, u_0, -u_3, ..)
    swap[2 * i + 1, 2 * i] = -1.0
    swap[2 * i, 2 * i + 1] = 1.0
    shape = [1] * u.ndim
    shape[axis], shape[-1] = s, w
    return u * cos.reshape(shape) + jnp.einsum(
        "...w,wv->...v", u, jnp.asarray(swap),
        precision=C.HIGHEST) * sin.reshape(shape)


def causal_attention(q, k, v, mode):
    """q, k [b, h, s, dqk], v [b, h, s, dv]; in blocks of Q_ROWS query
    rows, each against the whole row of keys."""
    s, dqk = q.shape[2], q.shape[3]
    rows = min(Q_ROWS, s)
    while s % rows:
        rows -= 1

    @jax.checkpoint
    def block(start):
        qb = lax.dynamic_slice_in_dim(q, start, rows, axis=2)
        sc = C.contract("bhqd,bhkd->bhqk", qb, k, mode) * dqk ** -0.5
        keep = jnp.arange(s)[None, :] <= start + jnp.arange(rows)[:, None]
        w = jax.nn.softmax(jnp.where(keep, sc, -1e30), axis=-1)
        return C.contract("bhqk,bhkd->bhqd", w, v, mode)

    out = lax.map(block, jnp.arange(0, s, rows))      # [n, b, h, r, dv]
    return jnp.moveaxis(out, 0, 2).reshape(v.shape[:2] + (s, -1))


def route(m, router_w, bias, cfg, mode):
    """(sel [.., k], weight [.., k], load [E]) of the sigmoid router
    with its selection bias: the bias chooses, the scores weigh."""
    scores = jax.nn.sigmoid(C.linear(m, router_w, None, mode))
    _, sel = lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, sel, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    load = jnp.zeros((router_w.shape[1],), jnp.float32).at[
        sel.reshape(-1)].add(1.0)
    return sel, w * cfg["routed_scaling_factor"], load


def held_experts(m, sel, w, p, pre, cfg, mode):
    """Sum over the held experts of (the weight the router gave it for
    the token, nought where it was not chosen) x expert(m): every
    expert held over every token, which no buffer can overflow. One
    expert at a time in a scan (one body to compile, not one an
    expert)."""
    def add(out, expert):
        e, w_gate, w_up, w_down = expert
        we = jnp.sum(jnp.where(sel == cfg["first_held_expert"] + e, w,
                               0.0), -1, keepdims=True)
        act = jax.nn.silu(C.linear(m, w_gate, None, mode)) \
            * C.linear(m, w_up, None, mode)
        return out + we * C.linear(act, w_down, None, mode), None

    out, _ = lax.scan(add, jnp.zeros_like(m), (
        jnp.arange(cfg["n_routed_experts"]), p[pre + ".w_gate"],
        p[pre + ".w_up"], p[pre + ".w_down"]))
    return out


def block_loss(params, rows, norm, key, cfg, mode):
    """These rows' share of the mean cross-entropy over all positions."""
    del key                                  # nothing here is random
    p, eps = params, cfg["rms_norm_eps"]
    b, s = rows["ids"].shape
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r, theta = cfg["kv_lora_rank"], cfg["rope_theta"]
    lin = functools.partial(C.linear, b=None, mode=mode)

    size = min(HEAD_GROUP, hq)
    while hq % size:
        size -= 1
    n_groups = hq // size

    def cut(w):
        """A matrix's last axis, hq x width lanes, into [groups, ...,
        lanes a group]."""
        return jnp.moveaxis(
            w.reshape(w.shape[:-1] + (n_groups, w.shape[-1] // n_groups)),
            -2, 0)

    def mla_half(p, h, i):
        pre = "layer%d_mla" % i
        a = rms_norm(h, p["layer%d_input_norm.w_0" % i], eps)
        kva = lin(a, p[pre + "_kv_a.w_0"])
        c = rms_norm(kva[..., :r], p[pre + "_kv_a_norm.w_0"], eps)
        k_r = rotate_pairs(kva[..., r:], theta, axis=1)     # [b, s, dr]
        t = lambda x: x.transpose(0, 2, 1, 3)           # noqa: E731

        @jax.checkpoint
        def group(w):
            q = lin(a, w["q"]).reshape(b, s, size, dn + dr)
            q = jnp.concatenate(
                [q[..., :dn], rotate_pairs(q[..., dn:], theta, axis=1)],
                -1)
            kv = lin(c, w["kv_b"]).reshape(b, s, size, dn + dv)
            k = jnp.concatenate(
                [kv[..., :dn],
                 jnp.broadcast_to(k_r[:, :, None, :], (b, s, size, dr))],
                -1)
            o = causal_attention(t(q), t(k), t(kv[..., dn:]), mode)
            return lin(t(o).reshape(b, s, size * dv), w["out"].T)

        pieces = {"q": cut(p[pre + "_q.w_0"]),
                  "kv_b": cut(p[pre + "_kv_b.w_0"]),
                  "out": cut(p[pre + "_out.w_0"].T)}
        # h + the groups' parts of the mixer's output, one at a time
        total, _ = lax.scan(lambda acc, w: (acc + group(w), None), h,
                            pieces)
        return total

    def mlp_half(p, h, i, sel, w):
        pre = "layer%d" % i
        rows_ = min(MLP_ROWS, s)
        while s % rows_:
            rows_ -= 1

        @jax.checkpoint
        def piece(x):
            m = rms_norm(x[0], p[pre + "_mlp_norm.w_0"], eps)
            if sel is None:
                return x[0] + T.gated_mlp(m, p, pre + "_mlp", mode)
            return x[0] + T.gated_mlp(m, p, pre + "_shared", mode) \
                + held_experts(m, x[1], x[2], p, pre + "_experts", cfg,
                               mode)

        def pieces(x):        # [b, s, ...] -> [s / rows, b, rows, ...]
            x = x.reshape((b, s // rows_, rows_) + x.shape[2:])
            return jnp.moveaxis(x, 1, 0)

        out = lax.map(piece, tuple(
            pieces(x) for x in ((h,) if sel is None else (h, sel, w))))
        return jnp.moveaxis(out, 0, 1).reshape(h.shape)

    h = p["embed_tokens"][rows["ids"]]
    reported = 0.0
    for i in range(cfg["num_hidden_layers"]):
        pre = "layer%d" % i
        h = jax.checkpoint(functools.partial(mla_half, i=i))(p, h)
        sel = w = None
        if i >= cfg["first_k_dense_replace"]:
            # the router stands outside the recomputed halves: its
            # report to the host goes out once
            m = rms_norm(h, p[pre + "_mlp_norm.w_0"], eps)
            sel, w, load = route(m, p[pre + "_router.w_0"],
                                 norm["router_bias"][i], cfg, mode)
            reported = reported + io_callback(
                functools.partial(T._report_load, i,
                                  cfg["load_balance_coeff"], b),
                jax.ShapeDtypeStruct((), jnp.float32), load)
        h = jax.checkpoint(functools.partial(mlp_half, i=i))(
            p, h, sel=sel, w=w)

    h = rms_norm(h, p["final_norm.w_0"], eps).reshape(b * s, d)
    labels = rows["labels"].reshape(b * s)
    mask = rows["mask"].reshape(b * s)
    n = min(HEAD_ROWS, b * s)
    while (b * s) % n:
        n -= 1

    @jax.checkpoint
    def head(args):
        hb, lb, mb = args
        logits = lin(hb, p["lm_head.w_0"])
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
        return jnp.sum((lse - gold) * mb)

    total = jnp.sum(lax.map(head, (h.reshape(-1, n, d),
                                   labels.reshape(-1, n),
                                   mask.reshape(-1, n))))
    return total / norm["tokens"] + lax.stop_gradient(reported)
