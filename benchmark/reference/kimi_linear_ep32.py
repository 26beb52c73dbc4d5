"""Plain float32 reference of the Kimi Linear block (Moonshot
Kimi-Linear-48B-A3B: public ``config.json``, ``model_type``
``kimi_linear``; technical report arXiv:2510.26692) as a causal
language model, with one chip's share of the routed experts.

Per layer, ``h`` the residual stream, every norm an RMSNorm with a
weight, no post-norms: ``h = h + mixer(norm_in(h))``,
``h = h + ffn(norm_mlp(h))``.

**KDA** (layers in ``linear_attn_config.kda_layers``; H heads of D):

  q = l2(silu(conv(a Wq))) / sqrt(D), k = l2(silu(conv(a Wk))),
  v = silu(conv(a Wv)); conv a causal depthwise convolution of K taps,
  ``y_t = sum_i w[:, i] x_{t-K+1+i}``; l2 per head,
  ``x / sqrt(sum x^2 + 1e-6)``;
  g_t = -exp(A_log_h) softplus((a Wfa) Wfb + dt_bias)   [H, D]
  beta_t = sigmoid(a Wb)                                [H]
  S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
  o_t = S_t^T q_t, S_0 = 0, **token by token**: a ``lax.scan`` over the
  tokens of a block inside a checkpointed scan over blocks, so the
  backward pass keeps one state a block;
  y = (rms_head(o) w_o * sigmoid((a Wga) Wgb)) Wout

``A_log`` and ``dt_bias`` are ``start + parameter``: the start is the
family's (A = exp(A_log) over (1, 16), softplus(dt_bias) over (0.001,
0.1)), a fixed spread written out again here (``gate_start``); the
parameters are drawn as noughts, because the benchmark's generator
(``common.init_params``) draws ones, noughts and centred normals only.

**MLA, NoPE** (layers in ``full_attn_layers``): q = a Wq [H, 128 + 64];
[c | k_r] = a Wkva (512 + 64); [k_n | v] = rms(c) Wkvb [H, 128 + 128];
k = [k_n | k_r], k_r the same for every head; no rotary; causal
softmax of q k^T / sqrt(192) in blocks of query rows; y = (P v) Wout.

**FFN**: the leading ``first_k_dense_replace`` layers a gated-SiLU MLP;
then s = sigmoid(m Wr) over the published width, sel = top_k(s + b),
w = s[sel] / (sum + 1e-20) * routed_scaling_factor, f = shared(m) + sum
over the e in sel THAT ARE HELD of w_e expert_e(m): the experts held
are ``first_held_expert .. + num_experts - 1``; what the others would
add is left out, as in the program. The bias buffer ``b`` moves after
each step as in ``reference/trinity_mini_ep16.py``, whose host-side
mechanism (``_STATE``, ``_report_load``, ``normalizers``) this module
uses as it stands.

Memory: ``common.train`` holds five float32 copies of the 602M
parameters (12 GB of the chip's 16), so a row's float32 activations get
what is left: each layer's two halves are ``jax.checkpoint``-ed, and
inside a half the work goes in recomputed pieces -- a KDA or MLA mixer
in groups of ``HEAD_GROUP`` heads (each group's projections,
convolutions, recurrence or attention and its rows of the output
projection, summed over the groups), attention in blocks of query
rows, the MLPs and experts in blocks of ``MLP_ROWS`` tokens, the head
in blocks of rows. In ``int8`` mode a tensor is rounded piece by piece
(one scale a group or block, not one a tensor).
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
TOKEN_BLOCK = 128     # tokens to a checkpointed block of the recurrence
HEAD_GROUP = 4        # heads to a recomputed piece of a mixer
MLP_ROWS = 1024       # tokens to a recomputed piece of an MLP half

normalizers = T.normalizers
rms_norm = T.rms_norm


def _kinds(cfg):
    la = cfg["linear_attn_config"]
    full = set(la["full_attn_layers"])
    return ["mla" if i + 1 in full else "kda"
            for i in range(cfg["num_hidden_layers"])]


def gate_start(n_heads, head_dim):
    """The family's start of (A_log [H], dt_bias [H * D]): the same
    spread as the program's ``models.kimi_linear.kda_gate_start``,
    written out independently."""
    phi = 0.6180339887498949
    u = np.mod(np.arange(1, n_heads + 1, dtype=np.float64) * phi, 1.0)
    w = np.mod(np.arange(1, n_heads * head_dim + 1, dtype=np.float64)
               * phi, 1.0)
    dt = 0.001 * (0.1 / 0.001) ** w
    return (np.log(1.0 + 15.0 * u).astype(np.float32),
            (dt + np.log(-np.expm1(-dt))).astype(np.float32))


def param_spec(cfg):
    d = cfg["hidden_size"]
    la = cfg["linear_attn_config"]
    hk, dk = la["num_heads"], la["head_dim"]
    wk, rank = hk * dk, cfg["kda_gate_rank"]
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

    for i, kind in enumerate(_kinds(cfg)):
        p = "layer%d" % i
        norm(p + "_input_norm")
        if kind == "kda":
            p += "_kda"
            for n in ("_q", "_k", "_v"):
                mat(p + n + ".w_0", d, wk)
                spec.append((p + n + "_conv.w_0",
                             (wk, la["short_conv_kernel_size"]),
                             "tnormal%g" % cfg["conv_init_std"]))
            mat(p + "_f_a.w_0", d, rank)
            mat(p + "_f_b.w_0", rank, wk)
            spec.append((p + "_gate.A_log", (hk,), "zeros"))
            spec.append((p + "_gate.dt_bias", (wk,), "zeros"))
            mat(p + "_b.w_0", d, hk)
            mat(p + "_g_a.w_0", d, rank)
            mat(p + "_g_b.w_0", rank, wk)
            norm(p + "_o_norm", dk)
            mat(p + "_out.w_0", wk, d)
        else:
            p += "_mla"
            mat(p + "_q.w_0", d, h * (dn + dr))
            mat(p + "_kv_a.w_0", d, cfg["kv_lora_rank"] + dr)
            norm(p + "_kv_a_norm", cfg["kv_lora_rank"])
            mat(p + "_kv_b.w_0", cfg["kv_lora_rank"], h * (dn + dv))
            mat(p + "_out.w_0", h * dv, d)
        p = "layer%d" % i
        norm(p + "_mlp_norm")
        if i < cfg["first_k_dense_replace"]:
            mlp(p + "_mlp", cfg["intermediate_size"])
        else:
            mat(p + "_router.w_0", d, cfg["num_experts_published"])
            n = cfg["num_experts"]
            mat(p + "_experts.w_gate", n, d, f)
            mat(p + "_experts.w_up", n, d, f)
            mat(p + "_experts.w_down", n, f, d)
            mlp(p + "_shared", f * cfg["num_shared_experts"])
    norm("final_norm")
    mat("lm_head.w_0", d, cfg["vocab_size"])
    # the bias buffers' sizes, for trinity_mini_ep16.normalizers
    T._STATE["sizes"] = {
        "layers": list(range(cfg["first_k_dense_replace"],
                             cfg["num_hidden_layers"])),
        "width": cfg["num_experts_published"]}
    return spec


def short_conv(x, w):
    """x [b, s, c], w [c, K]: ``silu(sum_i w[:, i] x_{t-K+1+i})``."""
    K, s = w.shape[1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(xp[:, i:i + s, :] * w[:, i]
                           for i in range(K)))


def l2(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta, mode):
    """q, k, g [b, s, h, dk], v [b, s, h, dv], beta [b, s, h] -> o
    [b, s, h, dv]: the recurrence, one token at a time."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    tb = min(TOKEN_BLOCK, s)
    while s % tb:
        tb -= 1

    def token(state, x):
        qt, kt, vt, gt, bt = x
        state = state * jnp.exp(gt)[..., None]
        kv = C.contract("bhk,bhkv->bhv", kt, state, mode)
        state = state + (bt[..., None] * kt)[..., None] \
            * (vt - kv)[..., None, :]
        return state, C.contract("bhk,bhkv->bhv", qt, state, mode)

    @jax.checkpoint
    def block(state, xs):
        return lax.scan(token, state, xs)

    def blocks(x):            # [b, s, ...] -> [s / tb, tb, b, ...]
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((s // tb, tb) + x.shape[1:])

    _, o = lax.scan(block, jnp.zeros((b, h, dk, dv), jnp.float32),
                    tuple(blocks(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((s,) + o.shape[2:]), 0, 1)


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
    scores = jax.nn.sigmoid(C.linear(m, router_w, None, mode))
    _, sel = lax.top_k(scores + bias, cfg["num_experts_per_token"])
    w = jnp.take_along_axis(scores, sel, axis=-1)
    if cfg["moe_renormalize"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    load = jnp.zeros((router_w.shape[1],), jnp.float32).at[
        sel.reshape(-1)].add(1.0)
    return sel, w * cfg["routed_scaling_factor"], load


def block_loss(params, rows, norm, key, cfg, mode):
    """These rows' share of the mean cross-entropy over all positions."""
    del key                                  # nothing here is random
    p, eps = params, cfg["rms_norm_eps"]
    b, s = rows["ids"].shape
    d = cfg["hidden_size"]
    la = cfg["linear_attn_config"]
    lin = functools.partial(C.linear, b=None, mode=mode)

    def grouped(n_heads):
        """(groups, a function that cuts a matrix's last axis, n_heads
        x width lanes, into [groups, ..., lanes a group])."""
        size = min(HEAD_GROUP, n_heads)
        while n_heads % size:
            size -= 1
        n = n_heads // size

        def cut(w):
            return jnp.moveaxis(
                w.reshape(w.shape[:-1] + (n, w.shape[-1] // n)), -2, 0)
        return n, cut

    def summed(h, group, pieces):
        """h + the groups' parts of the mixer's output, one at a time."""
        total, _ = lax.scan(lambda acc, w: (acc + group(w), None), h,
                            pieces)
        return total

    def kda_half(p, h, i):
        pre = "layer%d_kda" % i
        hk, dk = la["num_heads"], la["head_dim"]
        n, cut = grouped(hk)
        a = rms_norm(h, p["layer%d_input_norm.w_0" % i], eps)
        a_log0, dt_bias0 = gate_start(hk, dk)
        f_in = lin(a, p[pre + "_f_a.w_0"])
        g_in = lin(a, p[pre + "_g_a.w_0"])

        @jax.checkpoint
        def group(w):
            hg = hk // n

            def branch(name):
                t = short_conv(lin(a, w[name]), w[name + "_conv"].T)
                return t.reshape(b, s, hg, dk)

            q = l2(branch("q")) * dk ** -0.5
            k, v = l2(branch("k")), branch("v")
            g = -jnp.exp(w["a_log"])[:, None] * jax.nn.softplus(
                lin(f_in, w["f_b"]) + w["dt_bias"]).reshape(b, s, hg, dk)
            beta = jax.nn.sigmoid(lin(a, w["b"]))
            o = delta_rule(q, k, v, g, beta, mode)
            o = rms_norm(o, p[pre + "_o_norm.w_0"], eps).reshape(
                b, s, -1) * jax.nn.sigmoid(lin(g_in, w["g_b"]))
            return lin(o, w["out"].T)

        pieces = {name: cut(p[pre + "_" + name + ".w_0"])
                  for name in ("q", "k", "v", "f_b", "b", "g_b")}
        for name in ("q", "k", "v"):      # [W, K] -> [groups, K, W / n]
            pieces[name + "_conv"] = cut(
                p[pre + "_" + name + "_conv.w_0"].T)
        pieces["out"] = cut(p[pre + "_out.w_0"].T)
        pieces["a_log"] = cut(p[pre + "_gate.A_log"] + a_log0)
        pieces["dt_bias"] = cut(p[pre + "_gate.dt_bias"] + dt_bias0)
        return summed(h, group, pieces)

    def mla_half(p, h, i):
        pre = "layer%d_mla" % i
        hq = cfg["num_attention_heads"]
        dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
        r = cfg["kv_lora_rank"]
        n, cut = grouped(hq)
        a = rms_norm(h, p["layer%d_input_norm.w_0" % i], eps)
        kva = lin(a, p[pre + "_kv_a.w_0"])
        c = rms_norm(kva[..., :r], p[pre + "_kv_a_norm.w_0"], eps)
        k_r = kva[..., r:]
        t = lambda x: x.transpose(0, 2, 1, 3)           # noqa: E731

        @jax.checkpoint
        def group(w):
            hg = hq // n
            q = lin(a, w["q"]).reshape(b, s, hg, dn + dr)
            kv = lin(c, w["kv_b"]).reshape(b, s, hg, dn + dv)
            k = jnp.concatenate(
                [kv[..., :dn],
                 jnp.broadcast_to(k_r[:, :, None, :], (b, s, hg, dr))],
                -1)
            o = causal_attention(t(q), t(k), t(kv[..., dn:]), mode)
            return lin(t(o).reshape(b, s, hg * dv), w["out"].T)

        return summed(h, group, {
            "q": cut(p[pre + "_q.w_0"]), "kv_b": cut(p[pre + "_kv_b.w_0"]),
            "out": cut(p[pre + "_out.w_0"].T)})

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
                + T.held_experts(m, x[1], x[2], p, pre + "_experts", cfg,
                                 mode)

        def pieces(x):        # [b, s, ...] -> [s / rows, b, rows, ...]
            x = x.reshape((b, s // rows_, rows_) + x.shape[2:])
            return jnp.moveaxis(x, 1, 0)

        out = lax.map(piece, tuple(
            pieces(x) for x in ((h,) if sel is None else (h, sel, w))))
        return jnp.moveaxis(out, 0, 1).reshape(h.shape)

    h = p["embed_tokens"][rows["ids"]]
    reported = 0.0
    for i, kind in enumerate(_kinds(cfg)):
        pre = "layer%d" % i
        mixer = kda_half if kind == "kda" else mla_half
        h = jax.checkpoint(functools.partial(mixer, i=i))(p, h)
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
