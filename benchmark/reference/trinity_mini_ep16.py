"""Plain float32 reference of the AFMoE block (Arcee Trinity-Mini:
public ``config.json``, ``modeling_afmoe.py``) as a causal language
model, with one chip's share of the routed experts.

Per layer, ``h`` the residual stream, every norm an RMSNorm with a
weight:

  a = norm_in(h); q, k, v, g = a Wq, a Wk, a Wv, a Wg; q and k
  RMS-normed per head; rotary (rotate-half, whole head) on q, k in
  sliding layers only; q head i reads kv head i // (H / Hkv); causal,
  and in sliding layers key j only where i - j < window;
  o = softmax(q k^T / sqrt(Dh)) v * sigmoid(g);
  h = h + norm_post_attn(o Wo)
  m = norm_pre_mlp(h); dense layers f = (silu(m Wgate) * m Wup) Wdown;
  expert layers s = sigmoid(m Wr), sel = top_k(s + b), w = s[sel],
  w = route_scale * w / (sum w + 1e-20), f = shared(m) + sum over the
  e in sel THAT ARE HELD of w_e expert_e(m);
  h = h + norm_post_mlp(f)

and ``norm_final(h) Whead`` into the mean cross-entropy. The experts
held are ``first_held_expert .. + num_experts - 1`` of the
``num_experts_published`` the router scores; what the others would add
is left out, as in the program. Nothing is dropped: every expert held
is computed over every token and weighted by what the router gave it
(nought for most), which no buffer can overflow.

**The bias buffer** ``b`` is state that no gradient reaches: after
each step ``b += coeff * sign(mean(c) - c)``, ``b -= mean(b)``, c the
step's assignments per expert. ``common.train`` carries parameters
only, so the buffers ride in what ``normalizers`` returns (a dict that
``train`` hands to every block of every step): each block reports its
loads to the host (``io_callback``, outside the recomputed regions, so
once), and when the step's last block has reported the host moves the
buffer in that dict, before the next step is enqueued (``train`` reads
the step's loss first).

Memory: ``common.train`` holds five float32 copies of the parameters;
beside them 8k tokens fit because each layer's two halves are
``jax.checkpoint``-ed, attention runs in blocks of query rows (a
sliding layer's block reads its window's keys only) and the head in
blocks of rows, each recomputed in the backward pass.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import io_callback

from . import common as C

Q_ROWS = 256        # query rows to a block of attention
HEAD_ROWS = 1024    # rows to a block of the vocabulary head

# the bias buffers' host side: see the module's docstring
_STATE = {}


def _moe_layers(cfg):
    return list(range(cfg["num_dense_layers"], cfg["num_hidden_layers"]))


def param_spec(cfg):
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
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
        mat(p + "_att_q.w_0", d, h * dh)
        mat(p + "_att_k.w_0", d, hkv * dh)
        mat(p + "_att_v.w_0", d, hkv * dh)
        mat(p + "_att_gate.w_0", d, h * dh)
        norm(p + "_att_q_norm", dh)
        norm(p + "_att_k_norm", dh)
        mat(p + "_att_out.w_0", h * dh, d)
        norm(p + "_post_att_norm")
        norm(p + "_pre_mlp_norm")
        if i < cfg["num_dense_layers"]:
            mlp(p + "_mlp", cfg["intermediate_size"])
        else:
            mat(p + "_router.w_0", d, cfg["num_experts_published"])
            n = cfg["num_experts"]
            mat(p + "_experts.w_gate", n, d, f)
            mat(p + "_experts.w_up", n, d, f)
            mat(p + "_experts.w_down", n, f, d)
            mlp(p + "_shared", f * cfg["num_shared_experts"])
        norm(p + "_post_mlp_norm")
    norm("final_norm")
    mat("lm_head.w_0", d, cfg["vocab_size"])
    _STATE["sizes"] = {"layers": _moe_layers(cfg),
                       "width": cfg["num_experts_published"]}
    return spec


def normalizers(batch):
    """The loss's divisor and the bias buffers at their start (nought).
    A call starts a run: the host side of the buffers is reset. The
    buffers' sizes are the configuration's, which this function is not
    told: ``param_spec``, which ``common.train`` calls first, leaves
    them in ``_STATE``."""
    sizes = _STATE["sizes"]
    _STATE.clear()
    _STATE.update(sizes=sizes, rows=int(batch["ids"].shape[0]),
                  load={}, seen={})
    _STATE["norm"] = {
        "tokens": jnp.sum(jnp.asarray(batch["mask"])),
        "router_bias": {i: np.zeros((sizes["width"],), np.float32)
                        for i in sizes["layers"]}}
    return _STATE["norm"]


def _report_load(layer, coeff, rows_in_block, load):
    """Host side of one block's report. The step's last block moves the
    buffer."""
    st = _STATE
    st["load"][layer] = st["load"].get(layer, 0.0) \
        + np.asarray(load, np.float64)
    st["seen"][layer] = st["seen"].get(layer, 0) + 1
    if st["seen"][layer] == st["rows"] // int(rows_in_block):
        c = st["load"].pop(layer)
        st["seen"][layer] = 0
        b = np.asarray(st["norm"]["router_bias"][layer], np.float64)
        b = b + coeff * np.sign(np.mean(c) - c)
        st["norm"]["router_bias"][layer] = \
            (b - np.mean(b)).astype(np.float32)
    return np.float32(0.0)


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + eps) * w


def rotary(x, theta):
    """x [..., s, dh], rotate-half, position s of row s."""
    s, dh = x.shape[-2], x.shape[-1]
    half = dh // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32)
                         * (2.0 / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window, mode):
    """q [b, hkv, g, s, dh], k and v [b, hkv, s, dh]; causal, keys
    i-window+1..i where ``window``; in blocks of Q_ROWS query rows,
    each reading the slab of keys its rows can see."""
    s, dh = q.shape[-2], q.shape[-1]
    rows = min(Q_ROWS, s)
    while s % rows:
        rows -= 1
    span = min(s, rows + window - 1) if window else s

    @jax.checkpoint
    def block(start):
        k0 = jnp.clip(start + rows - span, 0, s - span)
        qb = lax.dynamic_slice_in_dim(q, start, rows, axis=3)
        kb = lax.dynamic_slice_in_dim(k, k0, span, axis=2)
        vb = lax.dynamic_slice_in_dim(v, k0, span, axis=2)
        sc = C.contract("bkgqd,bkmd->bkgqm", qb, kb, mode) * dh ** -0.5
        qi = start + jnp.arange(rows)[:, None]
        ki = k0 + jnp.arange(span)[None, :]
        keep = ki <= qi
        if window:
            keep = jnp.logical_and(keep, qi - ki < window)
        w = jax.nn.softmax(jnp.where(keep, sc, -1e30), axis=-1)
        return C.contract("bkgqm,bkmd->bkgqd", w, vb, mode)

    out = lax.map(block, jnp.arange(0, s, rows))    # [n, b, k, g, r, dh]
    return jnp.moveaxis(out, 0, 3).reshape(q.shape)


def gated_mlp(m, p, pre, mode):
    return C.linear(jax.nn.silu(C.linear(m, p[pre + "_gate.w_0"], None,
                                         mode))
                    * C.linear(m, p[pre + "_up.w_0"], None, mode),
                    p[pre + "_down.w_0"], None, mode)


def route(m, router_w, bias, cfg, mode):
    """(sel [.., k], weight [.., k], load [E]) of the sigmoid router."""
    scores = jax.nn.sigmoid(C.linear(m, router_w, None, mode))
    _, sel = lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, sel, axis=-1)
    if cfg["route_norm"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    load = jnp.zeros((router_w.shape[1],), jnp.float32).at[
        sel.reshape(-1)].add(1.0)
    return sel, w * cfg["route_scale"], load


def held_experts(m, sel, w, p, pre, cfg, mode):
    """Sum over the held experts of (the weight the router gave it for
    the token, nought where it was not chosen) x expert(m)."""
    out = 0.0
    for e in range(cfg["num_experts"]):
        we = jnp.sum(jnp.where(sel == cfg["first_held_expert"] + e, w,
                               0.0), -1, keepdims=True)
        act = jax.nn.silu(C.linear(m, p[pre + ".w_gate"][e], None, mode)) \
            * C.linear(m, p[pre + ".w_up"][e], None, mode)
        out = out + we * C.linear(act, p[pre + ".w_down"][e], None, mode)
    return out


def block_loss(params, rows, norm, key, cfg, mode):
    """These rows' share of the mean cross-entropy over all positions."""
    del key                                  # nothing here is random
    p, eps = params, cfg["rms_norm_eps"]
    b, s = rows["ids"].shape
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]

    def attention_half(p, h, i):
        pre = "layer%d" % i
        sliding = cfg["layer_types"][i] == "sliding_attention"
        a = rms_norm(h, p[pre + "_input_norm.w_0"], eps)

        def heads(name, n):
            t = C.linear(a, p[pre + name], None, mode)
            return t.reshape(b, s, n, dh).transpose(0, 2, 1, 3)

        q = rms_norm(heads("_att_q.w_0", hq),
                     p[pre + "_att_q_norm.w_0"], eps)
        k = rms_norm(heads("_att_k.w_0", hkv),
                     p[pre + "_att_k_norm.w_0"], eps)
        v = heads("_att_v.w_0", hkv)
        if sliding:
            q, k = rotary(q, cfg["rope_theta"]), \
                rotary(k, cfg["rope_theta"])
        o = attention(q.reshape(b, hkv, hq // hkv, s, dh), k, v,
                      cfg["sliding_window"] if sliding else 0, mode)
        o = o.reshape(b, hq, s, dh).transpose(0, 2, 1, 3).reshape(
            b, s, hq * dh)
        o = o * jax.nn.sigmoid(
            C.linear(a, p[pre + "_att_gate.w_0"], None, mode))
        return h + rms_norm(
            C.linear(o, p[pre + "_att_out.w_0"], None, mode),
            p[pre + "_post_att_norm.w_0"], eps)

    def mlp_half(p, h, i, sel, w):
        pre = "layer%d" % i
        m = rms_norm(h, p[pre + "_pre_mlp_norm.w_0"], eps)
        if sel is None:
            f = gated_mlp(m, p, pre + "_mlp", mode)
        else:
            f = gated_mlp(m, p, pre + "_shared", mode) \
                + held_experts(m, sel, w, p, pre + "_experts", cfg, mode)
        return h + rms_norm(f, p[pre + "_post_mlp_norm.w_0"], eps)

    h = p["embed_tokens"][rows["ids"]]
    if cfg["mup_enabled"]:
        h = h * d ** 0.5
    reported = 0.0
    for i in range(cfg["num_hidden_layers"]):
        pre = "layer%d" % i
        h = jax.checkpoint(functools.partial(attention_half, i=i))(p, h)
        sel = w = None
        if i >= cfg["num_dense_layers"]:
            # the router stands outside the recomputed halves: its
            # report to the host goes out once
            m = rms_norm(h, p[pre + "_pre_mlp_norm.w_0"], eps)
            sel, w, load = route(m, p[pre + "_router.w_0"],
                                 norm["router_bias"][i], cfg, mode)
            reported = reported + io_callback(
                functools.partial(_report_load, i,
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
        logits = C.linear(hb, p["lm_head.w_0"], None, mode)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
        return jnp.sum((lse - gold) * mb)

    total = jnp.sum(lax.map(head, (h.reshape(-1, n, d),
                                   labels.reshape(-1, n),
                                   mask.reshape(-1, n))))
    return total / norm["tokens"] + lax.stop_gradient(reported)
