"""The one traffic generator. A traffic mix is a JSON file of
parameters (``traffic/<name>.json``); this module turns it and a seed
into the arrays one training step is fed.

Every seed gets the SAME multiset of sequence lengths (the quantiles of
the uniform distribution the file names, so the batch is as ragged as a
uniform draw) in another order, and the same multiset of prediction
counts: the real tokens of a step, and so the work behind
``tokens_per_s``, do not change with the seed. Ids, labels and the
order do.

Copied in spirit from ``models.transformer.make_fake_batch`` and
``models.bert.make_fake_pretrain_batch`` (uniform lengths S/2..S, random
ids), which draw a different token count for every seed.

Field kinds (``fields`` in the file, in order):
  tokens        ids uniform in [low, args[vocab_key]) on real positions,
                ``pad_id`` on the rest
  length_mask   float32 1 on real positions, 0 on pads
  segments      0 on the first half of each sequence, 1 on the second
  mlm           masked-LM targets: ``count_range`` predicted positions a
                row (quantiles again), without repeats, inside the real
                tokens; emits ``<name>_pos`` (flat into batch*seq, as
                the program gathers), ``<name>_pos_in_row``,
                ``<name>_label``, ``<name>_weight``
  uniform_int   ints uniform in [low, high), shape [batch] + shape
"""

import numpy as np


def _quantiles(lo, hi, n, rs):
    """n values spread evenly over [lo, hi], in an order from rs."""
    vals = np.floor(lo + (np.arange(n) + 0.5) * (hi - lo + 1) / n)
    return rs.permutation(np.minimum(vals, hi).astype(np.int64))


def generate(traffic, args, seed):
    """Returns (batch, stats). ``stats``: positions, pad_positions and
    tokens_per_step (real positions of ``tokens_field``)."""
    rs = np.random.RandomState(int(seed) % (1 << 32))
    batch, s = int(traffic["batch"]), int(traffic["seq_len"])
    lo, hi = traffic["length_range"]
    lens = _quantiles(lo, hi, batch, rs)
    real = np.arange(s)[None, :] < lens[:, None]
    out = {}
    for f in traffic["fields"]:
        kind, name = f["kind"], f["name"]
        if kind == "tokens":
            ids = rs.randint(f.get("low", 1), args[f["vocab_key"]],
                             size=(batch, s)).astype(np.int64)
            out[name] = np.where(real, ids, f.get("pad_id", 0))
        elif kind == "length_mask":
            out[name] = real.astype(np.float32)
        elif kind == "segments":
            second = np.arange(s)[None, :] >= (lens[:, None] // 2)
            out[name] = (second & real).astype(np.int64)
        elif kind == "mlm":
            n_slots = int(args[f["slots_key"]])
            c_lo, c_hi = f["count_range"]
            counts = _quantiles(c_lo, min(c_hi, n_slots), batch, rs)
            pos = np.zeros((batch, n_slots), np.int64)
            weight = np.zeros((batch, n_slots), np.float32)
            for i in range(batch):
                n = int(min(counts[i], lens[i]))
                pos[i, :n] = rs.choice(int(lens[i]), size=n,
                                       replace=False)
                weight[i, :n] = 1.0
            out[name + "_pos_in_row"] = pos
            out[name + "_pos"] = np.where(
                weight > 0, pos + np.arange(batch)[:, None] * s, 0)
            out[name + "_label"] = rs.randint(
                f.get("low", 0), args[f["vocab_key"]],
                size=(batch, n_slots)).astype(np.int64)
            out[name + "_weight"] = weight
        elif kind == "uniform_int":
            out[name] = rs.randint(
                f["low"], f["high"],
                size=(batch,) + tuple(f["shape"])).astype(np.int64)
        else:
            raise ValueError("unknown traffic field kind %r" % kind)
    masks = [out[f["name"]] for f in traffic["fields"]
             if f["kind"] == "length_mask"]
    positions = sum(m.size for m in masks)
    stats = {
        "positions": int(positions),
        "pad_positions": int(positions - sum(m.sum() for m in masks)),
        "tokens_per_step": int(out[traffic["tokens_field"]].sum()),
        "lengths": lens.tolist(),
        "predictions": int(sum(
            out[f["name"] + "_weight"].sum()
            for f in traffic["fields"] if f["kind"] == "mlm")),
    }
    return out, stats
