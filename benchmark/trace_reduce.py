"""From a profiler trace to numbers: device busy and idle time, kernel
time by name, Mosaic (Pallas) time, collective time and its exposed
part, and the breakdown the ledger keeps.

``load`` reads the ``.xplane.pb`` files of a ``jax.profiler`` trace
into plain events; everything after that is arithmetic on lists, kept
apart so that it is tested on a recorded or synthetic event list.

An event is a dict: ``device`` (plane name), ``name``, ``start`` and
``dur`` in seconds, ``text`` (name and stats joined: what the kind is
read from).

Only LEAF events count: an op line nests (a ``while`` spans the whole
scan and holds its body's ops), and a parent's interval is its
children's plus the gaps between them.
"""

import glob
import os
import re

COLLECTIVE_WORDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all", "allreduce",
                    "all_reduce", "psum")
MOSAIC_WORDS = ("mosaic", "tpu_custom_call", "pallas")


OP_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"


def load(trace_dir):
    """Events of the device planes' op line, and of the asynchronous
    line beside it (``async``: where a TPU trace keeps the in-flight
    part of copies and collectives)."""
    from jax.profiler import ProfileData
    events = []
    for path in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True):
        data = ProfileData.from_file(path)
        for plane in data.planes:
            if not plane.name.startswith("/device:TPU:"):
                continue
            for line in plane.lines:
                if line.name not in (OP_LINE, ASYNC_LINE):
                    continue
                for ev in line.events:
                    if ev.duration_ns <= 0:
                        continue
                    try:
                        stats = " ".join("%s=%s" % (k, v)
                                         for k, v in ev.stats)
                    except Exception:
                        stats = ""
                    events.append({
                        "device": plane.name, "name": ev.name,
                        "start": ev.start_ns * 1e-9,
                        "dur": ev.duration_ns * 1e-9,
                        "async": line.name == ASYNC_LINE,
                        "text": (ev.name + " " + stats).lower()})
    return events


def leaves(events):
    """Events of one device that contain no other event of it. Two
    events that merely overlap (an asynchronous collective beside a
    fusion) are both leaves."""
    evs = sorted(events, key=lambda e: (e["start"], -e["dur"]))
    out, open_ = [], []          # open_: [event, end, has_child]
    for ev in evs:
        end = ev["start"] + ev["dur"]
        still = []
        for rec in open_:
            if rec[1] <= ev["start"] + 1e-12:
                if not rec[2]:
                    out.append(rec[0])
            else:
                if rec[1] >= end - 1e-12:
                    rec[2] = True
                still.append(rec)
        open_ = still + [[ev, end, False]]
    out.extend(rec[0] for rec in open_ if not rec[2])
    return sorted(out, key=lambda e: e["start"])


def union(intervals):
    """Total length of the union of (start, end) intervals, and the
    merged intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _minus(intervals, cover):
    """Length of ``intervals`` (merged) not covered by ``cover``
    (merged)."""
    total = 0.0
    for s, e in intervals:
        left = e - s
        for cs, ce in cover:
            lo, hi = max(s, cs), min(e, ce)
            if hi > lo:
                left -= hi - lo
        total += left
    return total


def short_name(name, width=96):
    """``%fusion.12 = (f32[8]{0}, f32[8,128]{...}) fusion(...),
    kind=kLoop`` -> ``fusion.12 kLoop f32[8,128]``: a trace names an op
    by its whole HLO line; kept are its name, its kind and the largest
    of its outputs."""
    head, _, rest = name.partition(" = ")
    if not rest:
        return name[:width]
    kind = ""
    for mark in ("kind=", "custom_call_target="):
        if mark in rest:
            kind = rest.split(mark, 1)[1].split(",")[0].strip('"\\ ')
            break
    outputs = rest[1:].split(") ", 1)[0] if rest.startswith("(") \
        else rest.split(" ", 1)[0]

    def size(shape):
        n = 1
        for d in shape[shape.index("[") + 1:-1].split(","):
            n *= int(d) if d else 1
        return n

    shapes = re.findall(r"[a-z]+[0-9]*\[[0-9,]*\]", outputs)
    shape = max(shapes, key=size) if shapes else ""
    return " ".join(x for x in (head.lstrip("%"), kind, shape)
                    if x)[:width]


def kind_of(ev):
    """``mosaic``, ``collective`` or ``op``. A trace names an op by its
    whole HLO line, operands included, so a fusion that merely CONSUMES
    ``%all-reduce.5`` must not count: where the line has a left side,
    the instruction's own name decides for collectives and the
    ``custom_call_target`` for Mosaic."""
    head, _, rest = ev["name"].partition(" = ")
    if rest:
        if 'custom_call_target="tpu_custom_call"' in rest:
            return "mosaic"
        head = head.lower()
        return "collective" if any(w in head for w in COLLECTIVE_WORDS) \
            else "op"
    text = ev["text"]
    if any(w in text for w in MOSAIC_WORDS):
        return "mosaic"
    if any(w in text for w in COLLECTIVE_WORDS):
        return "collective"
    return "op"


def reduce(events):
    """All the trace metrics read, averaged over the devices used."""
    by_dev = {}
    for ev in events:
        by_dev.setdefault(ev["device"], []).append(ev)
    if not by_dev:
        return None
    ops = [e for e in events if not e.get("async")] or events
    start = min(e["start"] for e in ops)
    end = max(e["start"] + e["dur"] for e in ops)
    n = len(by_dev)
    busy = mosaic = coll = exposed = 0.0
    mosaic_events = 0
    op_time, gaps = {}, []
    for dev in sorted(by_dev):
        sync = [e for e in by_dev[dev] if not e.get("async")]
        lv = leaves(sync)
        spans = [(e["start"], e["start"] + e["dur"]) for e in lv]
        b, merged = union(spans)
        busy += b / n
        for a, c in zip(merged[:-1], merged[1:]):
            gaps.append(c[0] - a[1])
        c_spans, o_spans = [], []
        for e in lv:
            k = kind_of(e)
            span = (e["start"], e["start"] + e["dur"])
            (c_spans if k == "collective" else o_spans).append(span)
            if k == "mosaic":
                mosaic += e["dur"] / n
                mosaic_events += 1
            name = short_name(e["name"])
            op_time[name] = op_time.get(name, 0.0) + e["dur"] / n
        c_spans += [(e["start"], e["start"] + e["dur"])
                    for e in by_dev[dev]
                    if e.get("async") and kind_of(e) == "collective"]
        c_len, c_merged = union(c_spans)
        coll += c_len / n
        exposed += _minus(c_merged, union(o_spans)[1]) / n
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    gaps.sort(reverse=True)
    return {
        "devices": n, "window_s": end - start, "busy_s": busy,
        "mosaic_s": mosaic, "mosaic_events": mosaic_events,
        "collective_s": coll, "collective_exposed_s": exposed,
        "device_ops": [[k, v] for k, v in top],
        "idle_gaps": [
            ["between_device_ops:_all_%d_gaps" % len(gaps),
             sum(gaps) / n],
            ["between_device_ops:_the_longest_gap",
             gaps[0] if gaps else 0.0]],
    }
