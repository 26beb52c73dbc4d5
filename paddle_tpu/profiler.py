"""Profiler: host-side event spans + aggregate tables + chrome trace,
and the reduction of a device trace to a table by the program's own
scopes.

Reference: paddle/fluid/platform/profiler.{h,cc} (RAII ``RecordEvent``
profiler.h:81, ``EnableProfiler/DisableProfiler`` :166-171 aggregating
min/max/avg tables from profiler.proto), platform/device_tracer.cc
(CUPTI device activity), python/paddle/fluid/profiler.py:39-222
(profiler/start_profiler/stop_profiler/reset_profiler/cuda_profiler)
and tools/timeline.py (proto -> chrome://tracing JSON).

TPU-native redesign: there is no per-op runtime to instrument — the
whole step is ONE fused XLA program — so the reference's per-op table
is rebuilt from two things the program writes while it works:

  - every op is LOWERED under ``jax.named_scope("<phase>/<layer>/<op
    type>")`` (executor.run_block), which XLA carries into the
    optimized HLO's ``op_name``; ``scope_table`` charges each device
    event of a ``jax.profiler`` trace (``trace_path``) to that scope;
  - every ``RecordEvent`` also enters a ``jax.profiler.TraceAnnotation``,
    so the host spans lie in the trace's own host plane, on its own
    clock, whoever started the trace; ``scope_table`` charges each idle
    gap between dispatches to the span that covers it.

Chrome-trace export works from the same events (the timeline.py
role)."""

from __future__ import annotations

import bisect
import contextlib
import glob
import json
import os
import re
import threading
import time
import weakref
from dataclasses import dataclass
from typing import List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["RecordEvent", "record_event", "start_profiler",
           "stop_profiler", "reset_profiler", "reset_counters",
           "profiler", "export_chrome_tracing", "scope_table",
           "device_scope_table", "device_summary_table", "memory_table",
           "format_memory_table", "device_memory_table", "bump_counter",
           "counter_values",
           "cuda_profiler", "npu_profiler"]

_state = threading.local()
_lock = threading.Lock()
_enabled = False
_events: List["_Event"] = []
_device_trace_dir: Optional[str] = None
# wall time of the ``profiler_clock_sync`` span entered right after
# jax's start_trace: the span's own timestamp in the trace is the
# wall<->trace correspondence tools/trace_merge.py needs
_sync_wall: Optional[float] = None
# what the one xplane reader returned for the last capture: device
# ops, and the host plane's spans
_device_events: List[dict] = []
_device_table: Optional[dict] = None    # scope_table of them, memoized
# live Executors, asked for their executables' optimized HLO when a
# device table is wanted (the join from a TPU event to its op_name)
_executors: "weakref.WeakSet" = weakref.WeakSet()
# the stat every RecordEvent's annotation carries: tells the program's
# spans from the runtime's own host events
_SPAN_STAT = "span"
_SYNC_SPAN = "profiler_clock_sync"


@dataclass
class _Event:
    name: str
    start: float
    end: float
    thread: int
    depth: int
    args: Optional[dict] = None

    @property
    def dur(self):
        return self.end - self.start


def _stack():
    if not hasattr(_state, "stack"):
        _state.stack = []
    return _state.stack


class RecordEvent:
    """RAII span (reference: platform/profiler.h:81). Usable as a
    context manager or via ``record_event``. Always a
    ``jax.profiler.TraceAnnotation`` (a flag test and under 2 us when
    no trace runs), so the span lies in the host plane of ANY running
    jax.profiler trace; recorded in this module's own list only while
    the profiler is enabled — cheap enough to leave in hot paths.
    ``args`` (a small JSON-able dict, e.g. the serving engine's batch
    bucket/occupancy) rides into the trace event's stats and the
    chrome-trace span's args panel."""

    def __init__(self, name, args=None):
        self.name = name
        self.args = args
        self._t0 = None
        self._annotation = None

    def __enter__(self):
        # always: a jax.profiler trace may be running that this module
        # did not start (a benchmark's own start_trace); outside a
        # trace the annotation is a flag test
        self._annotation = TraceAnnotation(
            self.name, **{_SPAN_STAT: "paddle_tpu", **(self.args or {})})
        self._annotation.__enter__()
        if _enabled:
            self._t0 = time.perf_counter()
            _stack().append(self.name)
        return self

    def __exit__(self, *exc):
        self._annotation.__exit__(*exc)
        if self._t0 is not None:
            end = time.perf_counter()
            stack = _stack()
            depth = len(stack) - 1
            stack.pop()
            ev = _Event(name=self.name, start=self._t0, end=end,
                        thread=threading.get_ident(), depth=depth,
                        args=self.args)
            with _lock:
                _events.append(ev)
        return False


record_event = RecordEvent


# -- always-on scalar counters ---------------------------------------
# Unlike spans, counters accumulate regardless of start_profiler: the
# input-pipeline stall metric (time the device dispatch loop waited on
# host data) must be measurable from a plain bench/probe run without
# turning on the full event recorder. Cost per bump is one lock + one
# float add. Storage is the process-wide observability.MetricsRegistry
# (same hot-path cost), so these counters show up in /metrics and
# obs_dump next to every other subsystem's.
_bump_names: set = set()


def _registry():
    from .observability import registry
    return registry()


def bump_counter(name, value=1.0):
    _bump_names.add(name)  # set.add is atomic under the GIL
    _registry().counter(name).inc(value)


def counter_values() -> dict:
    reg = _registry()
    return {n: reg.counter(n).value for n in sorted(_bump_names)}


def reset_counters():
    """Zero the always-on counters. Deliberately SEPARATE from
    ``reset_profiler``: counters back stall accounting and bench
    probes that must survive span resets — a probe that clears spans
    between phases must not silently lose its stall tally."""
    reg = _registry()
    for n in list(_bump_names):
        reg.counter(n).reset()


def start_profiler(state="All", trace_path=None):
    """Reference: profiler.py start_profiler (state CPU/GPU/All; GPU
    maps to the TPU/XLA device trace here). ``trace_path`` starts a
    jax.profiler trace capturing device activity (xprof)."""
    global _enabled, _device_trace_dir, _sync_wall
    if _enabled:
        return
    _enabled = True
    if trace_path and state in ("GPU", "TPU", "All"):
        try:
            import jax
            jax.profiler.start_trace(trace_path)
            _device_trace_dir = trace_path
            _sync_wall = time.time()
            with RecordEvent(_SYNC_SPAN):
                pass
        except Exception:
            _device_trace_dir = None
            _sync_wall = None


def reset_profiler():
    """Clear recorded SPANS (host + device events) only. The always-on
    counters are NOT touched — ``pyreader`` stall accounting and bench
    probes depend on them accumulating across span resets; clear those
    explicitly with ``reset_counters()``."""
    global _device_table
    with _lock:
        _events.clear()
        _device_events.clear()
        _device_table = None


def stop_profiler(sorted_key=None, profile_path=None, steps=1):
    """Aggregate + print the event table (reference: DisableProfiler →
    PrintProfiler, profiler.cc) and, after a device capture, the
    device time by scope (per step where ``steps`` says how many the
    capture holds); optionally dump chrome tracing JSON to
    ``profile_path`` (the timeline.py step, no separate tool needed)."""
    global _enabled, _device_trace_dir
    if not _enabled:
        return
    _enabled = False
    if _device_trace_dir is not None:
        try:
            import jax
            jax.profiler.stop_trace()
            _collect_device_events(_device_trace_dir)
        except Exception:
            pass
        _device_trace_dir = None
    if profile_path:
        export_chrome_tracing(profile_path)
    print(summary_table(sorted_key))
    if _device_events:
        print(device_summary_table(steps=steps))


def summary_table(sorted_key=None) -> str:
    with _lock:
        events = list(_events)
    agg = {}
    for ev in events:
        rec = agg.setdefault(ev.name,
                             {"calls": 0, "total": 0.0,
                              "min": float("inf"), "max": 0.0})
        rec["calls"] += 1
        rec["total"] += ev.dur
        rec["min"] = min(rec["min"], ev.dur)
        rec["max"] = max(rec["max"], ev.dur)
    wall = sum(r["total"] for r in agg.values()) or 1.0
    rows = []
    for name, r in agg.items():
        rows.append((name, r["calls"], r["total"] * 1e3,
                     r["min"] * 1e3, r["max"] * 1e3,
                     r["total"] / r["calls"] * 1e3,
                     r["total"] / wall))
    key = {None: lambda x: -x[2], "default": lambda x: -x[2],
           "total": lambda x: -x[2], "calls": lambda x: -x[1],
           "name": lambda x: x[0], "max": lambda x: -x[4],
           "min": lambda x: -x[3], "ave": lambda x: -x[5]}[sorted_key]
    rows.sort(key=key)
    lines = ["------------------------->     Profiling Report     "
             "<-------------------------", "",
             "%-32s %8s %12s %10s %10s %10s %8s" %
             ("Event", "Calls", "Total(ms)", "Min(ms)", "Max(ms)",
              "Ave(ms)", "Ratio")]
    for name, calls, total, mn, mx, ave, ratio in rows:
        lines.append("%-32s %8d %12.4f %10.4f %10.4f %10.4f %7.2f%%"
                     % (name[:32], calls, total, mn, mx, ave,
                        ratio * 100.0))
    return "\n".join(lines)


# -- from a trace to a table by scope ----------------------------------

OP_LINE, ASYNC_LINE, MODULE_LINE = "XLA Ops", "Async XLA Ops", \
    "XLA Modules"
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")
# the vocabulary executor.run_block lowers under; the leftmost match
# in an op_name is the outermost scope (a gradient op's op_name
# repeats its scope inside transpose(...) and jvp(...))
_SCOPE = re.compile(r"(?:^|/)(fwd|bwd|opt|sync)/([^/]+)/"
                    r"((?:\(xla\) )?[^/()\s]+)")
UNSCOPED = "unscoped"


def _collect_device_events(trace_dir):
    """THE xplane reader: the ``.xplane.pb`` files of a jax.profiler
    capture into plain events — the DeviceTracer/CUPTI-activity analog
    (reference: platform/device_tracer.cc:41). An event is a dict:
    ``name``, ``plane``, ``line``, ``ts_ns``, ``dur_ns``, ``stats`` (the
    event's own stats) and ``host`` (True for a span of the host
    plane). Device planes ("/device:TPU:*") carry an op line that nests
    (``XLA Ops``: a ``while`` holds its body's ops), the in-flight part
    of asynchronous ops beside it and the programs' intervals (``XLA
    Modules``); on CPU backends the XLA runtime threads ("tf_*" lines
    of the host plane) play the device's role. Every other line of the
    host plane gives host spans: ``TraceAnnotation``s (every
    ``RecordEvent`` is one) and the runtime's own; the Python tracer's
    per-call events ("$...") are left out. Returns the events and
    keeps them for ``device_summary_table`` / ``export_chrome_tracing``."""
    global _device_events, _device_table
    from jax.profiler import ProfileData
    events = []
    for f in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                       recursive=True):
        data = ProfileData.from_file(f)     # owns what planes point at
        planes = list(data.planes)
        on_device = any(p.name.startswith("/device:") for p in planes)
        for plane in planes:
            device_plane = plane.name.startswith("/device:")
            if not device_plane and not plane.name.endswith(":CPU"):
                continue
            for line in plane.lines:
                host = not device_plane and (
                    on_device or not line.name.startswith("tf_"))
                for e in line.events:
                    if e.name.startswith(("end: ", "begin: ", "$")) \
                            or (e.duration_ns <= 0 and not host):
                        continue
                    try:
                        stats = {k: v for k, v in e.stats}
                    except Exception:
                        stats = {}
                    events.append({"name": e.name, "plane": plane.name,
                                   "line": line.name, "host": host,
                                   "ts_ns": float(e.start_ns),
                                   "dur_ns": float(e.duration_ns),
                                   "stats": stats})
    with _lock:
        _device_events, _device_table = events, None
    return events


def _instruction(ev):
    """(module, instruction) of a device op event, or None. A TPU trace
    names an op by its whole HLO line and its program by an event of
    the ``XLA Modules`` line (looked up by the caller); a CPU trace
    gives both as stats."""
    stats = ev.get("stats") or {}
    if "hlo_op" in stats and "hlo_module" in stats:
        return stats["hlo_module"], stats["hlo_op"]
    if ev["line"] in (OP_LINE, ASYNC_LINE):
        return None, ev["name"].split(" = ", 1)[0].strip().lstrip("%")
    return None


_XLA_MADE = "(xla) "


def _opcode(instruction):
    """``copy-done.66`` -> ``copy-done``, ``broadcast.7.clone.2.clone``
    -> ``broadcast``."""
    return re.sub(r"(?:\.clone|[.\d])+$", "", instruction)


def _is_collective(ev):
    head = ev["name"].split(" = ", 1)[0]
    return any(w in head for w in _COLLECTIVES)


# one row of HLO text: an instruction, a computation's head, the names
# an instruction's operands and attributes mention (hlo_op_names and
# memory_table read the same text)
_HLO_INSTR = re.compile(r"^\s+(ROOT )?%?([\w.\-]+) = ")
_HLO_COMP = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_HLO_OPERAND = re.compile(r"%([\w.\-]+)")


def hlo_op_names(optimized_hlo):
    """{module: {instruction: op_name}} of optimized HLO text(s) (what
    ``Executor.aot_artifacts()`` gives as ``optimized_hlo``). What XLA
    made itself carries no ``op_name``; it is charged by structure,
    never by guess: a fusion takes the scope of what it fused (the
    last scoped instruction of the computation it calls), and data
    movement (a copy between layouts or memory spaces, a slice, the
    bits of a random generator) the scope of the first scoped
    instruction that consumes it, marked ``(xla)`` in place of the op
    type. What has neither stays without."""
    texts = [optimized_hlo] if isinstance(optimized_hlo, str) \
        else list(optimized_hlo or ())
    out = {}
    op_name = re.compile(r'op_name="([^"]*)"')
    calls = re.compile(r"calls=%?([\w.\-]+)")
    for text in texts:
        if not text:
            continue
        head = re.match(r"HloModule ([\w.\-]+)", text)
        names = out.setdefault(head.group(1) if head else "", {})
        by_comp, called, current = {}, {}, None
        bare, users = [], {}
        for row in text.splitlines():
            m = _HLO_INSTR.match(row)
            if m is None:
                c = _HLO_COMP.match(row)
                if c is not None:
                    current = by_comp.setdefault(c.group(2), [])
                    users = {}      # names are a computation's own
                continue
            name = m.group(2)
            n = op_name.search(row)
            n = n.group(1) if n else ""
            names.setdefault(name, n)
            if current is not None:
                current.append(n)
            for used in _HLO_OPERAND.findall(row[m.end():].split(
                    ", metadata=", 1)[0]):
                users.setdefault(used, []).append(name)
            if not _SCOPE.search(n):
                c = calls.search(row)
                if c is not None:
                    called[name] = c.group(1)
                bare.append((name, users))
        for name, callee in called.items():
            for n in reversed(by_comp.get(callee, ())):
                if _SCOPE.search(n):
                    names[name] = n
                    break
        # consumers come later in a scheduled module: resolve from the
        # end, so a chain of XLA's own (copy-start -> copy-done ->
        # bitcast -> fusion) reaches the op it feeds
        for name, users in reversed(bare):
            if _SCOPE.search(names[name]):
                continue
            for user in users.get(name, ()):
                m = _SCOPE.search(names.get(user, ""))
                if m is not None:
                    names[name] = "%s/%s/%s%s" % (
                        m.group(1), m.group(2), _XLA_MADE,
                        _opcode(name))
                    break
    return out


def _leaves(events):
    """Events of one op line that contain no other event of it (a
    ``while`` spans the whole scan and holds its body's ops)."""
    evs = sorted(events, key=lambda e: (e["ts_ns"], -e["dur_ns"]))
    out, open_ = [], []          # open_: [event, end, has_child]
    for ev in evs:
        end = ev["ts_ns"] + ev["dur_ns"]
        still = []
        for rec in open_:
            if rec[1] <= ev["ts_ns"]:
                if not rec[2]:
                    out.append(rec[0])
            else:
                if rec[1] >= end:
                    rec[2] = True
                still.append(rec)
        open_ = still + [[ev, end, False]]
    out.extend(rec[0] for rec in open_ if not rec[2])
    return sorted(out, key=lambda e: e["ts_ns"])


def scope_table(events, optimized_hlo=None):
    """Device time by the program's own scopes (the reference's
    ``fluid.profiler`` op table, rebuilt for one fused XLA program).

    ``events`` is what ``_collect_device_events`` returns;
    ``optimized_hlo`` the optimized HLO text(s) of the executables that
    ran (``Executor.aot_artifacts()``): a TPU event carries no
    ``op_name`` of its own, so it is joined by instruction name. Only
    LEAF events of the op line count; each is charged to the outermost
    ``<phase>/<layer>/<op type>`` in its ``op_name``, else to
    ``unscoped``. Times are milliseconds, averaged over the devices
    traced; every ``by_*`` table sums to ``busy_ms``. The in-flight part
    of asynchronous collectives overlaps the op line and is listed
    apart (``async_collectives_by_layer``). Each idle gap between leaf ops is ``inside a
    program`` or, between two dispatches, charged to the ``RecordEvent``
    span of the host plane that covers most of it.

    ``note`` says when the table cannot be trusted: an executable
    loaded from a store written before its program named its ops keeps
    the old ``op_name``s (the store's key ignores metadata), and most
    of its time then reads ``unscoped``."""
    names = hlo_op_names(optimized_hlo)
    by_module = {m.split("(")[0]: v for m, v in names.items()}
    planes = {}
    for ev in events:
        if not ev["host"] and _instruction(ev) is not None:
            planes.setdefault(ev["plane"], []).append(ev)
    modules = {}
    for ev in events:
        if ev["line"] == MODULE_LINE:
            modules.setdefault(ev["plane"], []).append(
                (ev["ts_ns"], ev["ts_ns"] + ev["dur_ns"],
                 ev["name"].split("(")[0]))
    spans = [ev for ev in events
             if ev["host"] and _SPAN_STAT in (ev.get("stats") or {})]
    n = max(1, len(planes))
    tables = {k: {} for k in ("by_phase", "by_layer", "by_layer_op",
                              "by_phase_layer", "collectives_by_layer",
                              "async_collectives_by_layer")}
    busy = unscoped = collective = window = 0.0
    idle, gaps = {}, []

    def module_at(plane, t):
        mods = modules.get(plane)
        if not mods:
            return None
        i = bisect.bisect_right(mods, (t, float("inf"), "")) - 1
        return mods[i] if i >= 0 and mods[i][1] >= t else None

    def scope_of(ev):
        module, name = _instruction(ev)
        if module is None:
            at = module_at(ev["plane"], ev["ts_ns"])
            module = at[2] if at else None
        if module is None and len(by_module) == 1:
            module = next(iter(by_module))  # no module line to ask
        m = _SCOPE.search(by_module.get(module, {}).get(name, ""))
        return m.groups() if m else None

    def add(table, key, ms):
        tables[table][key] = tables[table].get(key, 0.0) + ms

    for plane, evs in planes.items():
        if plane in modules:
            modules[plane].sort()
        lv = _leaves([e for e in evs if e["line"] != ASYNC_LINE])
        if not lv:
            continue
        window += (max(e["ts_ns"] + e["dur_ns"] for e in lv)
                   - lv[0]["ts_ns"]) / 1e6 / n
        end = None
        for ev in lv:
            ms = ev["dur_ns"] / 1e6 / n
            busy += ms
            scope = scope_of(ev)
            if _is_collective(ev):
                # inside the rows below too: GSPMD's all-reduces keep
                # the op_name of an op whose output they reduce
                collective += ms
                add("collectives_by_layer",
                    scope[1] if scope else UNSCOPED, ms)
            if scope is None:
                # what XLA made itself (copies between memory spaces,
                # a scan's bookkeeping) is told apart by its opcode
                unscoped += ms
                scope = (UNSCOPED, UNSCOPED,
                         _opcode(_instruction(ev)[1]))
            phase, layer, op = scope
            add("by_phase", phase, ms)
            add("by_layer", layer, ms)
            add("by_layer_op", "%s %s" % (layer, op), ms)
            add("by_phase_layer", "%s/%s" % (phase, layer), ms)
            if end is not None and ev["ts_ns"] > end:
                gaps.append((ev["ts_ns"] - end, plane, end))
            end = max(end or 0.0, ev["ts_ns"] + ev["dur_ns"])
        for ev in evs:
            if ev["line"] == ASYNC_LINE and _is_collective(ev):
                scope = scope_of(ev)
                add("async_collectives_by_layer",
                    scope[1] if scope else UNSCOPED,
                    ev["dur_ns"] / 1e6 / n)
    longest = []
    for dur, plane, start in sorted(gaps, reverse=True):
        at = module_at(plane, start)
        if at is not None and at[1] >= start + dur:
            where = "inside a program"
        else:
            # (a CPU trace has no program line: every gap lands here)
            best, cover = None, 0.0
            for sp in spans:
                over = min(start + dur, sp["ts_ns"] + sp["dur_ns"]) \
                    - max(start, sp["ts_ns"])
                if over > cover or (over == cover and over > 0 and
                                    sp["dur_ns"] < best["dur_ns"]):
                    best, cover = sp, over
            where = "between dispatches: %s" % (
                best["name"] if best else "(no span)")
        idle[where] = idle.get(where, 0.0) + dur / 1e6 / n
        if len(longest) < 5:
            longest.append([where, dur / 1e6])
    note = None
    if busy and unscoped > 0.5 * busy:
        note = ("%.0f%% of the device time carries no scope: %s"
                % (100.0 * unscoped / busy,
                   "the executable was built before its program named "
                   "its ops (a store or cache written by an older "
                   "tree: its key ignores metadata) -- clear it and "
                   "trace again" if any(by_module.values()) else
                   "no optimized HLO was given to join the events "
                   "against"))
    return dict(tables, devices=len(planes), window_ms=window,
                busy_ms=busy, unscoped_ms=unscoped,
                collective_ms=collective, idle_ms=sum(idle.values()),
                idle_by_cause=idle, longest_gaps=longest, note=note)


def _ranked_rows(title, unit, share, table, limit, per, whole):
    """One table's rows, largest first, as lines of text: each value
    over ``per`` and as a share of ``whole``; past ``limit`` the rest
    in one row. Nothing for an empty table."""
    rows = sorted(table.items(), key=lambda kv: -kv[1])
    if not rows:
        return []
    lines = ["", "%-52s %12s %8s" % (title, unit, share)]
    lines += ["%-52s %12.4f %7.2f%%" % (name[:52], v / per,
                                        100.0 * v / whole)
              for name, v in rows[:limit]]
    if limit and len(rows) > limit:
        lines.append("%-52s %12.4f" % (
            "... %d more" % (len(rows) - limit),
            sum(v for _, v in rows[limit:]) / per))
    return lines


def format_scope_table(table, steps=1) -> str:
    """``scope_table``'s result as text; ``steps`` divides every time
    (the steps the capture holds)."""
    steps = max(1, steps)
    unit = "ms/step" if steps > 1 else "ms"
    busy = table["busy_ms"] or 1.0
    lines = ["------------------------->   Device (XLA) Report   "
             "<-------------------------"]
    if table["note"]:
        lines.append("!! " + table["note"])
    lines.append(
        "%d device(s), %d step(s), %s: busy %.3f of a %.3f window, "
        "idle %.3f, unscoped %.3f (%.2f%% of busy), collectives %.3f"
        % (table["devices"], steps, unit, table["busy_ms"] / steps,
           table["window_ms"] / steps, table["idle_ms"] / steps,
           table["unscoped_ms"] / steps,
           100.0 * table["unscoped_ms"] / busy,
           table["collective_ms"] / steps))
    for title, key, limit in (
            ("Phase", "by_phase", None), ("Layer", "by_layer", None),
            ("Phase/layer", "by_phase_layer", None),
            ("Layer, op type", "by_layer_op", 30),
            ("Collectives (inside the rows above), by layer",
             "collectives_by_layer", None),
            ("Asynchronous collectives (overlap the rows above)",
             "async_collectives_by_layer", None),
            ("Idle, by cause", "idle_by_cause", None)):
        lines += _ranked_rows(title, unit, "of busy", table[key], limit,
                              steps, busy)
    if table["longest_gaps"]:
        lines += ["", "Longest idle gaps (ms, whole capture)"]
        lines += ["%-52s %12.4f" % (w[:52], ms)
                  for w, ms in table["longest_gaps"]]
    return "\n".join(lines)


# -- from scheduled HLO text to the step's fullest moment by scope -----

_ELEMENT_BYTES = {"pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
                  "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s32": 4,
                  "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
                  "c64": 8, "c128": 16}
_ARRAY = re.compile(r"([a-z]\w*)\[([^\]]*)\](?:\{([^}]*)\})?")
# ops that own no bytes: a value of nothing ...
_OWNS_NOTHING = ("parameter", "constant", "after-all", "partition-id",
                 "replica-id")
# ... and ops whose value IS their first operand's bytes (a ``-done``
# stands for its ``-start`` the same way)
_STANDS_FOR_OPERAND = ("bitcast", "while", "optimization-barrier",
                       "add-dependency")
_CALLED = re.compile(r"(?:body|to_apply|true_computation|"
                     r"false_computation)=%?([\w.\-]+)"
                     r"|branch_computations=\{([^}]*)\}")


@dataclass
class _Row:
    """One instruction of a computation's text."""
    name: str
    shape: str
    opcode: str
    operands: List[str]     # every %name its operands and attributes say
    called: List[str]       # computations a while / call / conditional runs
    root: bool
    index: Optional[int]    # a get-tuple-element's


def _split_shape(text):
    """The shape an instruction's row starts with, and the rest."""
    if not text.startswith("("):
        shape, _, rest = text.partition(" ")
        return shape, rest
    depth = 0
    for i, ch in enumerate(text):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return text[:i + 1], text[i + 2:]
    return text, ""


def _hbm_bytes(shape):
    """(bytes in HBM, the array's own text) of every array of a
    shape's text, in order (a tuple gives one entry an element, nested
    ones flattened): the dimensions padded to the layout's tiles, each
    tile laid over the minor dimensions the tile before it left
    (``T(8,128)(2,1)``), times the element's bytes (``E(n)`` bits where
    the layout packs them). An array the layout puts in another memory
    space (``S(n)``: VMEM, SMEM, the sync flags) holds none."""
    out = []
    for m in _ARRAY.finditer(shape):
        dtype, dims, layout = m.group(1), m.group(2), m.group(3) or ""
        if re.search(r"S\([1-9]", layout) or dtype not in _ELEMENT_BYTES \
                and not dtype.startswith("f8"):
            out.append((0, m.group(0)))
            continue
        dims = [int(d.lstrip("<=")) for d in dims.split(",") if d]
        order, _, tiling = layout.partition(":")
        order = [int(i) for i in order.split(",") if i]
        if len(order) == len(dims):
            dims = [dims[i] for i in reversed(order)]   # major first
        for tile in re.findall(r"\((\d[\d,]*)\)",
                               tiling.split("S(")[0].split("E(")[0]):
            tile = [int(t) for t in tile.split(",")]
            dims = [1] * (len(tile) - len(dims)) + dims
            k = len(dims) - len(tile)
            dims = dims[:k] + [-(-d // t) for d, t in zip(dims[k:], tile)] \
                + tile
        n = 1
        for d in dims:
            n *= d
        bits = re.search(r"E\((\d+)\)", layout)
        out.append((n * int(bits.group(1)) // 8 if bits
                    else n * _ELEMENT_BYTES.get(dtype, 1), m.group(0)))
    return out


def _hlo_computations(text):
    """({computation: [_Row, ...] in schedule order}, the entry's name)
    of one module's text."""
    comps, entry, current = {}, None, None
    for line in text.splitlines():
        m = _HLO_INSTR.match(line)
        if m is None:
            c = _HLO_COMP.match(line)
            if c is not None:
                current = comps.setdefault(c.group(2), [])
                if c.group(1):
                    entry = c.group(2)
            continue
        if current is None:
            continue
        shape, rest = _split_shape(line[m.end():])
        rest = rest.split(", metadata=", 1)[0]
        opcode = rest.split("(", 1)[0]
        called = [n.lstrip("%") for a, b in _CALLED.findall(rest)
                  for n in ([a] if a else re.split(r",\s*", b))] \
            if opcode in ("while", "call", "conditional") else []
        index = re.search(r"index=(\d+)", rest) \
            if opcode == "get-tuple-element" else None
        current.append(_Row(m.group(2), shape, opcode,
                            _HLO_OPERAND.findall(rest), called,
                            bool(m.group(1)),
                            int(index.group(1)) if index else None))
    return comps, entry


def _fullest_moment(comp, comps, walked):
    """(peak bytes, the buffers live at it as (bytes, instruction, array
    text), the innermost instruction it falls at as (computation, name,
    opcode)) of one computation and whatever it runs."""
    if comp in walked:
        return walked[comp]
    nothing = walked[comp] = (0, [], None)
    rows = comps.get(comp, ())
    users = {}
    for i, row in enumerate(rows):
        for used in row.operands:
            users.setdefault(used, []).append(i)
    done_of = {row.operands[0]: row for row in rows
               if row.opcode.endswith("-done") and row.operands}
    # refs[value]: the buffers it stands for, a tuple's element by
    # element; sized[buffer], born[buffer]; a buffer is (owner, element)
    refs, sized, born, returned = {}, {}, {}, set()
    for i, row in enumerate(rows):
        first = refs.get(row.operands[0]) if row.operands else None
        if row.opcode in _OWNS_NOTHING:
            refs[row.name] = set()
        elif row.opcode == "tuple":
            refs[row.name] = [_flat(refs.get(o)) for o in row.operands]
        elif row.opcode == "get-tuple-element":
            refs[row.name] = first[row.index] \
                if isinstance(first, list) and row.index < len(first) \
                else _flat(first)
        elif row.opcode in _STANDS_FOR_OPERAND \
                or row.opcode.endswith("-done"):
            refs[row.name] = first if first is not None else set()
        else:
            # an asynchronous pair's result exists from its start on
            owned = done_of[row.name].shape \
                if row.opcode.endswith("-start") and row.name in done_of \
                else row.shape
            keys = []
            for k, size in enumerate(_hbm_bytes(owned)):
                keys.append((row.name, k))
                sized[keys[-1]], born[keys[-1]] = size, i
            refs[row.name] = [{k} for k in keys] \
                if owned.startswith("(") else set(keys)
        if row.root:
            returned = _flat(refs[row.name])
    dies = dict(born)
    for name, r in refs.items():
        last = max(users.get(name, (0,)))
        for key in _flat(r):
            dies[key] = max(dies[key], last)
    counted = [key for key in born
               if key not in returned and sized[key][0]]
    delta = [0] * (len(rows) + 1)
    for key in counted:
        delta[born[key]] += sized[key][0]
        delta[dies[key] + 1] -= sized[key][0]
    best, at, held, below = 0, None, 0, nothing
    for i, row in enumerate(rows):
        held += delta[i]
        inner = max((_fullest_moment(c, comps, walked)
                     for c in row.called),
                    key=lambda w: w[0], default=nothing)
        if held + inner[0] > best:
            best, at, below = held + inner[0], i, inner
    if at is not None:
        walked[comp] = (
            best,
            [(sized[key][0], key[0], sized[key][1])
             for key in counted if born[key] <= at <= dies[key]]
            + below[1],
            below[2] or (comp, rows[at].name, rows[at].opcode))
    return walked[comp]


def memory_table(optimized_hlo, temp_bytes=None):
    """The step's fullest moment in HBM by the program's own scopes,
    MODELLED from one executable's optimized HLO text (what
    ``Executor.aot_artifacts()`` gives as ``optimized_hlo``: it is
    scheduled, so the order of a computation's rows is the order they
    run in). ``temp_bytes`` is the compiler's own count of the
    executable's temporaries (the artifact's ``memory`` record).

    The walk starts at the entry and goes down into every ``while``,
    ``call`` and ``conditional`` (a scan's step lies in its ``while``
    body). A buffer is born at its instruction (an asynchronous pair's
    at the ``-start``) and dies after the last instruction that reads
    it, through whatever stands for it meanwhile (``bitcast``,
    ``tuple``, ``get-tuple-element``, a ``-done``, a ``while``'s
    result). Its bytes come from the shape, the element type and the
    layout's tiles (``_hbm_bytes``). No bytes of their own: parameters
    (the executable's arguments, a loop's carried values), constants,
    the values a computation returns (outputs, and what a loop body
    writes into the carried buffers), and arrays the layout puts
    outside HBM. While a ``while`` runs, its body's own peak lies on
    top of what the caller holds.

    It is a model and not the compiler's buffer assignment: it shares
    no buffer between two values (no in-place update, no reuse of a
    dead buffer's space mid-fusion), counts nothing a fusion or a
    Mosaic call holds inside itself, and knows no fragmentation.
    ``coverage`` is the modelled peak over ``temp_bytes``: near 1 the
    tables can be read as the compiler's, far from 1 they say which
    scopes are live together and no more.

    Returns ``peak_bytes``; ``instruction``, ``opcode``, ``scope``
    (``phase/layer/op type``) and ``computation`` of the innermost
    instruction the peak falls at; the live bytes there ``by_phase``,
    ``by_layer`` and ``by_layer_op`` (each sums to ``peak_bytes``);
    ``largest``, the twenty largest live buffers; ``buffers``, how many
    were live; ``temp_bytes`` and ``coverage``."""
    head = re.match(r"HloModule ([\w.\-]+)", optimized_hlo or "")
    names = hlo_op_names(optimized_hlo).get(
        head.group(1) if head else "", {})
    comps, entry = _hlo_computations(optimized_hlo or "")
    peak, live, at = _fullest_moment(entry, comps, {})
    opcodes = {row.name: row.opcode
               for rows in comps.values() for row in rows}

    def scope_of(name):
        m = _SCOPE.search(names.get(name, ""))
        return m.groups() if m \
            else (UNSCOPED, UNSCOPED, _opcode(opcodes[name]))

    tables = {"by_phase": {}, "by_layer": {}, "by_layer_op": {}}
    for size, name, _array in live:
        phase, layer, op = scope_of(name)
        for table, key in (("by_phase", phase), ("by_layer", layer),
                           ("by_layer_op", "%s %s" % (layer, op))):
            tables[table][key] = tables[table].get(key, 0) + size
    comp, name, opcode = at or (None, None, None)
    return dict(
        tables, peak_bytes=peak, computation=comp, instruction=name,
        opcode=opcode, scope="/".join(scope_of(name)) if name else None,
        buffers=len(live),
        largest=[{"bytes": size, "instruction": n, "shape": array,
                  "scope": "/".join(scope_of(n))}
                 for size, n, array in sorted(live, reverse=True)[:20]],
        temp_bytes=temp_bytes,
        coverage=peak / temp_bytes if temp_bytes else None)


def _flat(refs):
    """Every buffer a value stands for, a tuple's elements together."""
    if isinstance(refs, list):
        return set().union(*refs) if refs else set()
    return set(refs or ())


def format_memory_table(table) -> str:
    """``memory_table``'s result as text."""
    gib = 2.0 ** 30
    peak = table["peak_bytes"] or 1
    cover = "coverage unknown (no temp_bytes given)" \
        if table["coverage"] is None else \
        "coverage %.3f of the compiler's temp_bytes %.3f GiB" % (
            table["coverage"], table["temp_bytes"] / gib)
    lines = ["-------------------->   HBM at the fullest moment (a MODEL "
             "of the scheduled HLO)   <--------------------",
             "!! a model, not the buffer assignment (no in-place reuse, "
             "nothing inside a fusion or kernel): %s" % cover,
             "modelled peak %.3f GiB in %d live buffers, at %s (%s) in %s"
             % (table["peak_bytes"] / gib, table["buffers"],
                table["instruction"], table["scope"],
                table["computation"])]
    for title, key, limit in (("Phase", "by_phase", None),
                              ("Layer", "by_layer", None),
                              ("Layer, op type", "by_layer_op", 20)):
        lines += _ranked_rows(title, "GiB", "of peak", table[key], limit,
                              gib, peak)
    lines += ["", "Largest live buffers (GiB)"]
    lines += ["%-30s %-40s %9.4f  %s" % (r["instruction"][:30],
                                         r["scope"][:40],
                                         r["bytes"] / gib, r["shape"][:60])
              for r in table["largest"]]
    return "\n".join(lines)


def _registered_artifacts():
    """The record of every executable the live Executors hold whose
    backend gives its optimized HLO."""
    return [rec for exe in list(_executors)
            for rec in exe.aot_artifacts() if rec.get("optimized_hlo")]


def _registered_hlo():
    """Optimized HLO of every executable the live Executors hold."""
    return [rec["optimized_hlo"] for rec in _registered_artifacts()]


def device_memory_table():
    """``memory_table`` of the executable the live Executors dispatched
    most (the step; of equals the one built last, so a startup
    program's does not stand in), against the compiler's own count of its
    temporaries, with the artifact's ``entry``, ``shape_key``,
    ``memory`` and ``state`` records beside; None before any
    executable was built."""
    built = _registered_artifacts()
    if not built:
        return None
    step = max(reversed(built),
               key=lambda rec: rec.get("dispatches") or 0)
    table = memory_table(step["optimized_hlo"],
                         (step.get("memory") or {}).get("temp_bytes"))
    return dict(table, **{k: step.get(k) for k in (
        "entry", "shape_key", "from_cache", "dispatches", "memory",
        "state")})


def device_scope_table():
    """``scope_table`` of the last capture against the live Executors'
    executables (computed once a capture)."""
    global _device_table
    with _lock:
        events, table = list(_device_events), _device_table
    if table is None:
        table = scope_table(events,
                            _registered_hlo() if events else None)
        with _lock:
            _device_table = table
    return table


def device_summary_table(sorted_key=None, steps=1) -> str:
    """DEVICE time of the last capture by the program's scopes — phase,
    layer kind, op type — with what stayed unscoped and what the idle
    gaps waited for (reference: the 'GPU' rows of PrintProfiler +
    tools/timeline.py device tracks; rows used to be raw HLO names,
    which on a TPU are numbered fusions). ``sorted_key`` is accepted
    for the reference's signature, rows sort by time; ``steps`` divides
    every time (the steps the capture holds)."""
    return format_scope_table(device_scope_table(), steps=steps)


def export_chrome_tracing(path):
    """ONE chrome://tracing JSON merging host spans and the captured
    device-op events on separate tracks (reference: tools/timeline.py
    merging profiler.proto host records with device_tracer.cc CUPTI
    records). After a device capture the host spans are the trace's
    own (every RecordEvent is a TraceAnnotation in its host plane), so
    both tracks share the capture's clock; without one they are this
    module's perf_counter spans from their first."""
    with _lock:
        events = list(_events)
        captured = list(_device_events)
    dev = [ev for ev in captured if not ev["host"]]
    host_spans = [ev for ev in captured if ev["host"]
                  and _SPAN_STAT in ev["stats"]]
    if dev and host_spans:
        lines = sorted({ev["line"] for ev in host_spans})
        trace_events = [
            {"name": ev["name"], "cat": "host", "ph": "X",
             "ts": ev["ts_ns"] / 1e3, "dur": ev["dur_ns"] / 1e3,
             "pid": 0, "tid": lines.index(ev["line"]),
             "args": {k: v for k, v in ev["stats"].items()
                      if k != _SPAN_STAT}}
            for ev in host_spans if ev["name"] != _SYNC_SPAN]
        sync = [ev for ev in host_spans if ev["name"] == _SYNC_SPAN]
        now_wall = _sync_wall if sync and _sync_wall else time.time()
        now_ts = sync[0]["ts_ns"] / 1e3 if sync else 0.0
    else:
        base = min(ev.start for ev in events) if events else 0.0
        trace_events = [
            {"name": ev.name, "cat": "host", "ph": "X",
             "ts": (ev.start - base) * 1e6, "dur": ev.dur * 1e6,
             "pid": 0, "tid": ev.thread % 10000,
             "args": dict({"depth": ev.depth}, **(ev.args or {}))}
            for ev in events]
        # wall-clock anchor: trace ts is perf_counter-based (per-process
        # arbitrary epoch), so cross-process merge (tools/
        # trace_merge.py) needs a (wall_time, trace_ts) correspondence
        now_wall = time.time()
        now_ts = (time.perf_counter() - base) * 1e6
    tids = {}
    for ev in dev:
        tid = tids.setdefault((ev["plane"], ev["line"]),
                              len(tids) + 1)
        trace_events.append(
            {"name": ev["name"], "cat": "device", "ph": "X",
             "ts": ev["ts_ns"] / 1e3, "dur": ev["dur_ns"] / 1e3,
             "pid": 1, "tid": tid, "args": {"stream": ev["line"]}})
    from .observability import journal as _obs_journal
    meta = [{"name": "process_name", "ph": "M", "pid": 0,
             "args": {"name": "host"}},
            {"name": "process_name", "ph": "M", "pid": 1,
             "args": {"name": "device (XLA)"}},
            {"name": "clock_sync", "ph": "M", "pid": 0,
             "args": {"wall_time_s": now_wall, "trace_ts_us": now_ts,
                      "role": _obs_journal.get_role(),
                      "pid_os": os.getpid()}}]
    # always-on counters ride along as chrome counter samples (one
    # terminal sample per counter — totals, not a timeseries)
    for cname, cval in counter_values().items():
        trace_events.append(
            {"name": cname, "cat": "counter", "ph": "C",
             "ts": now_ts, "pid": 0, "tid": 0,
             "args": {cname: cval}})
    trace = {"traceEvents": meta + trace_events}
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(trace, f)


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path=None,
             trace_path=None):
    """Reference: profiler.py profiler() context manager."""
    start_profiler(state, trace_path=trace_path)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def cuda_profiler(*args, **kwargs):
    """Accepted for API parity; device tracing on TPU goes through
    ``trace_path``/jax.profiler (reference: profiler.py cuda_profiler
    wrapping cudaProfilerStart/Stop)."""
    yield


npu_profiler = cuda_profiler
