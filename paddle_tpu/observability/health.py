"""Per-role health plane: watchdog, flight recorder, and the
machine-readable ``/healthz`` verdict.

PR 7 built the SIGNALS (MetricsRegistry, event journal, traces); this
module is what WATCHES them. Three pieces:

  - **Beacon + Watchdog** — a beacon is a cheap monotonic progress
    counter bumped by a hot loop (executor dispatch completion,
    serving batcher iteration, pserver barrier release, router
    request completion, prefetcher chunks). The watchdog daemon
    thread checks every armed watch each tick: a beacon that shows NO
    progress for ``deadline_s`` while its ``pending_fn`` reports work
    outstanding is a **stall** verdict — the silent-hang class that
    no exception ever reports.
    Declarative ``HealthRule``s over MetricsRegistry deltas catch the
    softer failures: recompile storms, throughput collapse vs a
    rolling baseline, queue saturation, anomaly-skip burn rate.
    Verdicts flow out three ways: a ``health`` journal event on every
    raise/clear, a ``health_state{role,reason}`` gauge, and the
    upgraded ``GET /healthz`` (export.py) that returns this module's
    ``healthz()`` payload instead of an unconditional 200.

  - **FlightRecorder** — the black box: a bounded ring of metric
    samples plus all-thread stack captures
    (``sys._current_frames``), dumped as ``blackbox.<role>.json``
    (stacks + journal tail + metrics + beacon ages) on SIGTERM, fatal
    error, or a watchdog stall verdict, so a SIGKILLed replica or a
    wedged dispatch leaves evidence a human (or ``tools/doctor.py``)
    can read after the fact. ``faulthandler`` is chained onto SIGTERM
    too, so even a process whose main thread is parked inside a C
    call (the observed ``jax.devices()`` hang) writes its stacks.

  - **healthz() / provider plumbing** — the process singleton
    watchdog backs ``GET /healthz``; 200 while healthy/degraded
    (degraded is advisory), 503 on an unhealthy verdict, body always
    the full JSON verdict.

``tools/doctor.py`` is the offline half: it turns journals + these
blackbox dumps into a ranked, evidence-cited root-cause verdict.

Cost posture: a beacon bump is one lock + int add per *dispatch/loop
iteration* (not per step); the watchdog is one daemon thread at
``interval_s`` (default 0.5 s) that reads counters. The
``health_overhead`` bench row (bench.py --all) keeps this < 2% on the
pipelined CPU probe.
"""

from __future__ import annotations

import collections
import json
import os
import sys
import threading
import time
import traceback
from typing import Callable, Dict, List, Optional

from . import journal as _journal
from .registry import MetricsRegistry, registry

__all__ = ["Beacon", "beacon", "beacons_snapshot", "HealthRule",
           "Watchdog", "FlightRecorder", "get_watchdog",
           "get_recorder", "set_blackbox_dir", "arm_process",
           "default_rules", "healthz", "register_control_provider"]

ENV_BLACKBOX_DIR = "PADDLE_TPU_BLACKBOX_DIR"

SEVERITY_RANK = {"healthy": 0, "degraded": 1, "unhealthy": 2}


# ---------------------------------------------------------------------------
# beacons
# ---------------------------------------------------------------------------

class Beacon:
    """A monotonic progress counter with a last-bump timestamp — the
    watchdog's cheapest input. Hot loops hold the object and ``bump()``
    once per unit of progress (one dispatch, one batch, one barrier
    release); cost is one lock + one int add."""

    __slots__ = ("name", "_mu", "_count", "_t_last")

    def __init__(self, name: str):
        self.name = name
        self._mu = threading.Lock()
        self._count = 0
        self._t_last = time.monotonic()

    def bump(self, n: int = 1):
        with self._mu:
            self._count += n
            self._t_last = time.monotonic()

    def read(self):
        """-> (count, monotonic time of last bump)."""
        with self._mu:
            return self._count, self._t_last

    @property
    def count(self) -> int:
        with self._mu:
            return self._count


_BEACONS: Dict[str, Beacon] = {}
_BEACONS_MU = threading.Lock()


def beacon(name: str) -> Beacon:
    """Process-wide named beacon (memoized). Components that need a
    private progress counter (one per Executor instance, say)
    construct ``Beacon`` directly and hand it to ``Watchdog.watch``."""
    b = _BEACONS.get(name)
    if b is not None:
        return b
    with _BEACONS_MU:
        b = _BEACONS.get(name)
        if b is None:
            b = _BEACONS[name] = Beacon(name)
        return b


def beacons_snapshot(now: Optional[float] = None) -> dict:
    """{name: {count, age_s}} for every registered process-wide
    beacon — part of every blackbox dump."""
    now = time.monotonic() if now is None else now
    with _BEACONS_MU:
        bs = list(_BEACONS.values())
    out = {}
    for b in bs:
        count, t_last = b.read()
        out[b.name] = {"count": count,
                       "age_s": round(now - t_last, 3)}
    return out


# ---------------------------------------------------------------------------
# declarative rules over MetricsRegistry deltas
# ---------------------------------------------------------------------------

def _metric_values(snapshot: dict, metric: str) -> List[float]:
    """Every series value of ``metric`` across label sets in a
    registry snapshot (counters + gauges tables)."""
    out = []
    for table in ("counters", "gauges"):
        for key, val in snapshot.get(table, {}).items():
            if key.split("{", 1)[0] == metric:
                out.append(float(val))
    return out


def _metric_total(snapshot: dict, metric: str):
    """Sum of every series of ``metric``; None when it has no series
    yet. The right reduction for RATE rules (aggregate throughput)."""
    vals = _metric_values(snapshot, metric)
    return sum(vals) if vals else None


class HealthRule:
    """One declarative check over MetricsRegistry deltas, evaluated
    each watchdog tick. Build via the classmethods:

      - ``rate_above(name, metric, per_s)`` — a counter's windowed
        rate exceeds ``per_s`` (recompile storm, anomaly-skip burn,
        shed burn);
      - ``rate_collapse(name, metric, frac)`` — a counter's windowed
        rate falls below ``frac`` of its rolling (EWMA) baseline after
        the baseline established itself (throughput collapse);
      - ``gauge_above(name, metric, threshold)`` — a gauge crossed a
        line (queue saturation, stall fraction).

    ``severity`` defaults to "degraded": rules are trend detectors;
    the hard "unhealthy" verdicts (and blackbox dumps) come from
    beacon stalls unless a rule opts in.
    """

    def __init__(self, name: str, kind: str, metric: str,
                 threshold: Optional[float] = None,
                 window_s: float = 30.0, frac: float = 0.25,
                 min_rate: float = 1.0, severity: str = "degraded"):
        if kind not in ("rate_above", "rate_collapse", "gauge_above"):
            raise ValueError("unknown HealthRule kind %r" % kind)
        if severity not in SEVERITY_RANK or severity == "healthy":
            raise ValueError("severity must be degraded|unhealthy")
        self.name = name
        self.kind = kind
        self.metric = metric
        self.threshold = threshold
        self.window_s = float(window_s)
        self.frac = float(frac)
        self.min_rate = float(min_rate)
        self.severity = severity
        self._samples: "collections.deque" = collections.deque()
        self._baseline: Optional[float] = None

    @classmethod
    def rate_above(cls, name, metric, per_s, window_s=30.0,
                   severity="degraded"):
        return cls(name, "rate_above", metric, threshold=float(per_s),
                   window_s=window_s, severity=severity)

    @classmethod
    def rate_collapse(cls, name, metric, frac=0.25, window_s=30.0,
                      min_rate=1.0, severity="degraded"):
        return cls(name, "rate_collapse", metric, frac=frac,
                   window_s=window_s, min_rate=min_rate,
                   severity=severity)

    @classmethod
    def gauge_above(cls, name, metric, threshold, severity="degraded"):
        return cls(name, "gauge_above", metric,
                   threshold=float(threshold), severity=severity)

    def _window_rate(self, now: float, value: float):
        self._samples.append((now, value))
        while len(self._samples) > 2 and \
                now - self._samples[0][0] > self.window_s:
            self._samples.popleft()
        t0, v0 = self._samples[0]
        dt = now - t0
        if dt <= 0 or len(self._samples) < 2:
            return None
        return max(0.0, (value - v0) / dt)

    def evaluate(self, snapshot: dict, now: float) -> Optional[dict]:
        """-> problem dict (reason/severity/kind/detail/value) or
        None while this rule holds."""
        if self.kind == "gauge_above":
            # per-series MAX, not sum: the threshold means "any one
            # queue/gauge crossed the line" — N healthy models must
            # not add up to a phantom saturation
            vals = _metric_values(snapshot, self.metric)
            if not vals:
                return None
            value = max(vals)
            if value >= self.threshold:
                return {"reason": self.name, "severity": self.severity,
                        "kind": "gauge_above", "metric": self.metric,
                        "value": value,
                        "detail": "%s=%.6g >= %.6g (worst of %d "
                        "series)" % (self.metric, value,
                                     self.threshold, len(vals))}
            return None
        value = _metric_total(snapshot, self.metric)
        if value is None:
            return None
        rate = self._window_rate(now, value)
        if rate is None:
            return None
        if self.kind == "rate_above":
            if rate > self.threshold:
                return {"reason": self.name, "severity": self.severity,
                        "kind": "rate_above", "metric": self.metric,
                        "value": round(rate, 6),
                        "detail": "%s rate %.3g/s > %.3g/s over %.0fs"
                        % (self.metric, rate, self.threshold,
                           self.window_s)}
            return None
        # rate_collapse: EWMA baseline tracks the achieved rate; a
        # live rate far under an established baseline is the collapse
        baseline = self._baseline
        collapsed = (baseline is not None and baseline >= self.min_rate
                     and rate < self.frac * baseline)
        if not collapsed:
            # don't learn the collapsed rate into the baseline — the
            # rule must keep remembering what "normal" looked like
            self._baseline = rate if baseline is None \
                else 0.8 * baseline + 0.2 * rate
        if collapsed:
            return {"reason": self.name, "severity": self.severity,
                    "kind": "rate_collapse", "metric": self.metric,
                    "value": round(rate, 6),
                    "baseline": round(baseline, 6),
                    "detail": "%s rate %.3g/s < %.0f%% of rolling "
                    "baseline %.3g/s" % (self.metric, rate,
                                         self.frac * 100, baseline)}
        return None


def default_rules() -> List[HealthRule]:
    """The stock rule set ``arm_process`` installs: recompile storm,
    training-throughput collapse, serving queue saturation,
    anomaly-skip burn rate, input-pipeline stall fraction."""
    return [
        HealthRule.rate_above("recompile_storm",
                              "executor_compiles_total",
                              per_s=0.5, window_s=60.0),
        HealthRule.rate_collapse("throughput_collapse",
                                 "executor_steps_total",
                                 frac=0.25, window_s=30.0,
                                 min_rate=1.0),
        HealthRule.gauge_above("queue_saturation",
                               "serving_queue_depth", threshold=256),
        HealthRule.rate_above("anomaly_skip_burn",
                              "guard_skipped_steps", per_s=0.5,
                              window_s=60.0),
        HealthRule.gauge_above("input_bound",
                               "input_stall_fraction", threshold=0.5),
    ]


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------

class _Watch:
    """One armed beacon watch. Stall = no count change for
    ``deadline_s`` while ``pending_fn`` (if any) reports work
    outstanding for that whole window."""

    def __init__(self, name, beacon_, deadline_s, pending_fn):
        self.name = name
        self.beacon = beacon_
        self.deadline_s = float(deadline_s)
        self.pending_fn = pending_fn
        count, _ = beacon_.read()
        now = time.monotonic()
        self.last_count = count
        self.last_progress_t = now
        self.pending_since: Optional[float] = None

    def check(self, now: float) -> Optional[dict]:
        count, _ = self.beacon.read()
        if count != self.last_count:
            self.last_count = count
            self.last_progress_t = now
            self.pending_since = None
            return None
        if self.pending_fn is None:
            # unconditional watch: the clock is simply last progress
            stalled_for = now - self.last_progress_t
        else:
            try:
                pending = bool(self.pending_fn())
            except Exception:
                pending = False  # a dying owner must not wedge ticks
            if not pending:
                self.pending_since = None
                return None
            if self.pending_since is None:
                # conservative: the clock starts when pending is
                # first OBSERVED, never retroactively
                self.pending_since = now
            stalled_for = now - max(self.last_progress_t,
                                    self.pending_since)
        if stalled_for < self.deadline_s:
            return None
        return {"reason": "stall:%s" % self.name,
                "severity": "unhealthy", "kind": "stall",
                "watch": self.name, "count": count,
                "stalled_s": round(stalled_for, 3),
                "deadline_s": self.deadline_s,
                "detail": "no progress on %s for %.1fs (deadline "
                "%.1fs) with work pending; count=%d"
                % (self.name, stalled_for, self.deadline_s, count)}

    def snapshot(self, now: float) -> dict:
        count, t_last = self.beacon.read()
        return {"count": count,
                "age_s": round(now - t_last, 3),
                "deadline_s": self.deadline_s,
                "pending_since_s": round(now - self.pending_since, 3)
                if self.pending_since is not None else None}


class Watchdog:
    """The per-role health daemon: a thread that ticks every
    ``interval_s``, checks every armed ``watch`` and ``HealthRule``,
    and on every raise/clear transition emits a ``health`` journal
    event and updates the ``health_state{role,reason}`` gauge. A NEW
    unhealthy problem additionally triggers every attached
    ``FlightRecorder`` (one dump per problem until it clears) and any
    ``on_unhealthy`` callbacks.

    The thread starts lazily with the first watch/rule and is a
    daemon — a watchdog never keeps a process alive."""

    def __init__(self, role: Optional[str] = None,
                 interval_s: float = 0.5,
                 registry_: Optional[MetricsRegistry] = None):
        self.role = role
        self.interval_s = float(interval_s)
        self._reg = registry_ or registry()
        self._mu = threading.Lock()
        self._watches: List[_Watch] = []
        self._rules: List[HealthRule] = []
        self._recorders: List["FlightRecorder"] = []
        self._callbacks: List[Callable[[dict], None]] = []
        self._active: Dict[str, dict] = {}   # reason -> problem
        self._dumped: set = set()            # reasons already dumped
        self._verdict = self._make_verdict([], time.monotonic())
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # serializes whole evaluations: check_now is called from the
        # daemon tick AND /healthz handler threads, and rule
        # window/baseline state + the raise/clear diff must never be
        # computed by two threads interleaved
        self._eval_mu = threading.Lock()
        self._tick_count = 0
        self._snap_cache: Optional[dict] = None

    # -- arming -------------------------------------------------------
    def watch(self, name: str, beacon: Optional[Beacon] = None,
              deadline_s: float = 30.0,
              pending_fn: Optional[Callable[[], bool]] = None):
        """Arm a stall watch; returns the handle to pass to
        ``unwatch``. ``beacon`` defaults to the process-wide beacon of
        the same name. A name already armed gets a ``#2``/``#3``
        suffix so two same-named components (two engines hosting model
        "default", say) never shadow each other's stall reason."""
        b = beacon if beacon is not None else globals()["beacon"](name)
        with self._mu:
            taken = {w.name for w in self._watches}
            unique, k = name, 2
            while unique in taken:
                unique = "%s#%d" % (name, k)
                k += 1
            w = _Watch(unique, b, deadline_s, pending_fn)
            self._watches.append(w)
        self._ensure_thread()
        return w

    def unwatch(self, handle):
        with self._mu:
            if handle in self._watches:
                self._watches.remove(handle)

    def add_rule(self, rule: HealthRule):
        with self._mu:
            self._rules.append(rule)
        self._ensure_thread()
        return rule

    def remove_rule(self, rule: HealthRule):
        with self._mu:
            if rule in self._rules:
                self._rules.remove(rule)

    def attach_recorder(self, recorder: "FlightRecorder"):
        with self._mu:
            if recorder not in self._recorders:
                self._recorders.append(recorder)

    def on_unhealthy(self, fn: Callable[[dict], None]):
        with self._mu:
            self._callbacks.append(fn)

    # -- lifecycle ----------------------------------------------------
    def _ensure_thread(self):
        with self._mu:
            if self._thread is not None and self._thread.is_alive():
                return
            self._stop = threading.Event()
            self._thread = threading.Thread(
                target=self._loop, daemon=True,
                name="health-watchdog")
            self._thread.start()

    def start(self):
        self._ensure_thread()
        return self

    def stop(self):
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=5)
        with self._mu:
            self._thread = None

    def _loop(self):
        while not self._stop.wait(self.interval_s):
            try:
                self.check_now()
                self._feed_recorders()
            except Exception:
                # the watchdog must never take the process down
                pass

    def _feed_recorders(self):
        """Per-tick black-box feeding: every attached recorder gets a
        metric sample each tick, and a stack capture every ~20 ticks
        (the pre-wedge trajectory a post-mortem dump replays). Daemon
        tick only — /healthz scrapes must not skew the ring cadence."""
        with self._mu:
            recorders = list(self._recorders)
            self._tick_count += 1
            nth = self._tick_count
        if not recorders:
            return
        # reuse the snapshot check_now just took for its rules (one
        # registry walk per tick, not two); rule-less watchdogs still
        # sample fresh
        snap, self._snap_cache = self._snap_cache, None
        if snap is None:
            snap = self._reg.snapshot()
        for rec in recorders:
            rec.sample(snap)
            if nth % 20 == 0:
                rec.capture_stacks()

    # -- evaluation ---------------------------------------------------
    def _make_verdict(self, problems: List[dict], now: float) -> dict:
        worst = "healthy"
        for p in problems:
            if SEVERITY_RANK[p["severity"]] > SEVERITY_RANK[worst]:
                worst = p["severity"]
        return {"state": worst,
                "role": self.role or _journal.get_role(),
                "t_wall": time.time(),
                "problems": list(problems),
                "watches": {w.name: w.snapshot(now)
                            for w in self._watches},
                "rules": [r.name for r in self._rules]}

    def check_now(self, rules: bool = True) -> dict:
        """Run one evaluation synchronously (the /healthz path and
        tests use this; the daemon thread calls it every tick).
        Evaluations are serialized: concurrent scrapes must not
        interleave inside rule window state or double-report a
        raise/clear transition. ``rules=False`` (the /healthz scrape
        path) re-checks only the stall watches and CARRIES the last
        tick's rule verdicts unchanged — rule windows/EWMA baselines
        must adapt at the daemon cadence, not at whatever frequency
        an external scraper happens to probe."""
        with self._eval_mu:
            return self._check_locked(rules)

    def _check_locked(self, rules_fresh: bool = True) -> dict:
        now = time.monotonic()
        with self._mu:
            watches = list(self._watches)
            rules = list(self._rules)
        problems = []
        for w in watches:
            p = w.check(now)
            if p is not None:
                problems.append(p)
        if rules and rules_fresh:
            snap = self._reg.snapshot()
            self._snap_cache = snap  # _feed_recorders reuses it
            for r in rules:
                p = r.evaluate(snap, now)
                if p is not None:
                    problems.append(p)
        elif rules:
            # scrape path: carry the daemon's last rule verdicts
            with self._mu:
                problems.extend(
                    p for p in self._active.values()
                    if p["kind"] != "stall")
        with self._mu:
            previous = self._active
            self._active = {p["reason"]: p for p in problems}
            raised = [p for p in problems
                      if p["reason"] not in previous]
            cleared = [p for r, p in previous.items()
                       if r not in self._active]
            for p in cleared:
                self._dumped.discard(p["reason"])
            verdict = self._make_verdict(problems, now)
            self._verdict = verdict
            recorders = list(self._recorders)
            callbacks = list(self._callbacks)
        role = verdict["role"]
        reg = self._reg
        for p in raised:
            _journal.emit("health", action="raise",
                          reason=p["reason"],
                          severity=p["severity"],
                          problem_kind=p["kind"],
                          detail=p.get("detail"))
            reg.gauge("health_state", role=role,
                      reason=p["reason"]).set(
                SEVERITY_RANK[p["severity"]])
        for p in cleared:
            _journal.emit("health", action="clear",
                          reason=p["reason"],
                          severity=p["severity"],
                          problem_kind=p["kind"])
            reg.gauge("health_state", role=role,
                      reason=p["reason"]).set(0.0)
        reg.gauge("health_state", role=role, reason="overall").set(
            SEVERITY_RANK[verdict["state"]])
        for p in raised:
            if p["severity"] != "unhealthy":
                continue
            with self._mu:
                if p["reason"] in self._dumped:
                    continue
                self._dumped.add(p["reason"])
            for rec in recorders:
                try:
                    rec.dump("watchdog:%s" % p["reason"],
                             extra={"verdict": verdict})
                except Exception:
                    pass
            for cb in callbacks:
                try:
                    cb(p)
                except Exception:
                    pass
        return verdict

    def verdict(self) -> dict:
        """The most recent verdict (no fresh evaluation)."""
        with self._mu:
            return dict(self._verdict)


# ---------------------------------------------------------------------------
# flight recorder (the black box)
# ---------------------------------------------------------------------------

def _capture_stacks() -> List[dict]:
    """All-thread stacks via sys._current_frames — the wedge evidence
    a SIGKILL would otherwise destroy."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = []
    for tid, frame in sys._current_frames().items():
        out.append({
            "thread_id": tid,
            "name": names.get(tid, "?"),
            "frames": [ln.rstrip("\n") for ln in
                       traceback.format_stack(frame)],
        })
    return out


class FlightRecorder:
    """Bounded in-memory ring of recent metric samples + periodic
    stack captures, dumped as ``blackbox.<role>.json`` on demand.
    Attach to a ``Watchdog`` (it samples each tick and dumps on a
    stall verdict) and/or ``install_signal_handlers()`` for the
    SIGTERM / fatal-error paths. With no ``dir`` (and no
    ``PADDLE_TPU_BLACKBOX_DIR``) the ring still fills but ``dump``
    is a no-op returning None."""

    def __init__(self, role: Optional[str] = None,
                 dir: Optional[str] = None, capacity: int = 128,
                 stack_history: int = 4,
                 registry_: Optional[MetricsRegistry] = None):
        self.role = role
        self.dir = dir if dir is not None \
            else os.environ.get(ENV_BLACKBOX_DIR) or None
        self._reg = registry_ or registry()
        self._mu = threading.Lock()
        self._samples: "collections.deque" = collections.deque(
            maxlen=int(capacity))
        self._stacks: "collections.deque" = collections.deque(
            maxlen=int(stack_history))
        self._dump_count = 0
        self._reasons: List[str] = []
        self._prev_sigterm = None
        self._prev_excepthook = None
        self._fault_file = None
        self._in_dump = False
        self._signals_installed = False

    def set_dir(self, dir: Optional[str]):
        self.dir = dir
        return self

    # -- sampling -----------------------------------------------------
    def sample(self, snapshot: Optional[dict] = None):
        """Append one metric sample to the ring (the watchdog calls
        this each tick when attached; callers may too)."""
        snap = snapshot if snapshot is not None \
            else self._reg.snapshot()
        lite = {"t_wall": time.time(),
                "counters": snap.get("counters", {}),
                "gauges": snap.get("gauges", {})}
        with self._mu:
            self._samples.append(lite)

    def capture_stacks(self):
        stacks = _capture_stacks()
        with self._mu:
            self._stacks.append({"t_wall": time.time(),
                                 "stacks": stacks})
        return stacks

    # -- dumping ------------------------------------------------------
    def dump_path(self) -> Optional[str]:
        if not self.dir:
            return None
        role = self.role or _journal.get_role()
        return os.path.join(self.dir,
                            "blackbox.%s.json" % role)

    def dump(self, reason: str, extra: Optional[dict] = None,
             path: Optional[str] = None) -> Optional[str]:
        """Write the black box: fresh all-thread stacks, the stack
        history ring, the metric-sample ring + a final registry
        snapshot, the journal tail, beacon ages, and every reason
        this process dumped so far. Atomic (tmp + rename); returns
        the path, or None when no directory is configured."""
        with self._mu:
            if self._in_dump:
                return None  # re-entrant signal during a dump
            self._in_dump = True
        try:
            out = path or self.dump_path()
            if out is None:
                return None
            role = self.role or _journal.get_role()
            with self._mu:
                self._dump_count += 1
                self._reasons.append(reason)
                samples = list(self._samples)
                stack_hist = list(self._stacks)
                count = self._dump_count
                reasons = list(self._reasons)
            box = {
                "role": role,
                "pid": os.getpid(),
                "reason": reason,
                "reasons": reasons,
                "dump_count": count,
                "t_wall": time.time(),
                "t_mono": time.monotonic(),
                "argv": list(sys.argv),
                "stacks": _capture_stacks(),
                "stack_history": stack_hist,
                "beacons": beacons_snapshot(),
                "metrics": self._reg.snapshot(),
                "metric_samples": samples,
                "journal_tail": _journal.events()[-256:],
                "extra": extra or {},
            }
            os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
            tmp = out + ".tmp.%d" % os.getpid()
            with open(tmp, "w") as f:
                json.dump(box, f, default=repr)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, out)
            _journal.emit("blackbox_dump", reason=reason, path=out)
            return out
        finally:
            with self._mu:
                self._in_dump = False

    # -- hooks --------------------------------------------------------
    def install_signal_handlers(self):
        """SIGTERM -> dump then chain to the previous handler (or the
        default die). Additionally registers ``faulthandler`` on
        SIGTERM writing ``blackbox.<role>.stacks.txt``: the
        C-level dump fires even when the main thread is wedged inside
        a C call where no Python handler can run (a backend init that
        never returns). Must be called from the main
        thread; returns False (and does nothing) elsewhere."""
        import signal
        if threading.current_thread() is not threading.main_thread():
            return False
        if getattr(self, "_signals_installed", False):
            # idempotent: repeated arm_process must not chain N dump
            # layers onto one SIGTERM or leak stacks-file handles
            return True
        self._signals_installed = True

        def _on_term(signum, frame):
            # dump on a HELPER thread with a bounded join: the handler
            # runs on the main thread, and if the signal interrupted a
            # frame that holds journal._MU (emit's critical section) a
            # same-thread dump would deadlock on its own lock. The
            # helper blocks instead; on timeout the handler proceeds
            # (the interrupted frame releases the lock once the
            # handler returns, and the daemon helper finishes the dump
            # if the chained handler doesn't exit first).
            try:
                t = threading.Thread(target=self.dump,
                                     args=("SIGTERM",), daemon=True)
                t.start()
                t.join(timeout=10.0)
            except Exception:
                pass
            prev = self._prev_sigterm
            if callable(prev):
                prev(signum, frame)
            elif prev == signal.SIG_IGN:
                return
            else:
                signal.signal(signal.SIGTERM, signal.SIG_DFL)
                os.kill(os.getpid(), signal.SIGTERM)

        self._prev_sigterm = signal.signal(signal.SIGTERM, _on_term)
        if self.dir:
            try:
                import faulthandler
                os.makedirs(self.dir, exist_ok=True)
                role = self.role or _journal.get_role()
                self._fault_file = open(
                    os.path.join(self.dir,
                                 "blackbox.%s.stacks.txt" % role),
                    "w")
                faulthandler.register(signal.SIGTERM,
                                      file=self._fault_file,
                                      chain=True)
            except Exception:
                pass
        return True

    def install_excepthook(self):
        """Uncaught-exception (fatal error) path: dump, then defer to
        the previous hook."""
        prev = sys.excepthook

        def _hook(tp, val, tb):
            try:
                self.dump("fatal:%s" % getattr(tp, "__name__", tp),
                          extra={"error": repr(val)})
            except Exception:
                pass
            prev(tp, val, tb)

        self._prev_excepthook = prev
        sys.excepthook = _hook
        return True


# ---------------------------------------------------------------------------
# process singletons + /healthz
# ---------------------------------------------------------------------------

# REENTRANT by design: get_watchdog() calls get_recorder() under it,
# and a future accessor / watchdog callback reached from inside one of
# these MUST NOT deadlock the way the CLI path once did (a plain Lock
# here wedged `doctor`-adjacent tooling but never pytest, because
# pytest happened to create the recorder first). Hardened PR 11 —
# regression-tested by test_health.py::TestSingletonReentrancy.
_SINGLETON_MU = threading.RLock()
_WATCHDOG: Optional[Watchdog] = None
_RECORDER: Optional[FlightRecorder] = None


def get_watchdog(role: Optional[str] = None,
                 interval_s: float = 0.5) -> Watchdog:
    """The process-wide watchdog every runtime component arms its
    watches on (created lazily; the singleton recorder is attached so
    stall verdicts leave a black box whenever a dump dir is
    configured)."""
    global _WATCHDOG
    wd = _WATCHDOG
    if wd is not None:
        return wd
    with _SINGLETON_MU:
        if _WATCHDOG is None:
            # safe under the (reentrant) singleton lock — this nested
            # acquisition is exactly the shape that used to deadlock
            rec = get_recorder()
            _WATCHDOG = Watchdog(role=role, interval_s=interval_s)
            _WATCHDOG.attach_recorder(rec)
        return _WATCHDOG


def get_recorder() -> FlightRecorder:
    """The process-wide flight recorder (dump dir from
    ``PADDLE_TPU_BLACKBOX_DIR`` unless ``set_blackbox_dir`` points it
    elsewhere)."""
    global _RECORDER
    rec = _RECORDER
    if rec is not None:
        return rec
    with _SINGLETON_MU:
        if _RECORDER is None:
            _RECORDER = FlightRecorder()
        return _RECORDER


def set_blackbox_dir(dir: Optional[str]) -> FlightRecorder:
    """Point the singleton recorder's dumps at ``dir`` (tests, tools
    and the launcher use this; env ``PADDLE_TPU_BLACKBOX_DIR`` is the
    fleet-wide way)."""
    return get_recorder().set_dir(dir)


def arm_process(role: Optional[str] = None,
                blackbox_dir: Optional[str] = None,
                rules: bool = True, signals: bool = True,
                excepthook: bool = False):
    """One-call arming for a worker process: role stamp, singleton
    watchdog + default rules, flight recorder (+ SIGTERM hook when on
    the main thread). Returns (watchdog, recorder). Idempotent-ish:
    repeated calls reuse the singletons (rules are only installed
    once)."""
    if role is not None:
        _journal.set_role(role)
    rec = get_recorder()
    if blackbox_dir is not None:
        rec.set_dir(blackbox_dir)
    wd = get_watchdog(role=role)
    if rules and not wd._rules:
        for r in default_rules():
            wd.add_rule(r)
    if signals:
        rec.install_signal_handlers()
    if excepthook:
        rec.install_excepthook()
    wd.start()
    return wd, rec


# the control plane (observability/control.py) registers its
# control_block() here so /healthz can show WHAT IS ACTING on this
# process next to what is being watched — armed policies, recent
# ledger entries, suppression counts
_CONTROL_PROVIDER: Optional[Callable[[], dict]] = None


def register_control_provider(fn: Optional[Callable[[], dict]]):
    """Install (or with ``None`` clear) the callable whose dict lands
    in the ``control`` block of every ``healthz()`` payload."""
    global _CONTROL_PROVIDER
    _CONTROL_PROVIDER = fn
    return fn


def _attach_control(verdict: dict) -> dict:
    prov = _CONTROL_PROVIDER
    if prov is not None:
        try:
            verdict["control"] = prov()
        except Exception:
            verdict["control"] = {"error": "control provider raised"}
    return verdict


def healthz():
    """The ``GET /healthz`` payload: (http_status, verdict_dict).
    200 while healthy/degraded (degraded is advisory — the process is
    making progress), 503 on an unhealthy verdict, and 200/"unknown"
    when no watchdog was ever armed in this process (nothing is
    watching, which is itself worth surfacing to the scraper). When a
    control plane is armed the payload grows a ``control`` block
    (armed policies, recent actions, suppressions)."""
    wd = _WATCHDOG
    if wd is None:
        return 200, _attach_control(
            {"state": "unknown",
             "role": _journal.get_role(),
             "detail": "no watchdog armed in this process"})
    # rules=False: a scrape re-checks the stall watches (cheap,
    # idempotent) but must not feed rule windows/baselines — external
    # probe frequency must never change detection sensitivity
    v = _attach_control(wd.check_now(rules=False))
    return (503 if v["state"] == "unhealthy" else 200), v
