"""Deterministic, seed-driven fault injection.

Every robustness claim in this subsystem is mechanically checkable: the
injector plants the exact failures the guarded trainer must survive —

  - ``nan_grad_at(step)``       poison one feed tensor with NaN so the
                                backward pass produces non-finite grads
                                at precisely that step (model-agnostic:
                                a NaN input NaNs the loss and every
                                gradient downstream);
  - ``transient_dispatch_at``   raise a PJRT-shaped UNAVAILABLE error
                                from the dispatch, ``times`` attempts
                                in a row (tests the retry classifier
                                and the backoff budget);
  - ``crash_save_at(step)``     kill the checkpoint writer after N data
                                files — the preemption/power-loss model
                                for the durability ordering in
                                ``io.CheckpointSaver._write`` (the crash
                                must strand an invisible tmp dir, never
                                a visible torn checkpoint).

Hooks are consumed by ``GuardedTrainer`` (``mutate_feed`` /
``before_dispatch`` / ``attach_saver``) and by ``tools/chaos_run.py``.
The injector records everything it does in ``events`` so a chaos run's
summary can prove the faults actually fired.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np


class InjectedDispatchError(ConnectionError):
    """Stand-in for a transient PJRT dispatch/transfer failure (the
    retry classifier treats it as transient by type AND by its
    UNAVAILABLE message, the status a real PJRT failure carries)."""


class SimulatedCrash(RuntimeError):
    """Stand-in for a process kill (SIGKILL/preemption) mid-operation.
    NOT transient: a killed writer doesn't come back."""


class FaultInjector:
    def __init__(self, seed: int = 0):
        self.seed = int(seed)
        self._rng = np.random.RandomState(self.seed)
        self._nan_feeds: Dict[int, Optional[str]] = {}
        self._dispatch: Dict[int, int] = {}
        self._crash_saves: Dict[int, int] = {}
        self.events: List[Tuple] = []

    # -- arming --------------------------------------------------------
    def nan_grad_at(self, *steps, feed_name: Optional[str] = None):
        """Poison the named (or first float, alphabetically) feed
        tensor at each given step — once per step."""
        for s in steps:
            self._nan_feeds[int(s)] = feed_name
        return self

    def transient_dispatch_at(self, step: int, times: int = 1):
        """Fail the first ``times`` dispatch attempts of ``step``."""
        self._dispatch[int(step)] = int(times)
        return self

    def crash_save_at(self, step: int, after_files: int = 1):
        """Kill the checkpoint write issued at ``step`` after
        ``after_files`` data files have reached the tmp dir."""
        self._crash_saves[int(step)] = int(after_files)
        return self

    # -- hooks ---------------------------------------------------------
    def mutate_feed(self, step: int, feed: Dict) -> Dict:
        if step not in self._nan_feeds:
            return feed
        name = self._nan_feeds.pop(step)
        if name is None:
            floats = sorted(
                k for k, v in feed.items()
                if np.issubdtype(np.asarray(v).dtype, np.floating))
            if not floats:
                return feed
            name = floats[0]
        arr = np.array(feed[name], dtype=np.asarray(feed[name]).dtype,
                       copy=True)
        # one seed-chosen element is enough — isfinite reduces over the
        # whole tensor, and a single NaN input poisons every grad it
        # touches (a full-NaN tensor would be an easier, less honest
        # test)
        flat = arr.reshape(-1)
        flat[int(self._rng.randint(flat.size))] = np.nan
        feed = dict(feed)
        feed[name] = arr
        self.events.append(("nan_grad", step, name))
        return feed

    def before_dispatch(self, step: int):
        """Raise if a dispatch fault is armed for this step (each call
        consumes one armed failure)."""
        remaining = self._dispatch.get(step, 0)
        if remaining > 0:
            self._dispatch[step] = remaining - 1
            self.events.append(("transient_dispatch", step))
            raise InjectedDispatchError(
                "UNAVAILABLE: injected transient dispatch failure "
                "(step %d)" % step)

    def attach_saver(self, saver):
        """Arm a CheckpointSaver: its per-file write hook raises
        SimulatedCrash once ``after_files`` files of a crash-armed
        step's checkpoint have been written (the writer thread dies
        exactly as a preempted process would — mid-tmp-dir)."""
        injector = self

        def hook(step, name, index):
            after = injector._crash_saves.get(int(step))
            if after is not None and index + 1 >= after:
                injector._crash_saves.pop(int(step))
                injector.events.append(("crash_save", int(step), name))
                raise SimulatedCrash(
                    "injected writer kill after %d file(s) of "
                    "ckpt-%d" % (index + 1, step))

        saver._write_file_hook = hook
        return saver

    # -- reporting -----------------------------------------------------
    def summary(self) -> Dict:
        return {
            "seed": self.seed,
            "events": [list(e) for e in self.events],
            "unfired": {
                "nan_grad": sorted(self._nan_feeds),
                "transient_dispatch": sorted(
                    s for s, n in self._dispatch.items() if n > 0),
                "crash_save": sorted(self._crash_saves),
            },
        }


def make_torn_checkpoint(dirname: str, step: int, marker: str,
                         nbytes: int = 64):
    """Craft the on-disk wreckage of a pre-durability-fix power loss: a
    marked checkpoint dir whose tensor files are truncated garbage.
    ``restore_latest`` must fall back past it (tests only — the fixed
    write ordering can no longer produce this shape, but old
    checkpoints in the wild can)."""
    import os
    d = os.path.join(dirname, "ckpt-%d" % step)
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "torn_tensor"), "wb") as f:
        f.write(b"\x00" * nbytes)
    with open(os.path.join(d, marker), "w") as f:
        f.write(str(step))
    return d
