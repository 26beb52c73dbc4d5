"""Retry/backoff for transient dispatch and transfer failures.

Reference analog: the Fluid parameter-server runtime retries RPCs to a
restarting pserver (grpc_client retry loops, listen_and_serv's
reconnect) — the model script never sees a transient network burp. Here
the transient surface is PJRT and the RPC plane: a dispatch can fail
with UNAVAILABLE/DEADLINE_EXCEEDED, a device-to-host transfer or an RPC
can hit a reset connection. Those are retryable; a shape mismatch or an
OOM is not.

Classification is by exception TYPE NAME + message pattern: one
``XlaRuntimeError`` type carries both transient and permanent status
codes, and the fault harness and RPC plane raise their own types.

Backoff is exponential with deterministic, seed-driven jitter (the
fault-injection harness demands reproducible schedules): attempt ``k``
sleeps ``min(max_delay, base * 2**k) * (1 + jitter * u_k)`` with ``u_k``
drawn from a ``numpy.random.RandomState(seed)``.
"""

from __future__ import annotations

import time
from typing import Callable, List, Optional, Tuple

import numpy as np

from ..core.enforce import EnforceNotMet

# Message substrings that mark an exception as transient when its type
# alone is ambiguous (XlaRuntimeError carries both transient and
# permanent gRPC codes).
TRANSIENT_MESSAGE_PATTERNS = (
    "UNAVAILABLE",
    "DEADLINE_EXCEEDED",
    "ABORTED",
    "CANCELLED",
    "connection reset",
    "connection refused",
    "broken pipe",
    "socket closed",
    "failed to connect",
    "transfer to device failed",
    "transfer from device failed",
    # a dispatch that died AFTER donation consumed its input buffers
    # leaves the scope holding deleted arrays; the retry is viable
    # only because GuardedTrainer._on_retry restores the latest
    # checkpoint when it sees this pattern — classifying it permanent
    # would crash the run with no final checkpoint instead
    "has been deleted",
    "donated buffer",
)

# Exception type names that are transient regardless of message.
TRANSIENT_TYPE_NAMES = (
    "ConnectionError",
    "ConnectionResetError",
    "ConnectionAbortedError",
    "BrokenPipeError",
    "TimeoutError",
    "InjectedDispatchError",  # the fault harness's stand-in
)

# Type names that MAY be transient — decided by message pattern.
AMBIGUOUS_TYPE_NAMES = ("XlaRuntimeError", "RpcError", "OSError",
                        "RuntimeError")

# Structured TERMINAL outcomes of the distributed runtime: their
# messages contain words like ABORTED that would otherwise satisfy the
# pattern classifier, but retrying them is never correct (an evicted
# trainer stays evicted; an aborted barrier stays aborted).
PERMANENT_TYPE_NAMES = ("BarrierAborted", "TrainerEvicted",
                        "SimulatedCrash")


def is_transient(exc: BaseException) -> bool:
    """True when retrying the dispatch could plausibly succeed."""
    if isinstance(exc, EnforceNotMet):
        return False  # framework-detected misuse never heals by itself
    names = {t.__name__ for t in type(exc).__mro__}
    if names & set(PERMANENT_TYPE_NAMES):
        return False
    if names & set(TRANSIENT_TYPE_NAMES):
        return True
    if names & set(AMBIGUOUS_TYPE_NAMES):
        msg = str(exc).lower()
        return any(p.lower() in msg
                   for p in TRANSIENT_MESSAGE_PATTERNS)
    return False


class RetryPolicy:
    """Budgeted exponential backoff with deterministic jitter."""

    def __init__(self, max_retries: int = 3, base_delay: float = 0.5,
                 max_delay: float = 30.0, jitter: float = 0.25,
                 seed: int = 0,
                 classify: Callable[[BaseException], bool] = None):
        self.max_retries = int(max_retries)
        self.base_delay = float(base_delay)
        self.max_delay = float(max_delay)
        self.jitter = float(jitter)
        self.seed = int(seed)
        self.classify = classify or is_transient

    def delays(self) -> List[float]:
        """The full deterministic backoff schedule (one delay per
        retry) — exposed so tests and the chaos report can print it."""
        rng = np.random.RandomState(self.seed)
        out = []
        for k in range(self.max_retries):
            d = min(self.max_delay, self.base_delay * (2.0 ** k))
            out.append(d * (1.0 + self.jitter * float(rng.rand())))
        return out


class RetryBudgetExhausted(RuntimeError):
    """All retries consumed. ``.attempts`` lists every failure."""

    def __init__(self, attempts):
        self.attempts = attempts
        last = attempts[-1][1] if attempts else None
        super().__init__(
            "retry budget exhausted after %d attempt(s); last: %r"
            % (len(attempts), last))


def retry_call(fn: Callable, policy: Optional[RetryPolicy] = None,
               on_retry: Callable[[int, BaseException, float], None]
               = None, sleep: Callable[[float], None] = time.sleep
               ) -> Tuple[object, int]:
    """Call ``fn`` with the policy's budget. Returns ``(result,
    retries_used)``. Non-transient exceptions propagate immediately;
    transient ones consume the budget and end in
    ``RetryBudgetExhausted`` (whose ``__cause__`` is the last
    failure)."""
    policy = policy or RetryPolicy()
    delays = policy.delays()
    attempts = []
    for attempt in range(policy.max_retries + 1):
        try:
            return fn(), attempt
        except BaseException as e:
            if not policy.classify(e):
                raise
            attempts.append((attempt, e))
            if attempt >= policy.max_retries:
                err = RetryBudgetExhausted(attempts)
                raise err from e
            delay = delays[attempt]
            if on_retry is not None:
                on_retry(attempt, e, delay)
            if delay > 0:
                sleep(delay)
