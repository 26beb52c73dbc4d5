"""Error-enforcement idiom.

TPU-native analog of the reference's ``PADDLE_ENFORCE`` family
(reference: paddle/fluid/platform/enforce.h). Errors carry the same
error categories so user-facing messages are comparable, but raise
normal Python exceptions (there is no C++/Python boundary to marshal
across in the hot path — the whole step is one compiled XLA program).
"""

from __future__ import annotations


class EnforceNotMet(RuntimeError):
    """Base framework error (reference: enforce.h EnforceNotMet)."""


class InvalidArgumentError(EnforceNotMet, ValueError):
    pass


class NotFoundError(EnforceNotMet, KeyError):
    pass


class OutOfRangeError(EnforceNotMet, IndexError):
    pass


class AlreadyExistsError(EnforceNotMet):
    pass


class PreconditionNotMetError(EnforceNotMet):
    pass


class UnimplementedError(EnforceNotMet, NotImplementedError):
    pass


class UnavailableError(EnforceNotMet):
    """Resource/service exists but cannot be used right now (reference:
    platform/errors.h UNAVAILABLE)."""
    pass


def enforce(cond, msg="", *args, exc=InvalidArgumentError):
    """PADDLE_ENFORCE analog: raise ``exc`` with ``msg % args`` if not cond."""
    if not cond:
        raise exc(msg % args if args else msg)


def enforce_not_none(val, name=""):
    if val is None:
        raise NotFoundError("expected %r to be set, got None" % name)
    return val
