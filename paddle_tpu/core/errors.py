"""Typed error hierarchy + enforce helpers.

TPU-native equivalent of the reference's PADDLE_ENFORCE machinery
(/root/reference/paddle/fluid/platform/enforce.h:440,505 and errors.h /
error_codes.proto). The reference formats typed error codes with stack
traces from C++ macros; here errors are Python exception classes with the
same hierarchy so user-facing behavior matches, and `enforce*` helpers give
call sites the same one-liner ergonomics.
"""
from __future__ import annotations


class EnforceNotMet(RuntimeError):
    """Base of all framework errors (reference: platform::EnforceNotMet)."""


class InvalidArgumentError(EnforceNotMet, ValueError):
    pass


class NotFoundError(EnforceNotMet, KeyError):
    pass


class OutOfRangeError(EnforceNotMet, IndexError):
    pass


class AlreadyExistsError(EnforceNotMet):
    pass


class ResourceExhaustedError(EnforceNotMet, MemoryError):
    pass


class PreconditionNotMetError(EnforceNotMet):
    pass


class PermissionDeniedError(EnforceNotMet, PermissionError):
    pass


class ExecutionTimeoutError(EnforceNotMet, TimeoutError):
    pass


class UnimplementedError(EnforceNotMet, NotImplementedError):
    pass


class UnavailableError(EnforceNotMet):
    pass


class FatalError(EnforceNotMet):
    pass


class ExternalError(EnforceNotMet):
    """Error from an external library (XLA / PJRT), reference enforce.h:976."""


def enforce(cond, msg="", exc=InvalidArgumentError):
    """PADDLE_ENFORCE equivalent (enforce.h:440)."""
    if not cond:
        raise exc(msg if msg else "Enforce failed.")


def enforce_eq(a, b, msg="", exc=InvalidArgumentError):
    if a != b:
        raise exc(f"Expected {a!r} == {b!r}. {msg}")


def enforce_ne(a, b, msg="", exc=InvalidArgumentError):
    if a == b:
        raise exc(f"Expected {a!r} != {b!r}. {msg}")


def enforce_gt(a, b, msg="", exc=InvalidArgumentError):
    if not a > b:
        raise exc(f"Expected {a!r} > {b!r}. {msg}")


def enforce_ge(a, b, msg="", exc=InvalidArgumentError):
    if not a >= b:
        raise exc(f"Expected {a!r} >= {b!r}. {msg}")


def enforce_lt(a, b, msg="", exc=InvalidArgumentError):
    if not a < b:
        raise exc(f"Expected {a!r} < {b!r}. {msg}")


def enforce_le(a, b, msg="", exc=InvalidArgumentError):
    if not a <= b:
        raise exc(f"Expected {a!r} <= {b!r}. {msg}")


def enforce_not_none(x, name="value", msg="", exc=NotFoundError):
    if x is None:
        raise exc(f"{name} should not be None. {msg}")
    return x
